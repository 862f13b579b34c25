"""Executors: the pure-jnp reference oracle and the stitched runtime.

``reference_execute`` walks the module with ``apply_op`` — the oracle every
generated kernel is validated against.

``StitchedExecutable`` runs a compile-time **ExecutionPlan** instead of
re-walking the module per call: constant-like chains are folded exactly once
at plan-build time, every value that flows between execution units lives in
a flat buffer table with precomputed last-use release points (intermediate
buffers are dropped eagerly), and each unit is pre-bound to its kernel and
operand slots.  The per-call hot path is a flat loop over pre-bound steps —
no graph traversal, no constant re-evaluation, no dict-keyed lookups.

The eager step loop still pays one Python->XLA dispatch per step — exactly
the launch overhead the compile-time passes fight.  ``jit_execute`` removes
it: the pre-bound loop is inlined **at trace time** into ``jax.jit``
segment callables (kernels, standalone ops, and library dots traced into
one XLA program per segment), so a steady-state call costs one dispatch per
segment instead of ``len(steps)`` — exactly ONE for graphs whose library
dots only consume parameters or earlier-segment outputs.  A library call
whose operand is produced inside the current segment starts a NEW segment:
as a segment leader its operands arrive as jit arguments with canonical
layouts — what the eager dispatch sees — which is what keeps the replay
**bit-identical** to the eager oracle (kept in-program, XLA folds layout
changes such as transposes into the dot operand and alters the
accumulation order).  Intermediate values the eager loop releases at their
last read are expressed to XLA as buffer donation of the corresponding
segment inputs, letting the runtime reuse their memory in place (parameter
and folded-constant buffers are never donated — the caller or the template
still holds them).  The eager loop is kept as the replay oracle, and
``LaunchStats`` counts traced vs eager dispatches.
"""
from __future__ import annotations

import functools
import warnings
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..tracing import span
from .codegen import StitchedKernel
from .fusion import FusionPlan, constant_like
from .ir import Instruction, Module, apply_op


def reference_execute(module: Module, feeds: Dict[str, object]) -> Dict[str, object]:
    vals: Dict[int, object] = {}
    for instr in module.instructions:
        if instr.opcode == "parameter":
            if instr.name not in feeds:
                raise KeyError(f"missing feed for parameter {instr.name}")
            v = jnp.asarray(feeds[instr.name], dtype=instr.dtype)
            assert tuple(v.shape) == tuple(instr.shape), (
                f"{instr.name}: feed shape {v.shape} != {instr.shape}"
            )
            vals[instr.id] = v
        else:
            vals[instr.id] = apply_op(instr, *[vals[o.id] for o in instr.operands])
    return {r.name: vals[r.id] for r in module.roots}


@dataclass
class LaunchStats:
    stitched_kernels: int = 0
    standalone_kernels: int = 0
    library_calls: int = 0
    collective_calls: int = 0        # mesh collectives — ICI steps, not launches
    loop_calls: int = 0              # sub-module loops (``call`` instructions)
    # runtime replay accounting: how calls were dispatched so far
    traced_calls: int = 0            # calls through the jitted replay
    eager_calls: int = 0             # calls through the eager step loop
    jit_traces: int = 0              # segment traces performed so far
    eager_dispatches_per_call: int = 0   # pre-bound steps the eager loop runs
    traced_dispatches_per_call: int = 0  # jitted replay segments
    donated_buffers: int = 0         # dead-after-segment inputs donated to XLA
    # feeds bound over every call so far (``_bind``): handed on as given,
    # or converted with ``jnp.asarray`` and their shape checked
    feeds_passed: int = 0
    feeds_converted: int = 0
    # kernel instances whose block of a batched-dot operand holds whole
    # matrices of more than one batch element (``schedule.DOT_BLOCK_LIMIT``)
    dot_batch_blocks: int = 0

    @property
    def total_non_library(self) -> int:
        return self.stitched_kernels + self.standalone_kernels


def order_units(plan: FusionPlan) -> List[object]:
    """Topological order over execution units (fusions + standalone).

    Fusion groups interleave in instruction order, so firing a group at its
    last member's position is NOT safe; we order groups by their value
    dependences instead (fusion-time cycle checks guarantee the group graph
    is a DAG).
    """
    units: List[object] = list(plan.fusions) + list(plan.standalone)
    unit_of: Dict[int, int] = {}
    for ui, u in enumerate(units):
        members = [u] if isinstance(u, Instruction) else u.members
        for m in members:
            unit_of[m.id] = ui
    deps: List[set] = [set() for _ in units]
    for ui, u in enumerate(units):
        srcs = u.operands if isinstance(u, Instruction) else u.inputs
        for s in srcs:
            if s.id in unit_of and unit_of[s.id] != ui:
                deps[ui].add(unit_of[s.id])
    # Kahn's algorithm (deque: the sorted-list pop(0) was O(n^2))
    indeg = [len(d) for d in deps]
    rdeps: List[set] = [set() for _ in units]
    for ui, d in enumerate(deps):
        for v in d:
            rdeps[v].add(ui)
    ready = deque(sorted(ui for ui, k in enumerate(indeg) if k == 0))
    order = []
    while ready:
        ui = ready.popleft()
        order.append(ui)
        for v in sorted(rdeps[ui]):
            indeg[v] -= 1
            if indeg[v] == 0:
                ready.append(v)
    if len(order) != len(units):
        raise RuntimeError("cyclic fusion plan — fusion cycle check failed")
    return [units[ui] for ui in order]


def _bind(feeds: Dict[str, object], binds, stats: LaunchStats,
          label: str = "feed shape") -> List[object]:
    """Validated values of ``binds`` (laid out as ``_param_binds``) in
    order.  A ``jax.Array`` feed of the parameter's dtype and shape, not
    weakly typed, is bound as it is: ``jnp.asarray`` would return it
    unchanged.  Any other feed is converted and its shape checked."""
    vals = []
    converted = 0
    for name, _, dtype, shape in binds:
        if name not in feeds:
            raise KeyError(f"missing feed for parameter {name}")
        v = feeds[name]
        if not (
            isinstance(v, jax.Array)
            and v.dtype == dtype
            and v.shape == shape
            and not v.weak_type
        ):
            v = jnp.asarray(v, dtype=dtype)
            if tuple(v.shape) != shape:
                raise ValueError(f"{name}: {label} {tuple(v.shape)} != {shape}")
            converted += 1
        vals.append(v)
    stats.feeds_passed += len(vals) - converted
    stats.feeds_converted += converted
    return vals


class _KernelStep:
    """One stitched-kernel launch, pre-bound to its buffer slots.  ``name``
    is the fusion instance's; the kernel's own name is shared by every
    instance of its signature."""

    __slots__ = ("kernel", "arg_slots", "out_slots", "release", "name")

    def __init__(self, kernel: StitchedKernel, arg_slots, out_slots, name: str):
        self.kernel = kernel
        self.arg_slots = arg_slots
        self.out_slots = out_slots
        self.release: List[int] = []
        self.name = name


class _OpStep:
    """One standalone instruction (library dot etc.), pre-bound.  ``fn``
    evaluates it on the values of ``arg_slots``."""

    __slots__ = ("instr", "arg_slots", "out_slot", "release", "fn")

    def __init__(self, instr: Instruction, arg_slots, out_slot, fn=None):
        self.instr = instr
        self.arg_slots = arg_slots
        self.out_slot = out_slot
        self.release: List[int] = []
        self.fn = fn or functools.partial(apply_op, instr)

    @property
    def name(self) -> str:
        return self.instr.name


def _is_transpose_2d(instr: Instruction) -> bool:
    return (
        instr.opcode == "transpose"
        and len(instr.shape) == 2
        and tuple(instr.attrs["perm"]) == (1, 0)
    )


def _folded_dot(instr: Instruction, lhs_t: bool, rhs_t: bool) -> Callable:
    """A 2-D ``dot`` that reads the operands of its standalone transposes:
    the transposes become contracting dims, the form XLA gives a
    transposed dot operand (and so its accumulation order)."""
    pref = jnp.float32 if np.dtype(instr.dtype) == np.float32 else None
    dims = (((0 if lhs_t else 1,), (1 if rhs_t else 0,)), ((), ()))

    def fn(lhs, rhs):
        return jax.lax.dot_general(
            lhs, rhs, dims, preferred_element_type=pref
        ).astype(instr.dtype)

    return fn


def _step_outs(step) -> List[int]:
    """Buffer slots a pre-bound step writes."""
    if type(step) is _OpStep:
        return [step.out_slot]
    return step.out_slots


class _LoopStep:
    """One sub-module loop (``call`` instruction), pre-bound.

    The body is a separately compiled ``ExecutionPlan`` whose step loop is
    inlined AT TRACE TIME via ``ExecutionPlan.trace_steps`` — same kernels,
    same step order, same barriers in every replay mode.  The eager path
    dispatches one jitted body call per iteration (``trip`` dispatches —
    exactly the per-iteration launch overhead the paper's decode loops
    pay); the traced path wraps the same inlined body in one
    ``jax.lax.scan`` under a single jit, so the whole loop costs ONE
    dispatch.  Carries double-buffer through the scan carry; per-iteration
    outputs stack into the planned output slots.
    """

    __slots__ = (
        "instr", "body_plan", "arg_slots", "out_slots", "out_indices",
        "release", "num_consts", "num_carry", "trip", "reverse",
        "out_order", "out_shapes", "out_dtypes", "_iter_fn", "_scan_fn",
    )

    def __init__(self, instr: Instruction, body_plan, arg_slots, out_slots,
                 out_indices):
        a = instr.attrs
        self.instr = instr
        self.body_plan = body_plan
        self.arg_slots = arg_slots
        self.out_slots = out_slots        # one per live ``get`` projection
        self.out_indices = list(out_indices)   # logical output index per slot
        self.release: List[int] = []
        self.num_consts = int(a["num_consts"])
        self.num_carry = int(a["num_carry"])
        self.trip = int(a["trip_count"])
        self.reverse = bool(a.get("reverse", False))
        self.out_order = list(a["out_order"])
        self.out_shapes = [tuple(s) for s in a["out_shapes"]]
        self.out_dtypes = list(a["out_dtypes"])
        self._iter_fn = None              # per-iteration jit (eager replay)
        self._scan_fn = None              # whole-loop jit (traced replay)

    # -- trace-time body --------------------------------------------------
    def _scan(self, args):
        """All logical outputs (final carries + stacked ys), traceable."""
        nc, k = self.num_consts, self.num_carry
        consts = list(args[:nc])
        init = list(args[nc:nc + k])
        xs = list(args[nc + k:])
        plan, order = self.body_plan, self.out_order

        def body(carry, x):
            x_vals = [] if x is None else list(x)
            roots = plan.trace_steps(consts + list(carry) + x_vals)
            ordered = [roots[j] for j in order]
            return tuple(ordered[:k]), tuple(ordered[k:])

        final, ys = jax.lax.scan(
            body,
            tuple(init),
            tuple(xs) if xs else None,
            length=self.trip,
            reverse=self.reverse,
        )
        return list(final) + list(ys)

    def run_nested(self, args):
        """Inline into an enclosing trace (nested loops): the projected
        output values for this step's ``out_slots``."""
        outs = self._scan(list(args))
        return [outs[i] for i in self.out_indices]

    # -- replay modes -----------------------------------------------------
    def run_traced(self, args, counter):
        if self._scan_fn is None:
            def fn(*vals):
                counter()             # runs only while tracing
                return tuple(self.run_nested(list(vals)))

            self._scan_fn = jax.jit(fn)
        return self._scan_fn(*args)

    def run_eager(self, args):
        nc, k = self.num_consts, self.num_carry
        consts = list(args[:nc])
        carry = list(args[nc:nc + k])
        xs = list(args[nc + k:])
        n_y = len(self.out_order) - k
        if self.trip == 0:
            all_outs = carry + [
                jnp.zeros(self.out_shapes[k + j], self.out_dtypes[k + j])
                for j in range(n_y)
            ]
            return [all_outs[i] for i in self.out_indices]
        if self._iter_fn is None:
            plan, order = self.body_plan, self.out_order

            def it(*vals):
                roots = plan.trace_steps(list(vals))
                return tuple(roots[j] for j in order)

            self._iter_fn = jax.jit(it)
        cols: List[List[object]] = [[] for _ in range(n_y)]
        steps = (
            range(self.trip - 1, -1, -1) if self.reverse
            else range(self.trip)
        )
        for t in steps:
            outs = self._iter_fn(*(consts + carry + [x[t] for x in xs]))
            carry = list(outs[:k])
            for j in range(n_y):
                cols[j].append(outs[k + j])
        if self.reverse:
            cols = [list(reversed(c)) for c in cols]
        all_outs = carry + [jnp.stack(c) for c in cols]
        return [all_outs[i] for i in self.out_indices]


class _JitSegment:
    """A run of pre-bound steps traced into one jitted callable.

    ``in_slots`` are buffer-table slots the segment reads but does not
    produce; ``out_slots`` are slots it produces that are still needed
    afterwards (roots, or read by a later segment / library call).
    ``donate`` indexes the ``in_slots`` whose eager-release point falls
    inside this segment — dead after the call, so their buffers are donated
    to XLA.  Only *intermediate* slots (produced by an earlier segment,
    owned by the runtime, fresh every call) are donated: template
    (folded-constant) buffers are shared across calls, and parameter
    buffers may still be held by the caller (a device-resident feed is
    bound as given — donating it would delete an array the caller reuses
    on the next call).
    """

    __slots__ = ("steps", "in_slots", "out_slots", "released", "donate", "fn")

    def __init__(self, steps: List[object], keep: set, protected_slots: set):
        self.steps = list(steps)
        written: List[int] = []
        written_set: set = set()
        in_slots: List[int] = []
        in_set: set = set()
        released: set = set()
        for step in self.steps:
            for s in step.arg_slots:
                if s not in written_set and s not in in_set:
                    in_set.add(s)
                    in_slots.append(s)
            outs = (
                step.out_slots if type(step) is _KernelStep else [step.out_slot]
            )
            for s in outs:
                if s not in written_set:
                    written_set.add(s)
                    written.append(s)
            released.update(step.release)
        self.in_slots = in_slots
        self.released = released
        self.out_slots = [
            s for s in written if s in keep or s not in released
        ]
        self.donate = tuple(
            i
            for i, s in enumerate(in_slots)
            if s in released and s not in protected_slots
        )
        self.fn = None               # jax.jit wrapper, built lazily

    def build(self, counter) -> None:
        """Trace-time body: the segment's pre-bound steps inlined into one
        XLA program.  Step outputs pass through ``optimization_barrier`` so
        XLA cannot re-fuse across step boundaries — fusion decisions belong
        to the FusionStitching passes, and the barrier keeps the traced
        program step-for-step equivalent to the eager oracle.  Each step
        runs under ``jax.named_scope(step.name)``, which ties its device
        operations to the plan in the trace's op metadata."""
        steps, in_slots, out_slots = self.steps, self.in_slots, self.out_slots

        def seg(*vals):
            counter()                # runs only while tracing
            local: Dict[int, object] = dict(zip(in_slots, vals, strict=False))
            for step in steps:
                args = [local[s] for s in step.arg_slots]
                with jax.named_scope(step.name):
                    if type(step) is _KernelStep:
                        outs = jax.lax.optimization_barrier(step.kernel(*args))
                        for s, o in zip(step.out_slots, outs, strict=False):
                            local[s] = o
                    else:
                        local[step.out_slot] = jax.lax.optimization_barrier(
                            step.fn(*args)
                        )
            return tuple(local[s] for s in out_slots)

        self.fn = jax.jit(seg, donate_argnums=self.donate)


def _dispatch_span(first: bool) -> str:
    """The span of one replay-segment call: the first call of a segment
    traces and compiles it, later calls only enqueue it."""
    return "repro.replay_build" if first else "repro.dispatch"


class ExecutionPlan:
    """Precomputed run recipe for a compiled FusionPlan.

    Built once at compile time:
      * constant-like chains are evaluated here (``fold_evals`` counts the
        evaluations — they never recur at call time);
      * a flat buffer table holds every inter-unit value; slots are released
        (set to None) right after their last consuming step;
      * each step carries its kernel/instruction and operand slot indices.
    """

    def __init__(
        self,
        module: Module,
        plan: FusionPlan,
        kernels: Dict[str, StitchedKernel],
        donate_params=None,
    ):
        member_ids = {m.id for f in plan.fusions for m in f.members}
        covered = member_ids | {s.id for s in plan.standalone}

        units = order_units(plan)

        # ---- which values must live in the buffer table -------------------
        needed: set = {r.id for r in module.roots}
        for u in units:
            if isinstance(u, Instruction):
                needed.update(o.id for o in u.operands)
            else:
                needed.update(i.id for i in kernels[u.name].inputs)

        slot_of: Dict[int, int] = {}

        def new_slot(instr_id: int) -> int:
            slot_of[instr_id] = len(slot_of)
            return slot_of[instr_id]

        # ---- parameters + compile-time constant folding -------------------
        self.fold_evals = 0
        folded_vals: Dict[int, object] = {}

        def fold(instr: Instruction):
            if instr.id in folded_vals:
                return folded_vals[instr.id]
            v = apply_op(instr, *[fold(o) for o in instr.operands])
            self.fold_evals += 1
            folded_vals[instr.id] = v
            return v

        self._param_binds: List[Tuple[str, int, object, Tuple[int, ...]]] = []
        template_fill: List[Tuple[int, object]] = []
        for instr in module.instructions:
            if instr.opcode == "parameter":
                s = new_slot(instr.id)
                self._param_binds.append(
                    (instr.name, s, instr.dtype, tuple(instr.shape))
                )
            elif instr.id not in covered:
                if not (instr.opcode == "constant" or constant_like(instr)):
                    raise RuntimeError(
                        f"{instr.name}: uncovered non-constant instruction"
                    )
                if instr.id in needed:
                    template_fill.append((new_slot(instr.id), fold(instr)))

        # ---- pre-bound steps in unit order ---------------------------------
        # A standalone 2-D transpose read only by standalone 2-D dots runs
        # no step of its own: the dots read its operand (``_folded_dot``).
        standalone_ids = {s.id for s in plan.standalone}
        root_ids = {r.id for r in module.roots}

        def is_2d_dot(i: Instruction) -> bool:
            return i.opcode == "dot" and all(len(o.shape) == 2 for o in i.operands)

        folded = {
            u.id for u in plan.standalone
            if _is_transpose_2d(u) and u.id not in root_ids and u.users
            and all(d.id in standalone_ids and is_2d_dot(d) for d in u.users)
        }
        self.steps: List[object] = []
        for u in units:
            if isinstance(u, Instruction):
                if u.opcode == "get" or u.id in folded:
                    continue   # a get's slot is created by the call's loop step
                srcs = [o.operands[0] if o.id in folded else o for o in u.operands]
                arg_slots = [slot_of[o.id] for o in srcs]
                if u.opcode == "call":
                    gets = sorted(
                        (g for g in u.users if g.opcode == "get"),
                        key=lambda g: g.attrs["index"],
                    )
                    if len(gets) != len(u.users):
                        raise RuntimeError(
                            f"{u.name}: call outputs must be consumed "
                            "through get projections"
                        )
                    cm = u.attrs.get("compiled_body")
                    if cm is None:
                        raise RuntimeError(
                            f"{u.name}: loop body was not compiled — "
                            "SubModulePass must run before plan construction"
                        )
                    self.steps.append(
                        _LoopStep(
                            u,
                            cm.executable.execution_plan,
                            arg_slots,
                            [new_slot(g.id) for g in gets],
                            [int(g.attrs["index"]) for g in gets],
                        )
                    )
                else:
                    fn = None
                    if any(o.id in folded for o in u.operands):
                        lhs, rhs = u.operands
                        fn = _folded_dot(u, lhs.id in folded, rhs.id in folded)
                    self.steps.append(_OpStep(u, arg_slots, new_slot(u.id), fn))
            else:
                k = kernels[u.name]
                arg_slots = [slot_of[i.id] for i in k.inputs]
                out_slots = [new_slot(r.id) for r in k.outputs]
                self.steps.append(_KernelStep(k, arg_slots, out_slots, u.name))

        self.num_slots = len(slot_of)
        self._root_binds: List[Tuple[str, int]] = [
            (r.name, slot_of[r.id]) for r in module.roots
        ]

        # ---- eager-release points: free a slot after its last read ---------
        keep = {s for _, s in self._root_binds}
        last_read: Dict[int, int] = {}
        for si, step in enumerate(self.steps):
            for s in step.arg_slots:
                last_read[s] = si
        for s, si in last_read.items():
            if s not in keep:
                self.steps[si].release.append(s)
        # Dead outputs — multi-output kernel slots (e.g. a fusion root with
        # no remaining consumer) are never in ``last_read``, so without this
        # they would hold their buffer for the whole run.  Release them at
        # the step that produces them.
        for si, step in enumerate(self.steps):
            for s in _step_outs(step):
                if s not in keep and s not in last_read:
                    step.release.append(s)

        template: List[Optional[object]] = [None] * self.num_slots
        for s, v in template_fill:
            template[s] = v
        self._template = template

        # ---- traced replay segments ---------------------------------------
        # The step loop traces into jitted segments.  A library call
        # (cuBLAS/MXU dot) whose operand was produced INSIDE the current
        # segment starts a new one: as a segment leader its operands arrive
        # as fresh jit arguments with canonical layouts — exactly what the
        # eager dispatch sees — whereas in-program XLA folds layout changes
        # (e.g. a transpose) into the dot operand and changes the
        # accumulation order, breaking bit-parity with the eager oracle.
        # Template + parameter slots are protected from donation (shared
        # across calls / possibly still held by the caller) — EXCEPT
        # parameters the caller explicitly donated (``donate_argnums``
        # through the frontend): those buffers belong to the plan after the
        # call, per the jax.jit donation contract.
        donate = frozenset(donate_params or ())
        protected_slots = {s for s, _ in template_fill} | {
            slot for name, slot, _, _ in self._param_binds
            if name not in donate
        }
        self.donated_param_slots = {
            slot for name, slot, _, _ in self._param_binds if name in donate
        }
        self._segments: List[object] = []
        run: List[object] = []
        produced: set = set()
        for step in self.steps:
            if type(step) is _LoopStep:
                # a loop is its own dispatch unit in the traced replay
                if run:
                    self._segments.append(
                        _JitSegment(run, keep, protected_slots)
                    )
                    run, produced = [], set()
                self._segments.append(step)
                continue
            is_lib = type(step) is _OpStep and step.instr.is_library_call
            if is_lib and run and any(s in produced for s in step.arg_slots):
                self._segments.append(_JitSegment(run, keep, protected_slots))
                run, produced = [], set()
            run.append(step)
            produced.update(_step_outs(step))
        if run:
            self._segments.append(_JitSegment(run, keep, protected_slots))
        self.stats = LaunchStats(
            eager_dispatches_per_call=sum(
                s.trip if type(s) is _LoopStep else 1 for s in self.steps
            ),
            traced_dispatches_per_call=len(self._segments),
            donated_buffers=sum(
                len(seg.donate) for seg in self._segments
                if type(seg) is _JitSegment
            ),
            loop_calls=sum(
                1 for s in self.steps if type(s) is _LoopStep
            ),
        )

    @property
    def num_folded(self) -> int:
        return sum(1 for v in self._template if v is not None)

    def trace_steps(self, param_vals) -> List[object]:
        """Trace-time inline of the whole pre-bound step loop, WITHOUT
        segmentation: this is the loop-body building block (``_LoopStep``),
        where the surrounding per-iteration jit / ``lax.scan`` is the
        dispatch unit.  Parameter values pass through
        ``optimization_barrier`` so library dots see canonical operands
        whether the body runs standalone (eager per-iteration jit) or
        inside ``lax.scan`` — XLA cannot fold carried-value or slice
        layouts into the dot and change its accumulation order, which
        keeps the two replay modes bit-identical.  Takes parameter values
        positionally (``_param_binds`` order = parameter creation order =
        call operand order) and returns root values in ``module.roots``
        order."""
        buf: List[Optional[object]] = list(self._template)
        for (name, slot, dtype, shape), v in zip(
            self._param_binds, param_vals
        , strict=False):
            buf[slot] = jax.lax.optimization_barrier(
                jnp.asarray(v, dtype=dtype)
            )
        for step in self.steps:
            args = [buf[s] for s in step.arg_slots]
            if type(step) is _KernelStep:
                outs = jax.lax.optimization_barrier(step.kernel(*args))
                for s, o in zip(step.out_slots, outs, strict=False):
                    buf[s] = o
            elif type(step) is _LoopStep:
                for s, o in zip(step.out_slots, step.run_nested(args), strict=False):
                    buf[s] = o
            else:
                buf[step.out_slot] = jax.lax.optimization_barrier(
                    step.fn(*args)
                )
            for s in step.release:
                buf[s] = None
        return [buf[s] for _, s in self._root_binds]

    def _bind_feeds(self, feeds: Dict[str, object]) -> List[object]:
        """Validated parameter values in ``_param_binds`` order (``_bind``)."""
        return _bind(feeds, self._param_binds, self.stats)

    def execute(self, feeds: Dict[str, object]) -> Dict[str, object]:
        """Eager replay: one Python-dispatched XLA call per step (the
        traced-replay oracle)."""
        buf = list(self._template)
        for (name, slot, dtype, shape), v in zip(
            self._param_binds, self._bind_feeds(feeds)
        , strict=False):
            buf[slot] = v
        for step in self.steps:
            if type(step) is _KernelStep:
                outs = step.kernel(*[buf[s] for s in step.arg_slots])
                for s, o in zip(step.out_slots, outs, strict=False):
                    buf[s] = o
            elif type(step) is _LoopStep:
                outs = step.run_eager([buf[s] for s in step.arg_slots])
                for s, o in zip(step.out_slots, outs, strict=False):
                    buf[s] = o
            else:
                buf[step.out_slot] = step.fn(*[buf[s] for s in step.arg_slots])
            for s in step.release:
                buf[s] = None
        self.stats.eager_calls += 1
        return {name: buf[s] for name, s in self._root_binds}

    # ------------------------------------------------------------ traced
    def _count_trace(self):
        self.stats.jit_traces += 1

    def jit_execute(self, feeds: Dict[str, object]) -> Dict[str, object]:
        """Traced replay: the pre-bound loop as a handful of jitted segment
        calls — ``traced_dispatches_per_call`` dispatches instead of one
        per step.

        Bit-identical to ``execute`` (same kernels, same ``apply_op``
        interpreter, same step order, segment boundaries wherever XLA could
        alter library-dot accumulation order).  Only runtime-owned
        intermediate buffers are donated, so caller-held feed arrays (jax
        or numpy) stay valid across calls.
        """
        with span("repro.bind"):
            vals = self._bind_feeds(feeds)
            buf = list(self._template)
            for (name, slot, dtype, shape), v in zip(self._param_binds, vals, strict=False):
                buf[slot] = v
        with warnings.catch_warnings():
            # donation on backends without aliasing support (CPU) only warns
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            for seg in self._segments:
                if type(seg) is _LoopStep:
                    with span(_dispatch_span(seg._scan_fn is None)):
                        outs = seg.run_traced(
                            [buf[s] for s in seg.arg_slots], self._count_trace
                        )
                    for s, o in zip(seg.out_slots, outs, strict=False):
                        buf[s] = o
                    for s in seg.release:
                        buf[s] = None
                    continue
                first = seg.fn is None
                if first:
                    seg.build(self._count_trace)
                with span(_dispatch_span(first)):
                    outs = seg.fn(*[buf[s] for s in seg.in_slots])
                for s, o in zip(seg.out_slots, outs, strict=False):
                    buf[s] = o
                for s in seg.released:
                    buf[s] = None
        self.stats.traced_calls += 1
        return {name: buf[s] for name, s in self._root_binds}


class StitchedExecutable:
    """Runs a compiled FusionPlan through its precomputed ExecutionPlan.

    ``jit_replay=True`` (the default) replays through the single traced
    callable; ``jit_replay=False`` keeps the eager per-step loop — the
    oracle the traced path is validated against.

    A ``mesh`` makes this ONE multi-device plan: the same pre-bound step
    loop is traced once under ``shard_map`` (``trace_steps`` inlined, with
    collective steps lowering to ``lax.psum``-family calls between the
    kernels) and jitted whole.  Feeds and results are then GLOBAL arrays;
    the per-shard view each device runs is exactly the module the compiler
    planned.  Every call — including ``jit_replay=False`` — goes through
    the traced path, because collectives only evaluate where mesh axis
    names are bound.
    """

    def __init__(
        self,
        module: Module,
        plan: FusionPlan,
        kernels: Dict[str, StitchedKernel],  # fusion name -> kernel
        jit_replay: bool = True,
        donate_params=None,
        mesh=None,
        param_layouts=None,
        out_layouts=None,
    ):
        self.module = module
        self.plan = plan
        self.kernels = kernels
        self.jit_replay = jit_replay
        self.execution_plan = ExecutionPlan(
            module, plan, kernels, donate_params=donate_params
        )
        self.mesh = mesh
        self.param_layouts = dict(param_layouts or {})
        self.out_layouts = list(out_layouts) if out_layouts else None
        self._sharded_fn = None
        if mesh is not None:
            self._build_sharded()

    def _build_sharded(self) -> None:
        from .shard import layout_to_pspec, wrap_shard_map

        ep = self.execution_plan
        self._global_binds = [
            (name, slot, dtype, self._global_shape(name, shape))
            for name, slot, dtype, shape in ep._param_binds
        ]
        in_specs = tuple(
            layout_to_pspec(self.param_layouts.get(name))
            for name, _, _, _ in ep._param_binds
        )
        outs = self.out_layouts or [None] * len(ep._root_binds)
        out_specs = tuple(layout_to_pspec(lay) for lay in outs)

        def run(*vals):
            return tuple(ep.trace_steps(list(vals)))

        self._sharded_fn = jax.jit(
            wrap_shard_map(run, self.mesh, in_specs, out_specs)
        )

    def _global_shape(self, name: str, local: Tuple[int, ...]) -> Tuple[int, ...]:
        lay = self.param_layouts.get(name)
        if lay is None:
            return tuple(local)
        sizes = {str(a): int(self.mesh.shape[a]) for a in self.mesh.axis_names}
        out = []
        for d, e in zip(local, lay, strict=False):
            g = 1
            for a in e or ():
                g *= sizes.get(a, 1)
            out.append(d * g)
        return tuple(out)

    def sharded_execute(self, feeds: Dict[str, object]) -> Dict[str, object]:
        """One dispatch of the whole multi-device plan on global feeds."""
        ep = self.execution_plan
        with span("repro.bind"):
            vals = _bind(feeds, self._global_binds, ep.stats, "global feed shape")
        with span(_dispatch_span(ep.stats.traced_calls == 0)):
            outs = self._sharded_fn(*vals)
        ep.stats.traced_calls += 1
        return {name: o for (name, _), o in zip(ep._root_binds, outs, strict=False)}

    def launch_stats(self) -> LaunchStats:
        st = LaunchStats()
        st.stitched_kernels = len(self.plan.fusions)
        st.standalone_kernels = sum(
            1 for s in self.plan.standalone
            if not s.is_library_call
            and not s.is_collective
            and s.opcode not in ("call", "get")
        )
        st.library_calls = self.plan.num_library_calls
        st.collective_calls = self.plan.num_collectives
        rt = self.execution_plan.stats
        st.loop_calls = rt.loop_calls
        st.traced_calls = rt.traced_calls
        st.eager_calls = rt.eager_calls
        st.jit_traces = rt.jit_traces
        st.eager_dispatches_per_call = rt.eager_dispatches_per_call
        st.traced_dispatches_per_call = (
            1 if self.mesh is not None else rt.traced_dispatches_per_call
        )
        st.donated_buffers = rt.donated_buffers
        st.feeds_passed = rt.feeds_passed
        st.feeds_converted = rt.feeds_converted
        st.dot_batch_blocks = sum(
            1 for k in self.kernels.values()
            if k.solution is not None and k.solution.dot_batch_block
        )
        return st

    def execute_eager(self, feeds: Dict[str, object]) -> Dict[str, object]:
        if self.mesh is not None:
            return self.sharded_execute(feeds)
        return self.execution_plan.execute(feeds)

    def jit_execute(self, feeds: Dict[str, object]) -> Dict[str, object]:
        if self.mesh is not None:
            return self.sharded_execute(feeds)
        return self.execution_plan.jit_execute(feeds)

    def __call__(self, feeds: Dict[str, object]) -> Dict[str, object]:
        if self.mesh is not None:
            return self.sharded_execute(feeds)
        if self.jit_replay:
            return self.execution_plan.jit_execute(feeds)
        return self.execution_plan.execute(feeds)
