"""IrEmitterStitched — block-composition code generation (paper §5.2).

Emits ONE ``pl.pallas_call`` per fused computation:

  * the launch grid is ``(blocks,)`` — the paper's CTA count, here the
    Pallas grid (TPU grid programs pipeline HBM->VMEM DMAs);
  * every fusion input/output gets a ``BlockSpec`` whose block shape is the
    propagated schedule's chunk and whose ``index_map`` is the schedule's
    block-index arithmetic;
  * ops whose MemoryPlan action is ALLOC/SHARE write their block tile into a
    VMEM scratch ref (``pltpu.VMEM`` via ``scratch_shapes``) and consumers
    read it back — block composition through scratchpad, exactly the paper's
    shared-memory stitching; slot sharing from the dominance-tree plan reuses
    one scratch ref for several ops;
  * INLINE ops are evaluated as straight vector expressions — thread
    composition (XLA's elemental emitter analogue, Algorithm 2's fallback
    branch).
  * the call is named ``stitch_<8 hex>`` from the fusion signature
    (``kernel_name``), so the kernel keeps one name in HLO and in the
    device trace, shared by every fusion instance it serves;
  * a parameter whose device lays it out with its two minor dims swapped
    (stamped ``native_layout`` by ``stamp_native_layouts``) is read in that
    layout: it crosses the call as ``swapaxes`` of itself, which XLA folds
    into a bitcast, so no relayout copy runs before the kernel.  Inside,
    a transpose that swaps it back and a dot that takes it as rhs use the
    swapped block as it is; any other consumer swaps it in VMEM.

The same ``apply_op`` interpreter evaluates ops here (on VMEM tiles) and in
the reference executor (on full arrays), so kernels match the oracle by
construction up to float reassociation.  Two ops leave it for forms Mosaic
lowers: block windows are static slices or ``pl.ds`` reads of an input
ref (never a value ``dynamic_slice``), and batched dots run as 2-D or
single-batch-dim matmuls.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu

from .fusion import FusedComputation
from .ir import Instruction, apply_op
from .memory import ALLOC, SCOPED_VMEM_BYTES, SHARE, MemoryPlan, StitchedMemoryPlan
from .schedule import (
    REPLICATED,
    Sched,
    ScheduleSolution,
    StitchedSolution,
    block_index,
    chunk_shape,
    native_block,
    propagate,
    swap_minor,
)
from .signature import fusion_signature

#: VMEM Mosaic keeps for itself on top of the planned buffers.
VMEM_HEADROOM = 4 * 1024 * 1024


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` means: run the Pallas interpreter exactly when there is no
    TPU.  On a TPU kernels compile for the chip unless told otherwise."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def default_minor_to_major(dtype, shape, device) -> tuple:
    """Minor-to-major order of ``device``'s default layout for a
    ``(dtype, shape)`` array: the layout a jitted call's arguments carry."""
    pjrt = device.client.get_default_layout(np.dtype(dtype), tuple(shape), device)
    return tuple(reversed(Layout.from_pjrt_layout(pjrt).major_to_minor))


def minor_dims_swapped(dtype, shape, device) -> bool:
    """Whether ``device`` lays a 32-bit ``(dtype, shape)`` array out
    row-major except for its two minor dims, which are swapped: then
    ``swapaxes(x, -1, -2)`` in row-major order is the same bytes.  A v5e
    does this to ``f32[..., 1024, 64]``, keeping the long dim on the lanes;
    a CPU never does."""
    n = len(shape)
    if n < 2 or np.dtype(dtype).itemsize != 4:
        return False
    swapped = (n - 2, n - 1) + tuple(range(n - 3, -1, -1))
    return default_minor_to_major(dtype, shape, device) == swapped


def stamp_native_layouts(module, device) -> None:
    """Stamp ``attrs["native_layout"]`` on each parameter of ``module`` that
    ``device`` lays out with its minor dims swapped, and clear it from the
    rest.  In a jitted replay a parameter is a segment argument and carries
    that default layout, so a kernel that reads it row-major makes XLA copy
    it first; a stamped one is read swapped instead.  ``device`` None (a
    loop body, whose parameters XLA lays out inside the loop) stamps
    nothing.  The stamp salts ``fusion_signature``."""
    for p in module.parameters:
        if device is not None and minor_dims_swapped(p.dtype, p.shape, device):
            p.attrs["native_layout"] = True
        else:
            p.attrs.pop("native_layout", None)


def _compiler_params(vmem_need: int):
    """Raise the kernel's scoped-VMEM limit when its plan needs more than
    the chip gives by default."""
    if vmem_need + VMEM_HEADROOM <= SCOPED_VMEM_BYTES:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_need + VMEM_HEADROOM)


# Pallas TPU blocks have rank >= 1: rank-0 kernel operands, results and
# scratch travel as (1, 1) blocks and are reshaped back inside the kernel.


def _lift(shape) -> tuple:
    return tuple(shape) if len(shape) else (1, 1)


def _load(ref, shape):
    v = ref[...]
    return v.reshape(()) if not len(shape) else v


def _store(ref, v) -> None:
    v = _value(v)
    ref[...] = v.reshape(ref.shape) if v.ndim == 0 else v


class _Input:
    """A kernel input: its ref, loaded whole on first use.  A block that
    needs only a window of it reads the window from the ref instead.
    ``swapped``: the ref holds the block with its two minor dims swapped
    (``raw``); ``value`` is then swapped back in VMEM."""

    def __init__(self, ref, shape, swapped: bool = False):
        self.ref = ref
        self.shape = tuple(shape)
        self.swapped = swapped
        self._raw = None
        self._val = None

    @property
    def raw(self):
        if self._raw is None:
            self._raw = _load(self.ref, self.shape)
        return self._raw

    @property
    def value(self):
        if self._val is None:
            self._val = jnp.swapaxes(self.raw, -1, -2) if self.swapped else self.raw
        return self._val


class _MinorSwapped:
    """A transpose of the two minor dims of ``raw``, not done yet: a dot
    that takes it as rhs contracts ``raw`` on its last dim instead (the
    MQA scores ``q @ k.T`` read K as it lies), and any other consumer swaps
    it in VMEM."""

    swapped = True

    def __init__(self, raw):
        self.raw = raw
        self._val = None

    @property
    def shape(self) -> tuple:
        return swap_minor(self.raw.shape)

    @property
    def value(self):
        if self._val is None:
            self._val = jnp.swapaxes(self.raw, -1, -2)
        return self._val


def _swapped_input(v) -> bool:
    return isinstance(v, (_Input, _MinorSwapped)) and v.swapped


def _value(v):
    return v.value if isinstance(v, (_Input, _MinorSwapped)) else v


def _starts(shape, sched: Sched, b):
    """Start of block ``b``'s window; a dim the block covers whole starts
    at a static 0 whatever ``b`` is."""
    idx = block_index(shape, sched, b)
    cs = chunk_shape(shape, sched)
    return tuple(
        0 if c == n else i * c for i, c, n in zip(idx, cs, shape, strict=False)
    )


def _window(v, starts, sizes):
    """The ``sizes`` window of ``v`` at ``starts``.  Mosaic has no value
    ``dynamic_slice``: static starts slice the value, traced starts read
    the window from the input's ref with ``pl.ds``."""
    if all(isinstance(st, int) for st in starts):
        val = _value(v)
        if tuple(sizes) == tuple(val.shape):
            return val
        return val[tuple(slice(st, st + n) for st, n in zip(starts, sizes, strict=False))]
    if not isinstance(v, _Input) or v.swapped:
        raise ValueError(
            "a block-dependent window of an in-kernel value or of a swapped "
            "input has no Mosaic lowering; the schedule must deliver it as a block"
        )
    return v.ref[tuple(pl.ds(st, n) for st, n in zip(starts, sizes, strict=False))]


def _adapt(val, opnd: Instruction, stored: Sched, needed: Sched, b):
    """Convert an operand's stored form to the consumer's needed form."""
    if stored == needed:
        return val
    if stored.kind == "replicated" and needed.kind == "chunked":
        return _window(
            val, _starts(opnd.shape, needed, b), chunk_shape(opnd.shape, needed)
        )
    if needed.kind == "replicated" and stored.kind == "replicated":
        return val
    raise AssertionError(
        f"cannot adapt {opnd.name}: stored {stored}, needed {needed}"
    )


def _dot(instr: Instruction, lhs, rhs, rhs_swapped: bool = False):
    """A batched ``dot`` as Mosaic's matmul takes it: 2-D when the block
    holds one batch, else with the batch dims folded into one.  f32 dots
    contract at full f32 precision, as the oracle does (Mosaic's default
    may round operands to bf16).  ``rhs_swapped``: ``rhs`` comes as
    ``(..., N, K)`` and contracts on its last dim."""
    batch = tuple(lhs.shape[:-2])
    nb = int(np.prod(batch, dtype=np.int64))
    f32 = np.dtype(instr.dtype) == np.float32
    kw = dict(
        preferred_element_type=jnp.float32 if f32 else None,
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    )
    rc = 1 if rhs_swapped else 0       # the rhs matrix's contracting dim
    if nb == 1:
        out = jax.lax.dot_general(
            lhs.reshape(lhs.shape[-2:]), rhs.reshape(rhs.shape[-2:]),
            (((1,), (rc,)), ((), ())), **kw,
        )
    else:
        out = jax.lax.dot_general(
            lhs.reshape((nb,) + lhs.shape[-2:]), rhs.reshape((nb,) + rhs.shape[-2:]),
            (((2,), (rc + 1,)), ((0,), (0,))), **kw,
        )
    return out.reshape(batch + out.shape[-2:]).astype(instr.dtype)


def _gather(instr: Instruction, table, idx):
    """Row gather as a one-hot matmul (Mosaic has no vector gather).  The
    one-hot rows pick table rows exactly at HIGHEST precision; a table that
    fits a kernel is small, so the extra MXU work is small too."""
    v = table.shape[0]
    flat = idx.reshape((-1, 1)).astype(jnp.int32)
    onehot = (flat == jax.lax.broadcasted_iota(jnp.int32, (flat.shape[0], v), 1))
    rows = jax.lax.dot_general(
        onehot.astype(table.dtype), table.reshape((v, -1)),
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    return rows.reshape(tuple(idx.shape) + tuple(table.shape[1:])).astype(instr.dtype)


def _emit_instr(instr: Instruction, sched: Sched, ovals: List, b):
    """Evaluate one instruction on block tiles (thread-composition body)."""
    op = instr.opcode
    a = instr.attrs
    out_chunk = chunk_shape(instr.shape, sched)

    if op == "broadcast":
        dims = tuple(a["dims"])
        opnd = instr.operands[0]
        v = ovals[0]
        if sched.kind == "chunked" and tuple(v.shape) == tuple(opnd.shape):
            # replicated operand feeding a chunked broadcast: slice the
            # operand window this block's output chunk maps onto.
            ost = _starts(instr.shape, sched, b)
            starts = tuple(
                ost[dims[j]] if opnd.shape[j] != 1 else 0
                for j in range(len(dims))
            )
            sizes = tuple(
                out_chunk[dims[j]] if opnd.shape[j] != 1 else 1
                for j in range(len(dims))
            )
            v = _window(v, starts, sizes)
        return jax.lax.broadcast_in_dim(_value(v), out_chunk, dims)

    # a swapped input taken as it is: the transpose that swaps it back is
    # the block the ref holds, and a dot contracts on its last dim; any
    # other swap of the minor dims is deferred to its consumers
    if op == "transpose" and tuple(a["perm"]) == swap_minor(range(len(instr.shape))):
        if _swapped_input(ovals[0]):
            return ovals[0].raw
        return _MinorSwapped(_value(ovals[0]))
    if op == "dot" and _swapped_input(ovals[1]):
        return _dot(instr, _value(ovals[0]), ovals[1].raw, rhs_swapped=True)

    ovals = [_value(v) for v in ovals]
    if op in ("reshape", "bitcast"):
        return jnp.reshape(ovals[0], out_chunk)

    if op == "iota":
        d = a["dim"]
        base = jax.lax.broadcasted_iota(instr.dtype, out_chunk, d)
        if sched.kind == "chunked":
            start = _starts(instr.shape, sched, b)[d]
            base = base + jnp.asarray(start, dtype=instr.dtype)
        return base

    if op == "dot":
        return _dot(instr, *ovals)

    if op == "gather":
        return _gather(instr, *ovals)

    return apply_op(instr, *ovals)


@dataclass
class StitchedKernel:
    """A compiled stitched kernel: call with input arrays in ``inputs`` order.

    Single-phase (schedule-consistent) kernels carry a ``solution``;
    multi-phase stitched kernels carry a ``stitched`` solution instead and
    ``solution`` is None.
    """

    fusion: FusedComputation
    solution: Optional[ScheduleSolution]
    plan: object                         # MemoryPlan | StitchedMemoryPlan
    fn: Callable
    inputs: List[Instruction]
    outputs: List[Instruction]
    stitched: Optional[StitchedSolution] = None
    name: str = ""                       # the ``pallas_call`` name
    native: Tuple[bool, ...] = ()        # per input: read in its native layout

    @property
    def blocks(self) -> int:
        if self.stitched is not None:
            return self.stitched.blocks
        return self.solution.blocks

    @property
    def num_phases(self) -> int:
        return self.stitched.num_phases if self.stitched is not None else 1

    def __call__(self, *args):
        return self.fn(*args)

    def bind(self, fusion: FusedComputation) -> "StitchedKernel":
        """Re-bind this kernel to a structurally-identical fusion instance.

        The compiled callable is purely positional, so any fusion with the
        same fusion-signature can share it; only the instruction lists used
        by the runtime to gather arguments and scatter results change.
        ``solution``/``plan`` keep referring to the representative instance.
        """
        return StitchedKernel(
            fusion, self.solution, self.plan, self.fn,
            fusion.inputs, fusion.roots, stitched=self.stitched, name=self.name,
            native=self.native,
        )


def kernel_name(fusion: FusedComputation) -> str:
    """``stitch_<8 hex of the fusion signature>``: the name the kernel
    carries into HLO and the device trace, shared by every instance of one
    signature."""
    return "stitch_" + fusion_signature(fusion)[:8]


def emit_fusion(
    fusion: FusedComputation,
    solution: ScheduleSolution,
    plan: MemoryPlan,
    interpret: Optional[bool] = None,
) -> StitchedKernel:
    members = fusion.members
    roots = fusion.roots
    inputs = fusion.inputs
    assign = solution.assignment
    blocks = solution.blocks
    member_ids = {m.id for m in members}
    for m in members:
        if m.is_collective:
            # unreachable through the planner (collectives are not fusable
            # and have no schedule) — fail loudly rather than emit a kernel
            # that silently drops the cross-device reduction
            raise ValueError(
                f"{m.name}: collective {m.opcode} cannot be emitted inside "
                "a kernel; it must stay a standalone schedule break"
            )

    def spec(shape, sched: Sched, native: bool = False) -> pl.BlockSpec:
        if not shape:
            return _full_spec(shape)
        if native:
            return pl.BlockSpec(
                swap_minor(chunk_shape(shape, sched)),
                functools.partial(_swapped_block_index, shape, sched),
            )
        return pl.BlockSpec(
            chunk_shape(shape, sched), functools.partial(block_index, shape, sched)
        )

    in_scheds = [assign.get(i.id, REPLICATED) for i in inputs]
    native = tuple(
        native_block(i, chunk_shape(i.shape, s), blocks > 1 and s.kind == "replicated")
        for i, s in zip(inputs, in_scheds, strict=True)
    )
    in_specs = [
        spec(tuple(i.shape), s, n) for i, s, n in zip(inputs, in_scheds, native, strict=True)
    ]
    # an exit reshape's block is its operand's; _boundary reshapes it after
    out_blocks = [solution.block(r) for r in roots]
    out_specs = [spec(*blk) for blk in out_blocks]
    out_shape = [
        jax.ShapeDtypeStruct(_lift(shape), r.dtype)
        for (shape, _), r in zip(out_blocks, roots, strict=False)
    ]
    scratch_shapes = [
        pltpu.VMEM(_lift(sshape), np.dtype(sdtype)) for sshape, sdtype in plan.slots
    ]

    n_in, n_out = len(inputs), len(roots)
    root_pos = {r.id: j for j, r in enumerate(roots)}

    def kernel(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in: n_in + n_out]
        scratch = refs[n_in + n_out:]
        # a one-block grid has static windows: no program_id at all
        b = pl.program_id(0) if blocks > 1 else 0

        stored: Dict[int, Sched] = {}
        vals: Dict[int, object] = {}
        for i, instr in enumerate(inputs):
            vals[instr.id] = _Input(
                in_refs[i], chunk_shape(instr.shape, in_scheds[i]), native[i]
            )
            stored[instr.id] = in_scheds[i]

        for m in members:
            sched = assign[m.id]
            if m.opcode == "constant":
                vals[m.id] = apply_op(m)
                stored[m.id] = REPLICATED
                continue
            needed = propagate(m, sched)
            ovals = [
                _adapt(vals[o.id], o, stored[o.id], ns, b)
                for o, ns in zip(m.operands, needed, strict=False)
            ]
            if m.id in solution.exits:
                v = _value(ovals[0])
            else:
                v = _emit_instr(m, sched, ovals, b)
            entry = plan.entries.get(m.id)
            if entry is not None and entry.action in (ALLOC, SHARE):
                # block composition: stitch through the VMEM scratch slot
                ref = scratch[entry.slot]
                _store(ref, v)
                v = _load(ref, v.shape)
            vals[m.id] = v
            stored[m.id] = sched
            if m.id in root_pos:
                _store(out_refs[root_pos[m.id]], v)

    name = kernel_name(fusion)
    call = pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=resolve_interpret(interpret),
        compiler_params=_compiler_params(plan.vmem_need),
        name=name,
    )
    return StitchedKernel(fusion, solution, plan, _boundary(call, inputs, roots, native),
                          inputs, roots, name=name, native=native)


# --------------------------------------------------------------------------
# Multi-phase stitched emission: phases as sequential loops in ONE kernel
# --------------------------------------------------------------------------


def _full_spec(shape) -> pl.BlockSpec:
    """Whole-tensor BlockSpec: the block IS the array (grid is trivial)."""
    shape = _lift(shape)
    return pl.BlockSpec(shape, lambda b, _n=len(shape): (0,) * _n)


def _swapped_block_index(shape, sched: Sched, b) -> tuple:
    return swap_minor(block_index(shape, sched, b))


def _crossing(a, instr: Instruction, native: bool):
    """An operand as it crosses the ``pallas_call``: rank 0 as a (1, 1)
    block, a native-layout input with its minor dims swapped (a bitcast of
    the parameter XLA hands in)."""
    if not instr.shape:
        return jnp.reshape(a, (1, 1))
    return jnp.swapaxes(a, -1, -2) if native else a


def _boundary(
    call, inputs: List[Instruction], roots: List[Instruction], native: Tuple[bool, ...]
) -> Callable:
    """Wrap a pallas_call so its operands cross it as ``_crossing`` gives
    them and exit reshapes apply to its outputs."""

    def fn(*args):
        args = [
            _crossing(a, i, n) for a, i, n in zip(args, inputs, native, strict=True)
        ]
        outs = call(*args)
        outs = outs if isinstance(outs, (list, tuple)) else (outs,)
        return tuple(
            o if o.shape == tuple(r.shape) else jnp.reshape(o, r.shape)
            for o, r in zip(outs, roots, strict=False)
        )

    return fn


def _store_chunk(ref, instr: Instruction, sched: Sched, v, b: int):
    """Write one block's value into a full-shape ref at static offsets."""
    if sched.kind == "replicated" or not instr.shape:
        _store(ref, v)
        return
    starts = _starts(instr.shape, sched, b)
    cs = chunk_shape(instr.shape, sched)
    ref[tuple(slice(s, s + c) for s, c in zip(starts, cs, strict=False))] = _value(v)


def emit_stitched_fusion(
    fusion: FusedComputation,
    stitched: StitchedSolution,
    plan: StitchedMemoryPlan,
    interpret: Optional[bool] = None,
) -> StitchedKernel:
    """Emit ONE Pallas kernel running every phase of a stitched group.

    The launch grid is trivial — each phase's grid is lowered as a
    *sequential loop* over that phase's own block schedule, unrolled at
    trace time (phase grids are capped by ``stitch_max_blocks``).  Inputs
    and outputs are whole-tensor blocks; every interface tensor is staged
    FULLY in a VMEM scratch ref by its producer phase and re-tiled (sliced
    per-block) by its consumer phases — shared-memory stitching across
    schedule breaks, per the FusionStitching follow-up work.
    """
    for m in fusion.members:
        if m.is_collective:
            raise ValueError(
                f"{m.name}: collective {m.opcode} cannot be emitted inside "
                "a stitched kernel; it must stay a standalone schedule break"
            )
    inputs = fusion.inputs
    roots = fusion.roots

    native = tuple(native_block(i, i.shape) for i in inputs)
    in_specs = [
        _full_spec(swap_minor(i.shape) if n else i.shape)
        for i, n in zip(inputs, native, strict=True)
    ]
    out_specs = [_full_spec(r.shape) for r in roots]
    out_shape = [jax.ShapeDtypeStruct(_lift(r.shape), r.dtype) for r in roots]

    # scratch layout: interface staging buffers first, then each phase's
    # chunk-granular slots at a per-phase offset
    scratch_shapes = []
    iface_slot: Dict[int, int] = {}
    for iid, buf in plan.interfaces.items():
        iface_slot[iid] = len(scratch_shapes)
        scratch_shapes.append(pltpu.VMEM(_lift(buf.shape), np.dtype(buf.dtype)))
    phase_offsets: List[int] = []
    for pplan in plan.phase_plans:
        phase_offsets.append(len(scratch_shapes))
        for sshape, sdtype in pplan.slots:
            scratch_shapes.append(pltpu.VMEM(_lift(sshape), np.dtype(sdtype)))

    n_in, n_out = len(inputs), len(roots)
    root_pos = {r.id: j for j, r in enumerate(roots)}

    def kernel(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in: n_in + n_out]
        scratch = refs[n_in + n_out:]

        global_vals: Dict[int, object] = {}
        for i, instr in enumerate(inputs):
            global_vals[instr.id] = _Input(in_refs[i], instr.shape, native[i])

        for pk, phase in enumerate(stitched.phases):
            assign = phase.solution.assignment
            pplan = plan.phase_plans[pk]
            off = phase_offsets[pk]
            # staged interfaces this phase consumes, read back whole — only
            # once their producer phase has fully run (same-phase consumers
            # use the block-local value instead)
            for m in phase.members:
                for o in m.operands:
                    if (
                        o.id in iface_slot
                        and o.id not in global_vals
                        and plan.interfaces[o.id].produced_phase < pk
                    ):
                        global_vals[o.id] = _Input(scratch[iface_slot[o.id]], o.shape)
            for b in range(phase.solution.blocks):
                vals: Dict[int, object] = {}
                stored: Dict[int, Sched] = {}
                for m in phase.members:
                    sched = assign[m.id]
                    if m.opcode == "constant":
                        v = apply_op(m)
                        sched = REPLICATED
                    else:
                        needed = propagate(m, sched)
                        ovals = []
                        for o, ns in zip(m.operands, needed, strict=False):
                            if o.id in vals:
                                ov = _adapt(vals[o.id], o, stored[o.id], ns, b)
                            else:
                                # kernel input or staged interface: stored whole
                                ov = _adapt(
                                    global_vals[o.id], o, REPLICATED, ns, b
                                )
                            ovals.append(ov)
                        v = _emit_instr(m, sched, ovals, b)
                        entry = pplan.entries.get(m.id)
                        if entry is not None and entry.action in (ALLOC, SHARE):
                            ref = scratch[off + entry.slot]
                            _store(ref, v)
                            v = _load(ref, v.shape)
                    vals[m.id] = v
                    stored[m.id] = sched
                    if m.id in iface_slot:
                        _store_chunk(scratch[iface_slot[m.id]], m, sched, v, b)
                    if m.id in root_pos:
                        _store_chunk(out_refs[root_pos[m.id]], m, sched, v, b)

    name = kernel_name(fusion)
    call = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=resolve_interpret(interpret),
        compiler_params=_compiler_params(plan.vmem_need),
        name=name,
    )
    return StitchedKernel(
        fusion, None, plan, _boundary(call, inputs, roots, native), inputs, roots,
        stitched=stitched, name=name, native=native,
    )
