"""``repro.stitch`` — a ``jax.jit``-shaped frontend for the compiler.

    from repro import stitch

    @stitch
    def attention(q, k, v):
        s = q @ jnp.swapaxes(k, -1, -2) / q.shape[-1] ** 0.5
        p = jax.nn.softmax(s, axis=-1)
        return p @ v

    out = attention(q, k, v)        # traced, lowered, compiled, executed
    print(attention.report())       # kernels / fusion ratio / VMEM plan

``stitch(fn)`` returns a ``StitchedFunction``: calling it traces ``fn`` with
``jax.make_jaxpr`` on the arguments' shapes/dtypes, lowers the jaxpr into
StitchIR (``jaxpr_lower``), runs the unchanged pass pipeline via
``compile_module``, and executes the planned runtime.  Compiled plans are
cached per input-signature (static-argument values + pytree structure +
leaf shapes/dtypes), so repeated calls at the same shapes never recompile,
and the per-function ``KernelCache`` is shared across signatures so a new
shape reuses tuned kernels where fusion signatures coincide.

``jax.jit`` parity surface:

  * ``static_argnums`` / ``static_argnames`` — arguments treated as
    compile-time constants and keyed (by value) into the plan cache;
  * ``donate_argnums`` — positional arguments whose buffers the caller
    relinquishes; the traced replay donates them to XLA where the backend
    supports aliasing;
  * ``stitched.lower(*args)`` — a ``Lowered`` handle with ``.as_text()``,
    ``.num_kernels`` and ``.cost_estimate()``, mirroring
    ``jax.jit(fn).lower(...)`` introspection.

``compile_module``/``trace`` remain the documented low-level path for
hand-built StitchIR.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..core.compiler import (
    CompiledModule,
    CompileStats,
    StitchOptions,
    compile_module,
)
from ..core.ir import Module
from ..core.shard import mesh_axes_of, wrap_shard_map
from ..core.signature import KernelCache
from ..tracing import span
from .jaxpr_lower import (
    LoweredJaxpr,
    LoweredShardedJaxpr,
    UnsupportedPrimitiveError,
    lower_jaxpr,
    lower_sharded_jaxpr,
)

_FALLBACK_MODES = ("error", "fallback")


@dataclass
class _PlanEntry:
    """One compiled (or fallen-back) plan for one input signature."""

    lowered: Optional[LoweredJaxpr]      # None => fallback entry
    compiled: Optional[CompiledModule]
    out_tree: Any

    @property
    def is_fallback(self) -> bool:
        return self.lowered is None


def _leaf_spec(leaf) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(np.shape(leaf), jnp.result_type(leaf))


def _leaf_key(leaf) -> Tuple[Tuple[int, ...], np.dtype]:
    """A leaf's part of the plan key: a ``jax.Array``'s own shape and dtype,
    else the shape and dtype tracing gives the leaf (``_leaf_spec``)."""
    if isinstance(leaf, jax.Array):
        return leaf.shape, leaf.dtype
    return tuple(np.shape(leaf)), jnp.result_type(leaf)


def _int_tuple(v, label: str) -> Tuple[int, ...]:
    if v is None:
        return ()
    if isinstance(v, int):
        v = (v,)
    out = tuple(v)
    if not all(isinstance(i, int) for i in out):
        raise TypeError(f"{label} must be an int or a sequence of ints: {v!r}")
    return out


def _str_tuple(v, label: str) -> Tuple[str, ...]:
    if v is None:
        return ()
    if isinstance(v, str):
        v = (v,)
    out = tuple(v)
    if not all(isinstance(s, str) for s in out):
        raise TypeError(f"{label} must be a str or a sequence of strs: {v!r}")
    return out


def _collect_modules(module: Module, acc: List[Module], seen: set) -> None:
    if id(module) in seen:
        return
    seen.add(id(module))
    acc.append(module)
    for instr in module.instructions:
        if instr.opcode == "call":
            _collect_modules(instr.attrs["body"], acc, seen)


@dataclass(frozen=True)
class CostEstimate:
    """Latency estimate for one compiled plan.

    ``analytic_s`` is the pure roofline-model prediction; ``measured_s``
    substitutes on-device timings for the ``measured_kernels`` stitched
    kernels the tuning store had rows for (None when nothing was measured).
    """

    analytic_s: float
    measured_s: Optional[float]
    measured_kernels: int
    num_kernels: int


class Lowered:
    """``jax.jit``-style lowering handle: the captured StitchIR plus lazy
    compilation for introspection (``.as_text()``, ``.num_kernels``,
    ``.cost_estimate()``).  Unknown attributes delegate to ``.module``, so
    existing ``.parameters`` / ``.instructions`` call sites keep working.
    """

    def __init__(
        self,
        lowered: LoweredJaxpr,
        compile_thunk: Callable[[], CompiledModule],
        compiled: Optional[CompiledModule] = None,
    ):
        self._lowered = lowered
        self._compile_thunk = compile_thunk
        self._compiled = compiled

    @property
    def module(self) -> Module:
        return self._lowered.module

    @property
    def param_names(self) -> List[str]:
        return list(self._lowered.param_names)

    def as_text(self) -> str:
        """The module text, loop-body sub-modules appended."""
        mods: List[Module] = []
        _collect_modules(self.module, mods, set())
        return "\n\n".join(repr(m) for m in mods)

    def compile(self) -> CompiledModule:
        if self._compiled is None:
            self._compiled = self._compile_thunk()
        return self._compiled

    @property
    def num_kernels(self) -> int:
        """Total kernels this plan launches code for: stitched + standalone
        + kernels inside unique loop bodies (library dots excluded, as in
        ``CompileStats``)."""
        s = self.compile().stats
        return s.stitched_kernels + s.standalone_kernels + s.sub_kernels

    def cost_estimate(self) -> CostEstimate:
        s = self.compile().stats
        # remainder = standalone ops, library calls, loop bodies — costs not
        # itemized in per-kernel reports
        remainder = s.predicted_time_s - sum(r.cost_s for r in s.reports)
        analytic = remainder + sum(
            r.model_cost_s if r.model_cost_s is not None else r.cost_s
            for r in s.reports
        )
        n_meas = sum(1 for r in s.reports if r.measured_cost_s is not None)
        measured = None
        if n_meas:
            measured = remainder + sum(
                r.measured_cost_s
                if r.measured_cost_s is not None
                else (r.model_cost_s if r.model_cost_s is not None else r.cost_s)
                for r in s.reports
            )
        return CostEstimate(
            analytic_s=analytic,
            measured_s=measured,
            measured_kernels=n_meas,
            num_kernels=self.num_kernels,
        )

    def __getattr__(self, name):
        return getattr(self._lowered.module, name)

    def __repr__(self):
        return f"Lowered({self.module.name}, {len(self.module.instructions)} instructions)"


class StitchedFunction:
    """A JAX function captured into StitchIR and compiled per input shape.

    Attributes/methods of note:
      * ``.options``       — the ``StitchOptions`` this function compiles under
      * ``.stats``         — ``CompileStats`` of the most recent compile
      * ``.lower(*args)``  — a ``Lowered`` introspection handle (no execute)
      * ``.report()``      — human-readable compile report
      * ``.num_compiles`` / ``.num_fallbacks`` — plan-cache accounting
    """

    def __init__(
        self,
        fn: Callable,
        options: Optional[StitchOptions] = None,
        on_unsupported: str = "error",
        name: Optional[str] = None,
        static_argnums: Union[int, Sequence[int], None] = (),
        static_argnames: Union[str, Sequence[str], None] = (),
        donate_argnums: Union[int, Sequence[int], None] = (),
        mesh=None,
        in_specs=None,
        out_specs=None,
    ):
        if not callable(fn):
            raise TypeError(f"stitch() requires a callable, got {type(fn).__name__}")
        if on_unsupported not in _FALLBACK_MODES:
            raise ValueError(
                f"on_unsupported={on_unsupported!r}; valid modes: "
                f"{', '.join(_FALLBACK_MODES)}"
            )
        self._fn = fn
        self.options = options if options is not None else StitchOptions()
        self.mesh = mesh
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.on_unsupported = on_unsupported
        self.name = name or getattr(fn, "__name__", "stitched")
        self.static_argnums = _int_tuple(static_argnums, "static_argnums")
        self.static_argnames = _str_tuple(static_argnames, "static_argnames")
        self.donate_argnums = _int_tuple(donate_argnums, "donate_argnums")
        overlap = set(self.static_argnums) & set(self.donate_argnums)
        if overlap:
            raise ValueError(
                f"static_argnums and donate_argnums cannot intersect: "
                f"{sorted(overlap)}"
            )
        if mesh is not None:
            if in_specs is None or out_specs is None:
                raise ValueError(
                    "stitch(mesh=...) needs in_specs and out_specs — the "
                    "shard_map placement of every argument and output"
                )
            if self.static_argnums or self.static_argnames or self.donate_argnums:
                raise ValueError(
                    "stitch(mesh=...) does not compose with static_argnums/"
                    "static_argnames/donate_argnums yet"
                )
            if not getattr(self.options, "mesh_axes", None):
                self.options = dataclasses.replace(
                    self.options, mesh_axes=mesh_axes_of(mesh)
                )
        elif in_specs is not None or out_specs is not None:
            raise ValueError("in_specs/out_specs require mesh=...")
        self._plans: Dict[Any, _PlanEntry] = {}
        self._kernel_cache = KernelCache(self.options.kernel_cache_path)
        # Shared across this function's per-shape compiles (like the kernel
        # cache): a kernel measured for one input shape guides the planner
        # on the next shape's compile.  Created lazily — most functions
        # never turn autotuning on.
        self._measured_store = None
        self._fallback_jit: Optional[Callable] = None
        self._last: Optional[_PlanEntry] = None
        self.num_compiles = 0
        self.num_fallbacks = 0
        functools.update_wrapper(self, fn)

    # -- static/dynamic argument split ------------------------------------
    def _resolve_nums(self, nums: Tuple[int, ...], n: int, label: str) -> set:
        out = set()
        for i in nums:
            j = i + n if i < 0 else i
            if not 0 <= j < n:
                raise ValueError(
                    f"{label} index {i} is out of range for a call with "
                    f"{n} positional argument(s)"
                )
            out.add(j)
        return out

    def _split(self, args, kwargs):
        """(statics_key, static_positions, dyn_args, dyn_kwargs)."""
        n = len(args)
        static_pos = self._resolve_nums(self.static_argnums, n, "static_argnums") \
            if self.static_argnums else set()
        static_names = set(self.static_argnames) & set(kwargs)
        statics = tuple(
            [(j, args[j]) for j in sorted(static_pos)]
            + [(k, kwargs[k]) for k in sorted(static_names)]
        )
        try:
            hash(statics)
        except TypeError as e:
            bad = [
                f"{tag}={type(v).__name__}" for tag, v in statics
                if not _hashable(v)
            ]
            raise TypeError(
                "Non-hashable static arguments are not supported: "
                + ", ".join(bad)
            ) from e
        dyn_args = tuple(a for i, a in enumerate(args) if i not in static_pos)
        dyn_kwargs = {k: v for k, v in kwargs.items() if k not in static_names}
        return statics, static_pos, dyn_args, dyn_kwargs

    def _donated_param_names(
        self, n_args: int, static_pos: set, dyn_args
    ) -> Optional[frozenset]:
        """Flattened-leaf parameter names covered by ``donate_argnums``.

        Parameters are named ``arg{i}`` over the flattened ``(dyn_args,
        dyn_kwargs)`` leaves, positional leaves first — so per-argument
        leaf counts locate each donated argument's name range."""
        if not self.donate_argnums:
            return None
        donated = self._resolve_nums(self.donate_argnums, n_args, "donate_argnums")
        if donated & static_pos:
            raise ValueError(
                "donate_argnums resolve onto static arguments: "
                f"{sorted(donated & static_pos)}"
            )
        dyn_positions = [i for i in range(n_args) if i not in static_pos]
        names: List[str] = []
        off = 0
        for dyn_idx, orig in enumerate(dyn_positions):
            cnt = len(jax.tree_util.tree_leaves(dyn_args[dyn_idx]))
            if orig in donated:
                names.extend(f"arg{off + k}" for k in range(cnt))
            off += cnt
        return frozenset(names) if names else None

    # -- plan cache -------------------------------------------------------
    def _signature(self, args, kwargs):
        statics, static_pos, dyn_args, dyn_kwargs = self._split(args, kwargs)
        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
        key = (statics, treedef, tuple(map(_leaf_key, leaves)))
        return key, leaves, static_pos, dyn_args, dyn_kwargs, len(args)

    def _trace(self, args, static_pos, dyn_args, dyn_kwargs, kwargs):
        """jax.make_jaxpr on the dynamic arguments' shapes; static values
        close over the traced function, so they are compile-time constants
        of the captured jaxpr (recompiled per distinct static value via the
        plan-cache key)."""
        n = len(args)
        static_vals = {i: args[i] for i in static_pos}
        static_kw = {
            k: kwargs[k] for k in self.static_argnames if k in kwargs
        }
        fn = self._fn

        def inner(*dyn, **dyn_kw):
            full = []
            it = iter(dyn)
            for i in range(n):
                full.append(static_vals[i] if i in static_vals else next(it))
            kw = dict(static_kw)
            kw.update(dyn_kw)
            return fn(*full, **kw)

        shaped_args, shaped_kwargs = jax.tree_util.tree_map(
            _leaf_spec, (dyn_args, dyn_kwargs)
        )
        if self.mesh is not None:
            # Trace shard_map(fn) at GLOBAL shapes: jax leaves exactly one
            # shard_map eqn whose inner jaxpr is the per-shard computation —
            # that is what lower_sharded_jaxpr compiles.
            inner = wrap_shard_map(
                inner, self.mesh, self.in_specs, self.out_specs
            )
        with span("repro.trace"):
            closed, out_shape = jax.make_jaxpr(inner, return_shape=True)(
                *shaped_args, **shaped_kwargs
            )
        return closed, jax.tree_util.tree_structure(out_shape)

    def _get_measured_store(self):
        if self._measured_store is None and (
            self.options.autotune or self.options.tuning_store_path
        ):
            from ..core.measure import MeasuredCostStore, device_fingerprint

            self._measured_store = MeasuredCostStore(
                self.options.tuning_store_path,
                device_fp=device_fingerprint(
                    interpret=self.options.interpret
                ),
            )
        return self._measured_store

    def _lower(self, closed) -> LoweredJaxpr:
        lower = lower_sharded_jaxpr if self.mesh is not None else lower_jaxpr
        with span("repro.lower"):
            return lower(closed, name=self.name, fuse_dot=self.options.fuse_dot)

    def _plan_device(self, dyn_args, dyn_kwargs):
        """The device the plan runs on: the mesh's first, else the one
        device an argument is placed on (an array, or a ``ShapeDtypeStruct``
        with a sharding), else JAX's default device."""
        if self.mesh is not None:
            return self.mesh.devices.flat[0]
        for leaf in jax.tree_util.tree_leaves((dyn_args, dyn_kwargs)):
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and len(sharding.device_set) == 1:
                return next(iter(sharding.device_set))
        return jax.devices()[0]

    def _compile_lowered(
        self, lowered: LoweredJaxpr, donate_params: Optional[frozenset], device
    ) -> CompiledModule:
        sharded = isinstance(lowered, LoweredShardedJaxpr)
        return compile_module(
            lowered.module, self.options, kernel_cache=self._kernel_cache,
            measured_store=self._get_measured_store(),
            donate_params=donate_params,
            mesh=lowered.mesh if sharded else None,
            param_layouts=lowered.param_layouts if sharded else None,
            out_layouts=lowered.out_layouts if sharded else None,
            device=device,
        )

    def _fallback(self) -> Callable:
        if self._fallback_jit is None:
            if self.mesh is not None:
                # The sharded oracle: the same shard_map placement, compiled
                # whole by XLA — also the bit-parity reference in the tests.
                self._fallback_jit = jax.jit(
                    wrap_shard_map(
                        self._fn, self.mesh, self.in_specs, self.out_specs
                    )
                )
            else:
                self._fallback_jit = jax.jit(
                    self._fn,
                    static_argnums=self.static_argnums,
                    static_argnames=self.static_argnames,
                    donate_argnums=self.donate_argnums,
                )
        return self._fallback_jit

    def _compile(
        self, key, args, kwargs, static_pos, dyn_args, dyn_kwargs, n_args
    ) -> _PlanEntry:
        closed, out_tree = self._trace(
            args, static_pos, dyn_args, dyn_kwargs, kwargs
        )
        try:
            lowered = self._lower(closed)
        except UnsupportedPrimitiveError:
            if self.on_unsupported != "fallback":
                raise
            self._fallback()
            self.num_fallbacks += 1
            entry = _PlanEntry(None, None, out_tree)
            self._plans[key] = entry
            return entry
        compiled = self._compile_lowered(
            lowered,
            self._donated_param_names(n_args, static_pos, dyn_args),
            self._plan_device(dyn_args, dyn_kwargs),
        )
        self.num_compiles += 1
        entry = _PlanEntry(lowered, compiled, out_tree)
        self._plans[key] = entry
        self._last = entry
        return entry

    # -- the jit-shaped surface -------------------------------------------
    def __call__(self, *args, **kwargs):
        with span("repro.call"):
            with span("repro.prepare"):
                key, leaves, static_pos, dyn_args, dyn_kwargs, n_args = (
                    self._signature(args, kwargs)
                )
                entry = self._plans.get(key)
            if entry is None:
                entry = self._compile(
                    key, args, kwargs, static_pos, dyn_args, dyn_kwargs, n_args
                )
            if entry.is_fallback:
                return self._fallback()(*args, **kwargs)
            feeds = dict(zip(entry.lowered.param_names, leaves, strict=False))
            out = entry.compiled(feeds)
            flat = [out[n] for n in entry.lowered.output_names]
            return jax.tree_util.tree_unflatten(entry.out_tree, flat)

    def lower(self, *args, **kwargs) -> Lowered:
        """A ``Lowered`` introspection handle (``jax.jit(...).lower()``
        analogue): ``.module`` / ``.as_text()`` inspect the captured
        StitchIR without compiling; ``.num_kernels`` / ``.cost_estimate()``
        compile lazily on first use.

        With arguments (arrays or ``ShapeDtypeStruct``s): trace + lower for
        those shapes.  Without arguments: the most recent compiled call.
        """
        if args or kwargs:
            key, _, static_pos, dyn_args, dyn_kwargs, n_args = (
                self._signature(args, kwargs)
            )
            entry = self._plans.get(key)
            if entry is not None and not entry.is_fallback:
                return Lowered(
                    entry.lowered,
                    lambda: entry.compiled,
                    compiled=entry.compiled,
                )
            closed, _ = self._trace(
                args, static_pos, dyn_args, dyn_kwargs, kwargs
            )
            lowered = self._lower(closed)
            donate = self._donated_param_names(n_args, static_pos, dyn_args)
            device = self._plan_device(dyn_args, dyn_kwargs)
            return Lowered(
                lowered, lambda: self._compile_lowered(lowered, donate, device)
            )
        if self._last is None:
            raise ValueError(
                f"{self.name} has not been compiled yet — call it (or pass "
                "example arguments to .lower())"
            )
        entry = self._last
        return Lowered(
            entry.lowered, lambda: entry.compiled, compiled=entry.compiled
        )

    @property
    def stats(self) -> CompileStats:
        """CompileStats of the most recent compile."""
        if self._last is None:
            if self.num_fallbacks:
                raise ValueError(
                    f"{self.name} has no compile stats: all "
                    f"{self.num_fallbacks} signature(s) fell back to plain "
                    "jax.jit (on_unsupported='fallback'), so nothing was "
                    "captured into StitchIR"
                )
            raise ValueError(
                f"{self.name} has not been compiled yet — call it first"
            )
        return self._last.compiled.stats

    def report(self) -> str:
        """Human-readable summary of the most recent compile."""
        s = self.stats
        m = self._last.lowered.module
        lines = [
            f"stitched function {self.name}: "
            f"{len(m.instructions)} StitchIR instructions, "
            f"{len(m.parameters)} parameters",
            f"  stitched kernels : {s.stitched_kernels}",
            f"  standalone       : {s.standalone_kernels}",
            f"  library calls    : {s.library_calls}",
            f"  XLA baseline     : {s.xla_baseline_kernels} kernels "
            f"(fusion ratio {s.fusion_ratio:.3f})",
            f"  plan cache       : {len(self._plans)} signature(s), "
            f"{self.num_compiles} compile(s), {self.num_fallbacks} fallback(s)",
            f"  dot batch blocks : {s.dot_batch_blocks} kernel(s) read several "
            "whole matrices a block",
        ]
        if s.loop_calls:
            lines.insert(
                5,
                f"  loop calls       : {s.loop_calls} site(s), "
                f"{s.sub_compiles} unique body(ies), "
                f"{s.sub_kernels} body kernel(s)",
            )
        for r in s.reports:
            lines.append(
                f"    kernel {r.name}: {r.num_ops} ops, {r.blocks} blocks, "
                f"{r.scratch_bytes}B VMEM scratch, roots={r.roots}"
            )
        return "\n".join(lines)

    def __repr__(self):
        return (
            f"StitchedFunction({self.name}, planner={self.options.planner!r}, "
            f"{len(self._plans)} cached plan(s))"
        )


def _hashable(v) -> bool:
    try:
        hash(v)
        return True
    except TypeError:
        return False


def stitch(
    fn: Optional[Callable] = None,
    *,
    options: Optional[StitchOptions] = None,
    on_unsupported: str = "error",
    name: Optional[str] = None,
    autotune: Optional[bool] = None,
    static_argnums: Union[int, Sequence[int], None] = (),
    static_argnames: Union[str, Sequence[str], None] = (),
    donate_argnums: Union[int, Sequence[int], None] = (),
    mesh=None,
    in_specs=None,
    out_specs=None,
) -> StitchedFunction:
    """Capture a JAX function into StitchIR and compile it per input shape.

    Usable directly (``stitched = stitch(fn)``) or as a decorator, bare or
    parameterized::

        @stitch
        def f(x): ...

        @stitch(options=StitchOptions(planner="greedy"))
        def g(x): ...

    ``on_unsupported``: ``"error"`` (default) raises
    ``UnsupportedPrimitiveError`` when the function uses a primitive outside
    the supported set; ``"fallback"`` executes the whole function through
    plain ``jax.jit`` instead, so partial coverage never blocks a caller.

    ``static_argnums`` / ``static_argnames`` mirror ``jax.jit``: the named
    arguments are compile-time constants, keyed by value into the plan
    cache (values must be hashable).  ``donate_argnums`` marks positional
    arguments whose buffers the caller gives up — the traced replay donates
    them to XLA on backends with buffer aliasing.

    ``autotune``: convenience override of ``options.autotune`` —
    ``stitch(fn, autotune=True)`` times each unique kernel once on device
    and re-plans later shapes against measured costs (``core/measure.py``).

    ``mesh`` + ``in_specs`` + ``out_specs`` compile ``fn`` as ONE
    multi-device plan: the function is traced under ``shard_map`` with that
    placement, collectives (``lax.psum`` family) lower to StitchIR
    collective instructions (natural fusion breaks), fusion scores
    per-shard tiles, and the whole ExecutionPlan replays under a single
    ``jax.jit(shard_map(...))`` — bit-identical to jitting the shard_map
    directly.  Callers pass GLOBAL arrays, as with ``jax.jit`` over a
    sharded computation.
    """
    if fn is None:
        return functools.partial(
            stitch, options=options, on_unsupported=on_unsupported,
            name=name, autotune=autotune, static_argnums=static_argnums,
            static_argnames=static_argnames, donate_argnums=donate_argnums,
            mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        )
    if autotune is not None:
        options = dataclasses.replace(
            options if options is not None else StitchOptions(),
            autotune=autotune,
        )
    return StitchedFunction(
        fn, options=options, on_unsupported=on_unsupported, name=name,
        static_argnums=static_argnums, static_argnames=static_argnames,
        donate_argnums=donate_argnums, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs,
    )
