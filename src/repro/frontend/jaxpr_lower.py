"""Lower a jaxpr captured from a real JAX function into StitchIR.

The paper's compiler consumes *framework-captured* computations (TF graphs
fed to XLA as HLO), not hand-transcribed IR.  This module closes that gap
for the reproduction: ``lower_jaxpr`` walks a ``ClosedJaxpr`` (produced by
``jax.make_jaxpr`` on shaped arguments) and emits the equivalent StitchIR
``Module`` through the existing ``GraphBuilder``, so the unchanged pass
pipeline (fusion -> schedule -> memory -> codegen) compiles real
``jax.numpy`` programs.

Lowering rules worth knowing:

  * jaxprs broadcast *implicitly* in two places StitchIR does not: scalar
    literals appear directly as elementwise operands (``mul a 0.17``), and
    rank-equal operands may carry degenerate (size-1) dims (``sub f[...,16]
    h[...,1]``).  ``_to_shape`` materializes both as explicit ``broadcast``
    instructions — the same shape ops a hand-built graph writes.
  * ``dot_general`` is canonicalized to StitchIR's batched-matmul ``dot``
    (contract lhs[-1] with rhs[-2], leading batch dims) via transposes and
    reshapes; the common ``q @ k.T`` layouts lower with no extra ops.
  * call-like primitives (``pjit``, ``custom_jvp_call``, ...) are inlined
    recursively, so ``jax.nn`` activations and ``jnp.where`` lower to their
    bodies instead of failing on the wrapper.
  * literals and closure constants fold as IR ``constant``s; the compiler's
    constant folding evaluates them once at plan-build time.

Anything else raises ``UnsupportedPrimitiveError`` naming the primitive and
its eqn (``repro.stitch`` turns that into a plain ``jax.jit`` fallback when
``on_unsupported="fallback"``).
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from jax.extend.core import Literal

from ..core.ir import GraphBuilder, Module, Tensor, _prod


# --------------------------------------------------------------------------
# Primitive tables (the README "supported primitives" table is generated
# from these — keep names in sync with jax.lax primitive names)
# --------------------------------------------------------------------------

#: jaxpr unary primitive -> StitchIR elementwise fn
UNARY_PRIMS: Dict[str, str] = {
    "exp": "exp",
    "log": "log",
    "tanh": "tanh",
    "sqrt": "sqrt",
    "rsqrt": "rsqrt",
    "neg": "neg",
    "abs": "abs",
    "sign": "sign",
    "floor": "floor",
    "logistic": "sigmoid",
    "not": "not",
    "cos": "cos",
    "sin": "sin",
}

#: jaxpr binary primitive -> StitchIR elementwise fn
BINARY_PRIMS: Dict[str, str] = {
    "add": "add",
    "add_any": "add",   # transpose-rule accumulation (jax.grad cotangents)
    "sub": "sub",
    "mul": "mul",
    "div": "div",
    "max": "max",
    "min": "min",
    "pow": "pow",
    "lt": "lt",
    "le": "le",
    "gt": "gt",
    "ge": "ge",
    "eq": "eq",
    "ne": "ne",
    "and": "and",
    "or": "or",
}

#: jaxpr reduce primitive -> StitchIR reduce kind
REDUCE_PRIMS: Dict[str, str] = {
    "reduce_sum": "sum",
    "reduce_max": "max",
    "reduce_min": "min",
    "reduce_prod": "prod",
}

#: value-preserving primitives lowered as aliases (no instruction emitted;
#: device placement is meaningless in StitchIR, so device_put aliases too)
IDENTITY_PRIMS = frozenset({"stop_gradient", "copy", "device_put"})

#: call-like primitives whose inner jaxpr is inlined ("remat2" is the
#: primitive jax.checkpoint/jax.remat actually emit)
CALL_PRIMS = frozenset(
    {"jit", "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
     "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr", "remat", "remat2",
     "checkpoint"}
)

#: structural primitives with bespoke lowerings below
STRUCTURAL_PRIMS = frozenset(
    {"dot_general", "broadcast_in_dim", "transpose", "reshape", "squeeze",
     "convert_element_type", "select_n", "integer_pow", "concatenate",
     "iota", "square", "clamp"}
)

#: control-flow primitives: ``scan`` lowers to a sub-module ``call`` loop
#: (``fori_loop`` over static Python-int bounds lowers to ``scan`` inside
#: jax, so it arrives here as one); ``while`` lowers the same way when a
#: static trip count is provable from the canonical counter pattern;
#: ``cond`` inlines both branches behind ``select``.
CONTROL_FLOW_PRIMS = frozenset({"scan", "while", "cond"})

#: collective primitives (appear only in shard_map-traced jaxprs, where an
#: axis env binds the mesh axis names) lowered to StitchIR collective
#: instructions — standalone schedule breaks replayed as lax.psum-family
#: calls, never fused into kernels.
COLLECTIVE_PRIMS = frozenset({"psum", "all_gather", "reduce_scatter"})

#: collectives the frontend recognizes but does not lower yet: named error
#: (with the fallback hint) instead of the generic unknown-primitive one.
UNLOWERED_COLLECTIVE_PRIMS = frozenset(
    {"ppermute", "all_to_all", "pmax", "pmin", "pbroadcast", "pgather",
     "axis_index", "psum_scatter"}
)

SUPPORTED_PRIMITIVES = frozenset(
    set(UNARY_PRIMS) | set(BINARY_PRIMS) | set(REDUCE_PRIMS)
    | IDENTITY_PRIMS | CALL_PRIMS | STRUCTURAL_PRIMS | CONTROL_FLOW_PRIMS
    | COLLECTIVE_PRIMS
)


class UnsupportedPrimitiveError(NotImplementedError):
    """A jaxpr primitive the frontend cannot lower to StitchIR.

    Carries the primitive name (``.primitive``) and the offending eqn
    (``.eqn``) so callers can report exactly what blocked the capture.
    """

    def __init__(self, primitive, eqn=None, reason: str = ""):
        self.primitive = str(primitive)
        self.eqn = eqn
        msg = f"jaxpr primitive '{self.primitive}' is not supported by repro.stitch"
        if reason:
            msg += f" ({reason})"
        if eqn is not None:
            msg += f"\n  in eqn: {eqn}"
        msg += (
            f"\nsupported primitives: {', '.join(sorted(SUPPORTED_PRIMITIVES))}"
            "\nhint: stitch(fn, on_unsupported='fallback') runs the whole "
            "function through plain jax.jit instead of failing."
        )
        super().__init__(msg)


@dataclass
class LoweredJaxpr:
    """A captured function: the StitchIR module plus its calling convention.

    ``param_names`` name the module parameters in flattened-argument order;
    ``output_names`` name one module root per flattened output (outputs that
    alias a parameter/constant or an interior value get a value-preserving
    ``reshape`` sink so the executor materializes them).
    """

    module: Module
    param_names: List[str]
    output_names: List[str]


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


def _live_eqns(eqns, live_outvars):
    """Reverse-liveness DCE over a jaxpr's eqns.

    ``jax.make_jaxpr`` does NOT dead-code-eliminate (jax.jit's DCE happens
    in XLA, after our capture point), so unused intermediates would lower
    to user-less instructions — which the compiler treats as module roots
    and computes on every call.  Keep only eqns whose outputs are
    (transitively) live; call-like eqns are treated atomically, with the
    same pruning applied to their inner jaxpr during inlining.

    Returns ``(kept_eqns, live_vars)`` — ``live_vars`` additionally gates
    constvar materialization (a dead closure constant must not become a
    user-less IR constant, i.e. a module root).

    Side-effecting eqns (``jax.debug.print``, ``io_callback``, ...) are
    always kept even with no live outputs: silently dropping an effect
    would diverge from ``jax.jit``, so they must reach the lowering and
    raise ``UnsupportedPrimitiveError`` (or trigger fallback) instead."""
    live = {v for v in live_outvars if not isinstance(v, Literal)}
    kept = []
    for eqn in reversed(eqns):
        if getattr(eqn, "effects", None) or any(
            not _is_dropvar(v) and v in live for v in eqn.outvars
        ):
            kept.append(eqn)
            live.update(v for v in eqn.invars if not isinstance(v, Literal))
    kept.reverse()
    return kept, live


class _Lowerer:
    def __init__(self, builder: GraphBuilder, fuse_dot: bool):
        self.b = builder
        self.fuse_dot = fuse_dot
        #: live vars of the jaxpr currently being lowered (set by
        #: ``lower_jaxpr`` / saved+restored around inlined sub-jaxprs);
        #: multi-output eqns consult it so dead outputs never become
        #: user-less instructions (= accidental module roots).
        self.live: set = set()

    # -- environment ------------------------------------------------------
    def read(self, env: Dict, atom) -> Tensor:
        if isinstance(atom, Literal):
            val = np.asarray(atom.val, dtype=atom.aval.dtype)
            return self.b.constant(val)
        return env[atom]

    def to_shape(self, t: Tensor, shape: Sequence[int]) -> Tensor:
        """Materialize jaxpr implicit broadcasting (scalars + size-1 dims)."""
        shape = tuple(int(s) for s in shape)
        if tuple(t.shape) == shape:
            return t
        if t.ndim == 0:
            return self.b.broadcast(t, shape, ())
        if t.ndim == len(shape):
            return self.b.broadcast(t, shape, tuple(range(t.ndim)))
        raise ValueError(
            f"cannot broadcast rank-{t.ndim} value {tuple(t.shape)} to {shape}"
        )

    # -- eqn dispatch -----------------------------------------------------
    def lower_eqns(self, env: Dict, eqns) -> None:
        for eqn in eqns:
            self.lower_eqn(env, eqn)

    def lower_eqn(self, env: Dict, eqn) -> None:
        prim = eqn.primitive.name
        if prim in CALL_PRIMS:
            self._inline_call(env, eqn)
            return
        if prim == "scan":
            self._lower_scan(env, eqn)
            return
        if prim == "while":
            self._lower_while(env, eqn)
            return
        if prim == "cond":
            self._lower_cond(env, eqn)
            return
        outs = self._lower_value_eqn(env, eqn)
        for var, t in zip(eqn.outvars, outs, strict=False):
            if not _is_dropvar(var):
                env[var] = t

    def _inline_call(self, env: Dict, eqn) -> None:
        sub = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            if key in eqn.params:
                sub = eqn.params[key]
                break
        if sub is None:
            raise UnsupportedPrimitiveError(
                eqn.primitive.name, eqn, "call primitive with no inner jaxpr"
            )
        inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
        consts = sub.consts if hasattr(sub, "consts") else []
        args = [self.read(env, v) for v in eqn.invars]
        if len(args) != len(inner.invars):
            raise UnsupportedPrimitiveError(
                eqn.primitive.name, eqn,
                f"arity mismatch inlining inner jaxpr "
                f"({len(args)} args vs {len(inner.invars)} invars)",
            )
        live_outs = [
            iv for ov, iv in zip(eqn.outvars, inner.outvars, strict=False)
            if not _is_dropvar(ov) and ov in self.live
        ]
        kept, live = _live_eqns(inner.eqns, live_outs)
        sub_env: Dict = {}
        for var, const in zip(inner.constvars, consts, strict=False):
            if var in live:
                sub_env[var] = self.b.constant(np.asarray(const))
        for var, t in zip(inner.invars, args, strict=False):
            sub_env[var] = t
        saved, self.live = self.live, live
        try:
            self.lower_eqns(sub_env, kept)
        finally:
            self.live = saved
        for outer, inner_out in zip(eqn.outvars, inner.outvars, strict=False):
            if not _is_dropvar(outer) and outer in self.live:
                env[outer] = self.read(sub_env, inner_out)

    def _lower_value_eqn(self, env: Dict, eqn) -> List[Tensor]:
        prim = eqn.primitive.name
        b = self.b
        out_aval = eqn.outvars[0].aval

        if prim in IDENTITY_PRIMS:
            return [self.read(env, eqn.invars[0])]

        if prim in UNARY_PRIMS:
            return [b.unary(UNARY_PRIMS[prim], self.read(env, eqn.invars[0]))]

        if prim in BINARY_PRIMS:
            lhs = self.to_shape(self.read(env, eqn.invars[0]), out_aval.shape)
            rhs = self.to_shape(self.read(env, eqn.invars[1]), out_aval.shape)
            return [b.binary(BINARY_PRIMS[prim], lhs, rhs)]

        if prim in REDUCE_PRIMS:
            x = self.read(env, eqn.invars[0])
            axes = tuple(eqn.params["axes"])
            if not axes:  # reduce over no axes is the identity
                return [x]
            return [b.reduce(x, axes, REDUCE_PRIMS[prim])]

        if prim == "square":
            return [b.square(self.read(env, eqn.invars[0]))]

        if prim == "integer_pow":
            return [self._integer_pow(env, eqn)]

        if prim == "convert_element_type":
            x = self.read(env, eqn.invars[0])
            new = np.dtype(eqn.params["new_dtype"])
            if np.dtype(x.dtype) == new:
                return [x]
            return [b.convert(x, new)]

        if prim == "broadcast_in_dim":
            x = self.read(env, eqn.invars[0])
            shape = tuple(int(s) for s in eqn.params["shape"])
            dims = tuple(eqn.params["broadcast_dimensions"])
            if tuple(x.shape) == shape and dims == tuple(range(x.ndim)):
                return [x]
            return [b.broadcast(x, shape, dims)]

        if prim == "transpose":
            x = self.read(env, eqn.invars[0])
            perm = tuple(eqn.params["permutation"])
            if perm == tuple(range(x.ndim)):
                return [x]
            if (
                perm == (1, 0)
                and x.instr.opcode == "dot"
                and not x.instr.users
                and all(o.ndim == 2 for o in x.instr.operands)
            ):
                # transpose(dot(a, b)) == dot(b^T, a^T).  AD emits this for
                # every weight gradient (dw = (dy^T @ x)^T); commuting keeps
                # the dot's result in the default layout — XLA CPU otherwise
                # folds the result-transpose into a column-major dot output
                # layout its DotThunk refuses to execute.  The original dot
                # is orphaned here; lower_jaxpr's dead-instruction sweep
                # removes it unless a later eqn still reads it.
                return [self._commute_dot_transpose(x.instr)]
            return [b.transpose(x, perm)]

        if prim == "reshape":
            if eqn.params.get("dimensions") is not None:
                raise UnsupportedPrimitiveError(
                    prim, eqn, "reshape with a dimensions permutation"
                )
            x = self.read(env, eqn.invars[0])
            new = tuple(int(s) for s in eqn.params["new_sizes"])
            if tuple(x.shape) == new:
                return [x]
            return [b.reshape(x, new)]

        if prim == "squeeze":
            x = self.read(env, eqn.invars[0])
            return [b.reshape(x, tuple(int(s) for s in out_aval.shape))]

        if prim == "concatenate":
            xs = [self.read(env, v) for v in eqn.invars]
            return [b.concat(xs, int(eqn.params["dimension"]))]

        if prim == "iota":
            shape = tuple(int(s) for s in eqn.params["shape"])
            return [b.iota(shape, int(eqn.params["dimension"]),
                           np.dtype(eqn.params["dtype"]))]

        if prim == "clamp":
            # lax.clamp(lo, x, hi) == min(max(x, lo), hi) elementwise
            lo = self.to_shape(self.read(env, eqn.invars[0]), out_aval.shape)
            x = self.to_shape(self.read(env, eqn.invars[1]), out_aval.shape)
            hi = self.to_shape(self.read(env, eqn.invars[2]), out_aval.shape)
            return [b.binary("min", b.binary("max", x, lo), hi)]

        if prim == "select_n":
            if len(eqn.invars) != 3:
                raise UnsupportedPrimitiveError(
                    prim, eqn, f"{len(eqn.invars) - 1}-case select "
                    "(only boolean 2-case select is supported)"
                )
            pred = self.to_shape(self.read(env, eqn.invars[0]), out_aval.shape)
            if np.dtype(pred.dtype) != np.dtype(np.bool_):
                raise UnsupportedPrimitiveError(
                    prim, eqn, "select_n with a non-boolean selector"
                )
            # select_n(pred, *cases): cases[0] is the False branch
            on_false = self.to_shape(self.read(env, eqn.invars[1]), out_aval.shape)
            on_true = self.to_shape(self.read(env, eqn.invars[2]), out_aval.shape)
            return [b.select(pred, on_true, on_false)]

        if prim == "dot_general":
            return [self._dot_general(env, eqn)]

        if prim in COLLECTIVE_PRIMS:
            return self._lower_collective(env, eqn)

        if prim in UNLOWERED_COLLECTIVE_PRIMS:
            raise UnsupportedPrimitiveError(
                prim, eqn,
                "collective not lowered by the sharded frontend yet; only "
                "psum, all_gather and reduce_scatter compile to StitchIR",
            )

        raise UnsupportedPrimitiveError(prim, eqn)

    def _lower_collective(self, env: Dict, eqn) -> List[Tensor]:
        """psum/all_gather/reduce_scatter -> StitchIR collective instructions.

        These only appear in shard_map-traced jaxprs (an axis env must bind
        the names); the executor replays them as the matching lax call
        inside its own shard_map, so axis semantics round-trip exactly."""
        b = self.b
        prim = eqn.primitive.name
        p = eqn.params
        if p.get("axis_index_groups") is not None:
            raise UnsupportedPrimitiveError(
                prim, eqn, "axis_index_groups subgrouping is not supported"
            )
        raw = p["axes"] if prim == "psum" else p["axis_name"]
        axes = (raw,) if isinstance(raw, str) else tuple(raw)
        if not axes or not all(isinstance(a, str) for a in axes):
            raise UnsupportedPrimitiveError(
                prim, eqn,
                "positional (vmap) axes cannot lower to mesh collectives",
            )
        if prim == "psum":
            # one all_reduce per operand (lax.psum over a tree arrives as a
            # single multi-operand eqn)
            return [b.all_reduce(self.read(env, v), axes) for v in eqn.invars]
        if not p.get("tiled", False):
            raise UnsupportedPrimitiveError(
                prim, eqn,
                "untiled gather/scatter (a fresh leading dim) is not "
                "supported; lax.all_gather(..., tiled=True) and "
                "lax.psum_scatter(..., tiled=True) compile",
            )
        x = self.read(env, eqn.invars[0])
        g = int(p["axis_size"])
        if prim == "all_gather":
            return [b.all_gather(x, axes, int(p["all_gather_dimension"]), g)]
        return [b.reduce_scatter(x, axes, int(p["scatter_dimension"]), g)]

    # -- bespoke lowerings ------------------------------------------------
    def _integer_pow(self, env: Dict, eqn) -> Tensor:
        """x ** n as XLA lowers it: repeated multiplication (never a
        transcendental ``pow``, which diverges on negative bases)."""
        b = self.b
        x = self.read(env, eqn.invars[0])
        n = int(eqn.params["y"])
        if n == 0:
            one = b.constant(np.asarray(1, dtype=x.dtype))
            return self.to_shape(one, x.shape)
        out = x
        if abs(n) == 2:
            out = b.square(x)
        else:
            for _ in range(abs(n) - 1):
                out = b.binary("mul", out, x)
        if n < 0:
            out = b.unary("reciprocal", out)
        return out

    def _dot_general(self, env: Dict, eqn) -> Tensor:
        """Canonicalize an arbitrary dot_general to StitchIR ``dot``:
        (batch..., M, K) x (batch..., K, N) with leading batch dims, via
        transposes/reshapes.  The output dim order of dot_general —
        (batch, lhs free, rhs free) — is exactly what the canonical form
        produces, so a final reshape restores the declared shape."""
        b = self.b
        lhs = self.read(env, eqn.invars[0])
        rhs = self.read(env, eqn.invars[1])
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lc, rc, lb, rb = map(tuple, (lc, rc, lb, rb))
        out_aval = eqn.outvars[0].aval
        lfree = tuple(d for d in range(lhs.ndim) if d not in lc and d not in lb)
        rfree = tuple(d for d in range(rhs.ndim) if d not in rc and d not in rb)

        def permute(t: Tensor, perm: Tuple[int, ...]) -> Tensor:
            if perm == tuple(range(t.ndim)):
                return t
            return b.transpose(t, perm)

        left = permute(lhs, lb + lfree + lc)
        right = permute(rhs, rb + rc + rfree)
        batch = tuple(int(lhs.shape[d]) for d in lb)
        m = _prod([lhs.shape[d] for d in lfree])
        k = _prod([lhs.shape[d] for d in lc])
        n = _prod([rhs.shape[d] for d in rfree])
        if tuple(left.shape) != batch + (m, k):
            left = b.reshape(left, batch + (m, k))
        if tuple(right.shape) != batch + (k, n):
            right = b.reshape(right, batch + (k, n))
        out = b.dot(left, right, fusable=self.fuse_dot)
        out_shape = tuple(int(s) for s in out_aval.shape)
        if tuple(out.shape) != out_shape:
            out = b.reshape(out, out_shape)
        if np.dtype(out.dtype) != np.dtype(out_aval.dtype):
            out = b.convert(out, out_aval.dtype)
        return out

    def _commute_dot_transpose(self, dot_instr) -> Tensor:
        """``dot(a, b)^T`` as ``dot(b^T, a^T)``, cancelling an operand that
        is itself a rank-2 transpose instead of stacking a second one."""
        b = self.b

        def flipped(instr) -> Tensor:
            if instr.opcode == "transpose" and tuple(instr.attrs["perm"]) == (1, 0):
                return Tensor(b, instr.operands[0])
            return b.transpose(Tensor(b, instr), (1, 0))

        lhs, rhs = dot_instr.operands
        return b.dot(
            flipped(rhs), flipped(lhs),
            fusable=bool(dot_instr.attrs.get("fusable", True)),
        )

    # -- control flow ------------------------------------------------------
    def _emit_loop(
        self,
        env: Dict,
        eqn,
        body_closed,
        operands: List[Tensor],
        *,
        num_consts: int,
        num_carry: int,
        trip_count: int,
        reverse: bool,
        kind: str,
    ) -> None:
        """Shared scan/while tail: lower ``body_closed`` as a sub-module,
        emit one ``call`` loop and a ``get`` per live outer output.

        The contract with the executor is fully positional (operand order =
        body parameter-creation order; ``out_order`` maps logical output j
        to its position among the body's roots), so two structurally
        identical bodies — e.g. stacked transformer layers — share one
        compiled sub-module via ``module_signature``."""
        inner = body_closed.jaxpr
        n_x = len(inner.invars) - num_consts - num_carry
        pnames = (
            [f"c{i}" for i in range(num_consts)]
            + [f"h{i}" for i in range(num_carry)]
            + [f"x{i}" for i in range(n_x)]
        )
        sub = lower_jaxpr(
            body_closed,
            name=f"{self.b.module.name}.{kind}_body",
            fuse_dot=self.fuse_dot,
            param_names=pnames,
        )
        root_pos = {r.name: i for i, r in enumerate(sub.module.roots)}
        out_order = [root_pos[n] for n in sub.output_names]
        call = self.b.call_loop(
            operands,
            sub.module,
            trip_count=trip_count,
            num_consts=num_consts,
            num_carry=num_carry,
            out_order=out_order,
            out_shapes=[tuple(int(s) for s in ov.aval.shape)
                        for ov in eqn.outvars],
            out_dtypes=[np.dtype(ov.aval.dtype) for ov in eqn.outvars],
            reverse=reverse,
            kind=kind,
        )
        for j, ov in enumerate(eqn.outvars):
            if not _is_dropvar(ov) and ov in self.live:
                env[ov] = self.b.get(call, j)

    def _lower_scan(self, env: Dict, eqn) -> None:
        """``lax.scan`` -> ``call`` loop.  Carries double-buffer through the
        body plan; per-iteration outputs stack into ``(length, ...)``
        buffers.  ``fori_loop`` over static Python-int bounds arrives here
        too (jax lowers it to scan)."""
        p = eqn.params
        self._emit_loop(
            env, eqn, p["jaxpr"],
            [self.read(env, v) for v in eqn.invars],
            num_consts=int(p["num_consts"]),
            num_carry=int(p["num_carry"]),
            trip_count=int(p["length"]),
            reverse=bool(p["reverse"]),
            kind="scan",
        )

    def _lower_while(self, env: Dict, eqn) -> None:
        """``lax.while_loop`` lowers only when a static trip count is
        provable from the canonical counter pattern jax emits for bounded
        loops: cond = single ``lt(carry[i], LIMIT)`` eqn, body sets
        ``carry[i] + 1``, and both the init and LIMIT are literals (LIMIT
        may also be a cond constant fed by an outer literal)."""
        trip, i = self._while_trip_count(eqn) or (None, None)
        if trip is None:
            raise UnsupportedPrimitiveError(
                "while", eqn,
                "no static trip count: lax.while_loop compiles only when "
                "the condition is the canonical bounded-counter pattern "
                "`carry[i] < LIMIT` with `carry[i] += 1` in the body and "
                "literal init/limit; use lax.scan or lax.fori_loop with "
                "static bounds",
            )
        p = eqn.params
        cn = int(p["cond_nconsts"])
        bn = int(p["body_nconsts"])
        # drop the cond consts: the compiled loop replays the body only
        self._emit_loop(
            env, eqn, p["body_jaxpr"],
            [self.read(env, v) for v in eqn.invars[cn:]],
            num_consts=bn,
            num_carry=len(eqn.outvars),
            trip_count=trip,
            reverse=False,
            kind="while",
        )

    def _while_trip_count(self, eqn) -> Optional[Tuple[int, int]]:
        """``(trip_count, counter_index)`` if the while is a provably
        bounded counter loop, else None."""
        p = eqn.params
        cond = p["cond_jaxpr"].jaxpr
        body = p["body_jaxpr"].jaxpr
        cn, bn = int(p["cond_nconsts"]), int(p["body_nconsts"])
        if len(cond.eqns) != 1 or cond.eqns[0].primitive.name != "lt":
            return None
        lt = cond.eqns[0]
        if not cond.outvars or cond.outvars[0] is not lt.outvars[0]:
            return None
        ctr_atom, limit_atom = lt.invars
        cond_carries = list(cond.invars[cn:])
        if isinstance(ctr_atom, Literal) or ctr_atom not in cond_carries:
            return None
        i = cond_carries.index(ctr_atom)
        if not np.issubdtype(np.dtype(ctr_atom.aval.dtype), np.integer):
            return None
        # LIMIT: a literal, or a cond const whose outer operand is a literal
        if isinstance(limit_atom, Literal):
            limit = int(np.asarray(limit_atom.val).item())
        elif limit_atom in list(cond.invars[:cn]):
            outer = eqn.invars[list(cond.invars[:cn]).index(limit_atom)]
            if not isinstance(outer, Literal):
                return None
            limit = int(np.asarray(outer.val).item())
        else:
            return None
        # body must step the counter by exactly one
        out_i = body.outvars[i]
        if isinstance(out_i, Literal) or _is_dropvar(out_i):
            return None
        step = next(
            (e for e in body.eqns if any(v is out_i for v in e.outvars)), None
        )
        if step is None or step.primitive.name != "add":
            return None

        def _is_one(atom):
            return (
                isinstance(atom, Literal)
                and np.asarray(atom.val).ndim == 0
                and np.asarray(atom.val).item() == 1
            )

        ctr_body = body.invars[bn + i]
        x, y = step.invars
        if not ((x is ctr_body and _is_one(y)) or (y is ctr_body and _is_one(x))):
            return None
        init_atom = eqn.invars[cn + bn + i]
        if not isinstance(init_atom, Literal):
            return None
        init = int(np.asarray(init_atom.val).item())
        return max(0, limit - init), i

    def _lower_cond(self, env: Dict, eqn) -> None:
        """2-branch ``lax.cond`` inlines both branches and selects per
        output (the same thing ``vmap``-of-cond does in jax); branch
        payloads are elementwise towers, so the selects fuse into the
        surrounding kernels instead of forcing a host-side branch."""
        branches = eqn.params["branches"]
        if len(branches) != 2:
            raise UnsupportedPrimitiveError(
                "cond", eqn,
                f"{len(branches)}-way lax.switch "
                "(only 2-branch lax.cond inlines via select)",
            )
        idx = self.read(env, eqn.invars[0])
        args = [self.read(env, v) for v in eqn.invars[1:]]
        wanted = [
            j for j, ov in enumerate(eqn.outvars)
            if not _is_dropvar(ov) and ov in self.live
        ]
        branch_outs: List[Dict[int, Tensor]] = []
        for bi, br in enumerate(branches):
            inner = br.jaxpr
            live_outs = [inner.outvars[j] for j in wanted]
            kept, live = _live_eqns(inner.eqns, live_outs)
            sub_env: Dict = {}
            for var, const in zip(inner.constvars, br.consts, strict=False):
                if var in live:
                    sub_env[var] = self.b.constant(np.asarray(const))
            for var, t in zip(inner.invars, args, strict=False):
                sub_env[var] = t
            saved, self.live = self.live, live
            try:
                self.lower_eqns(sub_env, kept)
            finally:
                self.live = saved
            branch_outs.append(
                {j: self.read(sub_env, inner.outvars[j]) for j in wanted}
            )
        pred = self.b.binary(
            "ne", idx, self.b.constant(np.asarray(0, dtype=idx.dtype))
        )
        for j in wanted:
            ov = eqn.outvars[j]
            shape = tuple(int(s) for s in ov.aval.shape)
            dtype = np.dtype(ov.aval.dtype)
            # branches[0] is the FALSE branch (lax.cond index semantics)
            on_false, on_true = branch_outs[0][j], branch_outs[1][j]
            for bi, t in ((0, on_false), (1, on_true)):
                if tuple(t.shape) != shape or np.dtype(t.dtype) != dtype:
                    raise UnsupportedPrimitiveError(
                        "cond", eqn,
                        f"branch {bi} output {j} lowered to "
                        f"{np.dtype(t.dtype)}{list(t.shape)} but the cond "
                        f"declares {dtype}{list(shape)}",
                    )
            env[ov] = self.b.select(
                self.to_shape(pred, shape),
                self.to_shape(on_true, shape),
                self.to_shape(on_false, shape),
            )


def lower_jaxpr(
    closed_jaxpr,
    *,
    name: str = "stitched",
    fuse_dot: bool = True,
    param_names: Optional[Sequence[str]] = None,
) -> LoweredJaxpr:
    """Lower a ``ClosedJaxpr`` into a StitchIR ``Module``.

    ``param_names`` (optional) names the module parameters, one per jaxpr
    invar; defaults to ``arg0..argN``.  ``fuse_dot`` sets the per-dot
    ``fusable`` attr (the paper's user decision — ``StitchOptions.fuse_dot``
    flows through here from ``repro.stitch``).
    """
    jaxpr = closed_jaxpr.jaxpr
    b = GraphBuilder(name)
    lw = _Lowerer(b, fuse_dot)
    kept_eqns, live = _live_eqns(jaxpr.eqns, jaxpr.outvars)
    lw.live = live
    env: Dict = {}
    for var, const in zip(jaxpr.constvars, closed_jaxpr.consts, strict=False):
        if var in live:
            env[var] = b.constant(np.asarray(const))
    if param_names is None:
        param_names = [f"arg{i}" for i in range(len(jaxpr.invars))]
    if len(param_names) != len(jaxpr.invars):
        raise ValueError(
            f"{len(param_names)} param names for {len(jaxpr.invars)} jaxpr invars"
        )
    # every invar stays a parameter (the feed contract covers unused args)
    for pname, var in zip(param_names, jaxpr.invars, strict=False):
        env[var] = b.parameter(
            pname, tuple(var.aval.shape), np.dtype(var.aval.dtype)
        )
    lw.lower_eqns(env, kept_eqns)
    output_names = _finish_outputs(b, lw, env, jaxpr.outvars)
    return LoweredJaxpr(b.module, list(param_names), output_names)


def _finish_outputs(b: GraphBuilder, lw: _Lowerer, env: Dict, outvars) -> List[str]:
    """Shared lowering tail: root sinks for the outputs + orphan sweep.

    Outputs must be module roots (the executor returns sink values).  An
    output that aliases a parameter/constant, an interior value with other
    users, or a repeated output gets a value-preserving reshape sink.

    The sweep removes instructions orphaned by peepholes (the commuted-dot
    rewrite leaves the original dot user-less when nothing else reads it) —
    a user-less non-output would otherwise become a phantom module root the
    executor computes and returns on every call.  Parameters stay: the feed
    contract covers unused arguments.
    """
    out_tensors = [lw.read(env, ov) for ov in outvars]
    dup = Counter(t.instr.id for t in out_tensors)
    output_names: List[str] = []
    for t in out_tensors:
        instr = t.instr
        if (
            instr.users
            or dup[instr.id] > 1
            or instr.opcode in ("parameter", "constant")
        ):
            t = b.reshape(t, instr.shape)
            instr = t.instr
        output_names.append(instr.name)

    out_names = set(output_names)
    changed = True
    while changed:
        changed = False
        for instr in list(b.module.instructions):
            if (
                not instr.users
                and instr.opcode != "parameter"
                and instr.name not in out_names
            ):
                b.module.instructions.remove(instr)
                for op in instr.operands:
                    op.users.remove(instr)
                changed = True
    b.module.verify()
    return output_names


@dataclass
class LoweredShardedJaxpr(LoweredJaxpr):
    """A shard_map-captured function: the PER-SHARD module plus the mesh
    placement the one multi-device ExecutionPlan replays under.

    ``param_layouts`` maps parameter names to ``core.shard`` layout tuples
    (from the shard_map ``in_specs``); ``out_layouts`` is one layout per
    module root, in ``module.roots`` order — exactly what
    ``compile_module(..., mesh=, param_layouts=, out_layouts=)`` takes.
    """

    mesh: object = None
    mesh_axes: Tuple = ()
    param_layouts: Dict[str, Tuple] = None
    out_layouts: List = None


def lower_sharded_jaxpr(
    closed_jaxpr,
    *,
    name: str = "stitched",
    fuse_dot: bool = True,
    param_names: Optional[Sequence[str]] = None,
) -> LoweredShardedJaxpr:
    """Lower a jaxpr whose whole body is ONE ``shard_map`` eqn.

    The caller traces ``shard_map(fn, mesh, in_specs, out_specs)`` at
    GLOBAL shapes (``frontend.api`` does this when ``stitch`` is given a
    mesh); jax leaves a single shard_map eqn whose inner jaxpr is the
    per-shard computation — local shapes, collectives as psum-family eqns.
    That inner jaxpr is what lowers to StitchIR: fusion and the latency
    model then score per-shard tiles with no further changes, and the
    shard_map placement (mesh + in/out names) rides along for the
    ShardingPass and the executor's replay.

    Closure constants are hoisted by jax to the OUTER jaxpr and enter the
    shard_map as extra replicated operands — those materialize as IR
    constants.  A constant operand that shard_map expects SHARDED has no
    global value to slice here and raises ``UnsupportedPrimitiveError``.
    """
    from ..core.shard import mesh_axes_of, pspec_to_layout

    jaxpr = closed_jaxpr.jaxpr
    sm = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    if len(sm) != 1 or len(jaxpr.eqns) != 1:
        raise UnsupportedPrimitiveError(
            "shard_map", None,
            "sharded capture expects the traced function to be exactly one "
            "shard_map call wrapping the whole computation",
        )
    eqn = sm[0]
    mesh = eqn.params["mesh"]
    inner = eqn.params["jaxpr"]          # raw per-shard Jaxpr (no constvars)
    in_specs = eqn.params["in_specs"]
    out_specs = eqn.params["out_specs"]

    outer_args = {v: i for i, v in enumerate(jaxpr.invars)}
    consts = dict(zip(jaxpr.constvars, closed_jaxpr.consts, strict=False))
    if param_names is None:
        param_names = [f"arg{i}" for i in range(len(jaxpr.invars))]
    if len(param_names) != len(jaxpr.invars):
        raise ValueError(
            f"{len(param_names)} param names for {len(jaxpr.invars)} jaxpr invars"
        )

    b = GraphBuilder(name)
    lw = _Lowerer(b, fuse_dot)
    kept_eqns, live = _live_eqns(inner.eqns, inner.outvars)
    lw.live = live
    env: Dict = {}
    used_names: List[str] = []
    param_layouts: Dict[str, Tuple] = {}
    # Parameters first, in outer-arg order, so the executor's positional
    # contract matches the user's flattened arguments; constant operands
    # (hoisted closures) fold afterwards.
    binds = sorted(
        range(len(eqn.invars)),
        key=lambda k: (
            outer_args.get(eqn.invars[k], len(outer_args)) if not isinstance(
                eqn.invars[k], Literal) else len(outer_args),
            k,
        ),
    )
    for k in binds:
        atom = eqn.invars[k]
        ivar = inner.invars[k]
        rank = len(ivar.aval.shape)
        layout = pspec_to_layout(in_specs[k], rank)
        if not isinstance(atom, Literal) and atom in outer_args:
            pname = param_names[outer_args[atom]]
            env[ivar] = b.parameter(
                pname, tuple(ivar.aval.shape), np.dtype(ivar.aval.dtype)
            )
            used_names.append(pname)
            param_layouts[pname] = layout
            continue
        if any(e for e in layout):
            raise UnsupportedPrimitiveError(
                "shard_map", eqn,
                "a closure constant enters the shard_map sharded; only "
                "replicated closure constants are supported — pass sharded "
                "values as function arguments",
            )
        val = atom.val if isinstance(atom, Literal) else consts[atom]
        env[ivar] = b.constant(np.asarray(val))
    lw.lower_eqns(env, kept_eqns)
    output_names = _finish_outputs(b, lw, env, inner.outvars)

    out_layout_by_name = {
        oname: pspec_to_layout(spec, len(ov.aval.shape))
        for oname, ov, spec in zip(output_names, inner.outvars, out_specs, strict=False)
    }
    out_layouts = [
        out_layout_by_name.get(r.name) for r in b.module.roots
    ]
    return LoweredShardedJaxpr(
        b.module,
        used_names,
        output_names,
        mesh=mesh,
        mesh_axes=mesh_axes_of(mesh),
        param_layouts=param_layouts,
        out_layouts=out_layouts,
    )
