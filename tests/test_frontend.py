"""jaxpr-frontend suite: repro.stitch parity, plan caching, fallback,
StitchOptions validation, duplicate-parameter rejection.

The parity contract: for each ported benchmark family, ``stitch(fn)`` must
produce outputs allclose to ``jax.jit(fn)`` AND commit the same kernel
counts as compiling the hand-built StitchIR module of the same computation.
"""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import (
    StitchOptions,
    StitchedFunction,
    UnsupportedPrimitiveError,
    compile_module,
    stitch,
)
from repro.core import GraphBuilder, Module, trace
from repro.frontend import SUPPORTED_PRIMITIVES, lower_jaxpr

from graphs import JNP_FAMILIES

OPTS = StitchOptions(max_blocks=32)


def assert_tree_close(a, b, rtol=2e-5, atol=2e-5):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=False):
        np.testing.assert_allclose(
            np.asarray(x, dtype=np.float64),
            np.asarray(y, dtype=np.float64),
            rtol=rtol, atol=atol,
        )


# --------------------------------------------------------------------------
# end-to-end: pure-jnp functions, zero GraphBuilder calls
# --------------------------------------------------------------------------


def fig3_attention(q, k, v):
    d = q.shape[-1]
    s = jnp.matmul(q, jnp.swapaxes(k, -1, -2)) * (1.0 / d ** 0.5)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s)
    return jnp.matmul(e / jnp.sum(e, axis=-1, keepdims=True), v)


def rmsnorm(x, g):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + 1e-6) * g


def gated_mlp(x, w_gate, w_up):
    return jax.nn.silu(jnp.matmul(x, w_gate)) * jnp.matmul(x, w_up)


def layer_stats(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def speech_head(x):
    lg = jnp.log(jnp.maximum(jnp.square(x), 1e-6))
    tr = jnp.transpose(lg, (0, 2, 1))
    feats = jnp.concatenate([tr, tr * 0.5 + 0.1], axis=1)
    return jnp.mean(jax.nn.sigmoid(feats) * feats, axis=2)


@pytest.mark.parametrize(
    "name,fn,arg_shapes",
    [
        ("fig3_attention", fig3_attention, [(2, 4, 16, 32)] * 3),
        ("rmsnorm", rmsnorm, [(16, 64), (64,)]),
        ("gated_mlp", gated_mlp, [(16, 64), (64, 128), (64, 128)]),
        ("layer_stats", layer_stats, [(8, 96)]),
        ("speech_head", speech_head, [(4, 20, 16)]),
    ],
)
def test_stitch_end_to_end(rng, name, fn, arg_shapes):
    args = [rng.randn(*s).astype("f4") for s in arg_shapes]
    stitched = stitch(fn, options=OPTS)
    out = stitched(*args)
    assert_tree_close(out, jax.jit(fn)(*args))
    assert stitched.num_compiles == 1
    assert stitched.stats.stitched_kernels + stitched.stats.standalone_kernels >= 1


@pytest.mark.parametrize("family", sorted(JNP_FAMILIES))
def test_parity_with_hand_built_modules(rng, family):
    """The frontend reproduces the hand-built plans: same kernel counts,
    outputs allclose to jax.jit of the same function."""
    fam = JNP_FAMILIES[family]
    hand = compile_module(fam["module"](), OPTS)
    stitched = stitch(fam["fn"], options=replace(OPTS, **fam["options"]))
    args = fam["args"](rng)
    assert_tree_close(stitched(*args), jax.jit(fam["fn"])(*args), rtol=2e-4, atol=2e-4)
    hs, fs = hand.stats, stitched.stats
    assert (fs.stitched_kernels, fs.standalone_kernels, fs.library_calls) == (
        hs.stitched_kernels, hs.standalone_kernels, hs.library_calls
    ), f"{family}: frontend plan diverged from the hand-built plan"


def test_fig3_attention_single_stitched_kernel(rng):
    """The paper's headline: attention lowers to ONE stitched kernel."""
    stitched = stitch(fig3_attention, options=OPTS)
    args = [rng.randn(2, 4, 16, 32).astype("f4") for _ in range(3)]
    stitched(*args)
    assert stitched.stats.stitched_kernels == 1
    assert stitched.stats.standalone_kernels == 0


# --------------------------------------------------------------------------
# per-shape plan caching
# --------------------------------------------------------------------------


def test_plan_cache_no_recompile_at_same_shape(rng):
    stitched = stitch(rmsnorm, options=OPTS)
    x, g = rng.randn(16, 64).astype("f4"), rng.randn(64).astype("f4")
    stitched(x, g)
    assert stitched.num_compiles == 1
    stitched(x + 1, g)                      # same signature, new values
    assert stitched.num_compiles == 1       # no recompile
    stitched(x[:8], g)                      # new shape: recompile once
    assert stitched.num_compiles == 2
    out = stitched(x[:8] * 2, g)
    assert stitched.num_compiles == 2
    assert_tree_close(out, jax.jit(rmsnorm)(x[:8] * 2, g))


def test_plan_cache_distinguishes_dtypes(rng):
    stitched = stitch(lambda x: x * 2.0 + 1.0, options=OPTS)
    x = rng.randn(8, 8)
    stitched(x.astype("f4"))
    stitched(x.astype("f4") * 3)
    assert stitched.num_compiles == 1
    stitched(np.abs(x).astype("i4"))
    assert stitched.num_compiles == 2


# --------------------------------------------------------------------------
# pytrees, kwargs, aliased outputs, closures
# --------------------------------------------------------------------------


def test_pytree_inputs_and_outputs(rng):
    def fn(params, x):
        h = jnp.tanh(jnp.matmul(x, params["w"]) + params["b"])
        return {"h": h, "norms": (jnp.sum(h * h), jnp.max(h))}

    params = {"w": rng.randn(8, 4).astype("f4"), "b": rng.randn(4).astype("f4")}
    x = rng.randn(3, 8).astype("f4")
    stitched = stitch(fn, options=OPTS)
    out = stitched(params, x)
    assert set(out) == {"h", "norms"} and isinstance(out["norms"], tuple)
    assert_tree_close(out, jax.jit(fn)(params, x))


def test_kwargs_supported(rng):
    stitched = stitch(lambda x, scale: x * scale, options=OPTS)
    x = rng.randn(4, 4).astype("f4")
    assert_tree_close(stitched(x, scale=jnp.float32(2.5)), x * 2.5)


def test_aliased_and_duplicate_outputs(rng):
    """Outputs that alias a parameter, an interior value, or repeat must
    still materialize (reshape sinks keep them as module roots)."""
    def fn(x):
        y = jnp.exp(x)
        return x, y, y * 2.0, y
    x = rng.randn(4, 4).astype("f4")
    out = stitch(fn, options=OPTS)(x)
    assert_tree_close(out, jax.jit(fn)(x))


def test_closure_constants_fold(rng):
    table = rng.randn(8, 8).astype("f4")
    def fn(x):
        return jnp.matmul(x, jnp.asarray(table) * 2.0)
    stitched = stitch(fn, options=OPTS)
    x = rng.randn(4, 8).astype("f4")
    assert_tree_close(stitched(x), jax.jit(fn)(x))
    module = stitched.lower()
    assert any(i.opcode == "constant" for i in module.instructions)
    assert len(module.parameters) == 1      # the closure array is NOT a feed


def test_dead_code_is_eliminated(rng):
    """jax.make_jaxpr does not DCE; the lowering must, or dead subgraphs
    become module roots computed on every call."""
    def fn(x):
        dead = jnp.exp(x) / jnp.sum(jnp.tanh(x))     # unused chain
        _also_dead = jnp.where(x > 0, dead, x)       # unused nested select
        return x + 1.0

    x = rng.randn(4, 4).astype("f4")
    stitched = stitch(fn, options=OPTS)
    assert_tree_close(stitched(x), jax.jit(fn)(x))
    m = stitched.lower()
    opcodes = {i.opcode for i in m.instructions}
    fns = {i.attrs.get("fn") for i in m.instructions if i.opcode == "elementwise"}
    assert "reduce" not in opcodes and "select" not in opcodes
    assert "exp" not in fns and "tanh" not in fns
    assert len(m.roots) == 1                         # only the real output


def test_dead_closure_constant_not_materialized(rng):
    big = np.ones((64, 64), "f4")

    def fn(x):
        _dead = jnp.matmul(x, jnp.asarray(big))      # unused
        return x * 2.0

    x = rng.randn(4, 64).astype("f4")
    stitched = stitch(fn, options=OPTS)
    assert_tree_close(stitched(x), jax.jit(fn)(x))
    m = stitched.lower()
    assert not any(
        i.opcode == "constant" and i.num_elements > 1 for i in m.instructions
    )
    assert "dot" not in {i.opcode for i in m.instructions}


def test_side_effecting_eqns_are_not_silently_dropped(rng):
    """An effectful primitive (jax.debug.print) must raise — or fall back —
    rather than being dead-code-eliminated into silent divergence."""
    def fn(x):
        jax.debug.print("x0={v}", v=x[0, 0])
        return x + 1.0

    x = rng.randn(4, 4).astype("f4")
    with pytest.raises(UnsupportedPrimitiveError):
        stitch(fn, options=OPTS)(x)
    assert_tree_close(
        stitch(fn, on_unsupported="fallback", options=OPTS)(x), x + 1.0
    )


def test_remat_checkpoint_inlines(rng):
    def fn(x):
        return jax.checkpoint(lambda y: jnp.tanh(y) * 2.0)(x) + x

    x = rng.randn(4, 4).astype("f4")
    stitched = stitch(fn, options=OPTS)
    assert_tree_close(stitched(x), jax.jit(fn)(x))
    assert stitched.num_compiles == 1


def test_stats_error_names_fallback_cause(rng):
    fb = stitch(lambda x: jnp.cumsum(x), on_unsupported="fallback", options=OPTS)
    fb(rng.randn(4, 4).astype("f4"))
    with pytest.raises(ValueError, match="fell back to plain"):
        fb.stats


def test_unused_argument_stays_a_parameter(rng):
    stitched = stitch(lambda x, unused: x * 3.0, options=OPTS)
    x, u = rng.randn(4, 4).astype("f4"), rng.randn(8).astype("f4")
    assert_tree_close(stitched(x, u), x * 3.0)
    assert [p.name for p in stitched.lower().parameters] == ["arg0", "arg1"]


# --------------------------------------------------------------------------
# lowering coverage details
# --------------------------------------------------------------------------


def test_dot_general_noncanonical_layouts(rng):
    def fn(a, b, c):
        y = jnp.einsum("bij,bkj->bik", a, b)   # contract rhs last dim
        z = jnp.matmul(y, c)                   # matvec: (B,I,K) @ (K,)
        return jnp.sum(z, axis=-1)
    a = rng.randn(2, 3, 5).astype("f4")
    b = rng.randn(2, 4, 5).astype("f4")
    c = rng.randn(4).astype("f4")
    assert_tree_close(stitch(fn, options=OPTS)(a, b, c), jax.jit(fn)(a, b, c))


def test_integer_pow_and_reciprocal(rng):
    def fn(x):
        return x ** 3 + (x + 2.0) ** -2
    x = np.abs(rng.randn(4, 4)).astype("f4") + 0.5
    assert_tree_close(stitch(fn, options=OPTS)(x), jax.jit(fn)(x))


def test_select_convert_and_compare(rng):
    def fn(x):
        mask = x > 0
        return jnp.where(mask, x, -x) + mask.astype(jnp.float32)
    x = rng.randn(8, 8).astype("f4")
    assert_tree_close(stitch(fn, options=OPTS)(x), jax.jit(fn)(x))


def test_stop_gradient_and_int_inputs(rng):
    def fn(x, n):
        return jax.lax.stop_gradient(x) * n.astype(jnp.float32)
    x = rng.randn(4, 4).astype("f4")
    n = rng.randint(0, 5, size=(4, 4)).astype("i4")
    assert_tree_close(stitch(fn, options=OPTS)(x, n), jax.jit(fn)(x, n))


def test_lower_returns_lowered_handle(rng):
    from repro import Lowered

    stitched = stitch(rmsnorm, options=OPTS)
    m = stitched.lower(
        jax.ShapeDtypeStruct((16, 64), jnp.float32),
        jax.ShapeDtypeStruct((64,), jnp.float32),
    )
    assert isinstance(m, Lowered)
    assert isinstance(m.module, Module)
    assert [p.shape for p in m.parameters] == [(16, 64), (64,)]
    assert stitched.num_compiles == 0       # lowering never compiles
    with pytest.raises(ValueError, match="has not been compiled"):
        stitched.stats
    stitched(np.ones((16, 64), "f4"), np.ones(64, "f4"))
    assert isinstance(stitched.lower(), Lowered)
    assert "rmsnorm" in stitched.report()


def test_decorator_forms(rng):
    @stitch
    def f1(x):
        return x * 2.0

    @stitch(options=StitchOptions(planner="greedy", max_blocks=32))
    def f2(x):
        return x + 1.0

    x = rng.randn(4, 4).astype("f4")
    assert isinstance(f1, StitchedFunction) and isinstance(f2, StitchedFunction)
    assert f2.options.planner == "greedy"
    assert_tree_close(f1(x), x * 2.0)
    assert_tree_close(f2(x), x + 1.0)


# --------------------------------------------------------------------------
# unsupported primitives + fallback
# --------------------------------------------------------------------------


def test_unsupported_primitive_error_names_the_eqn(rng):
    stitched = stitch(lambda x: jnp.cumsum(x) * 2.0, options=OPTS)
    with pytest.raises(UnsupportedPrimitiveError) as ei:
        stitched(rng.randn(4, 4).astype("f4"))
    err = ei.value
    assert err.primitive == "cumsum"
    assert err.eqn is not None and "cumsum" in str(err.eqn)
    assert "fallback" in str(err)           # points at the escape hatch
    assert "cumsum" not in SUPPORTED_PRIMITIVES


def test_fallback_mode_runs_via_jax_jit(rng):
    fn = lambda x: jnp.cumsum(x) + 1.0  # noqa: E731
    stitched = stitch(fn, on_unsupported="fallback", options=OPTS)
    x = rng.randn(4, 4).astype("f4")
    assert_tree_close(stitched(x), jax.jit(fn)(x))
    assert stitched.num_fallbacks == 1 and stitched.num_compiles == 0
    stitched(x)                             # fallback entry is cached too
    assert stitched.num_fallbacks == 1


def test_fallback_mode_still_stitches_supported_fns(rng):
    stitched = stitch(rmsnorm, on_unsupported="fallback", options=OPTS)
    x, g = rng.randn(16, 64).astype("f4"), rng.randn(64).astype("f4")
    assert_tree_close(stitched(x, g), jax.jit(rmsnorm)(x, g))
    assert stitched.num_compiles == 1 and stitched.num_fallbacks == 0


def test_invalid_on_unsupported_mode():
    with pytest.raises(ValueError, match="on_unsupported"):
        stitch(lambda x: x, on_unsupported="ignore")


def test_stitch_requires_callable():
    with pytest.raises(TypeError, match="callable"):
        stitch(42)


# --------------------------------------------------------------------------
# satellite: StitchOptions validation
# --------------------------------------------------------------------------


def test_options_rejects_unknown_planner():
    with pytest.raises(ValueError, match=r"cost.*greedy|greedy.*cost"):
        StitchOptions(planner="gredy")


def test_options_rejects_negative_budgets():
    with pytest.raises(ValueError, match="vmem_limit"):
        StitchOptions(vmem_limit=-1)
    with pytest.raises(ValueError, match="stitch_max_blocks"):
        StitchOptions(stitch_max_blocks=-4)
    with pytest.raises(ValueError, match="stitch_replicate_limit"):
        StitchOptions(stitch_replicate_limit=-2)


def test_options_validate_on_dataclasses_replace():
    opts = StitchOptions()
    with pytest.raises(ValueError, match="planner"):
        replace(opts, planner="bogus")
    assert replace(opts, planner="greedy").planner == "greedy"
    opts.validate()                         # explicit re-validation is public


# --------------------------------------------------------------------------
# satellite: duplicate parameter names
# --------------------------------------------------------------------------


def test_graphbuilder_rejects_duplicate_parameter_names():
    b = GraphBuilder("dup")
    b.parameter("x", (4,), jnp.float32)
    with pytest.raises(ValueError, match="duplicate parameter name 'x'"):
        b.parameter("x", (8,), jnp.float32)


def test_trace_rejects_duplicate_spec_names():
    def fn(b, x, y):
        return x + y
    with pytest.raises(ValueError, match="duplicate parameter name"):
        trace(fn, ("x", (4,), jnp.float32), ("x", (4,), jnp.float32))


# --------------------------------------------------------------------------
# lower_jaxpr is usable standalone (the documented low-level path)
# --------------------------------------------------------------------------


def test_lower_jaxpr_standalone(rng):
    closed = jax.make_jaxpr(rmsnorm)(
        jax.ShapeDtypeStruct((8, 32), jnp.float32),
        jax.ShapeDtypeStruct((32,), jnp.float32),
    )
    lowered = lower_jaxpr(closed, name="rms", param_names=["x", "g"])
    assert [p.name for p in lowered.module.parameters] == ["x", "g"]
    from repro.core import reference_execute

    x, g = rng.randn(8, 32).astype("f4"), rng.randn(32).astype("f4")
    out = reference_execute(lowered.module, {"x": x, "g": g})
    assert_tree_close(
        [out[n] for n in lowered.output_names], [rmsnorm(x, g)]
    )
