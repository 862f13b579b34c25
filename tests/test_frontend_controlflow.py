"""Control-flow and gradient capture through ``repro.stitch``.

The contract (ISSUE 8): ``lax.scan``, bounded ``fori_loop``/``while_loop``,
shape-agreeing ``lax.cond`` and ``jax.grad``/``value_and_grad`` all compile
with ZERO fallbacks and are bit-identical to ``jax.jit`` in both replay
modes (eager per-step dispatch and one traced ``lax.scan`` segment).
Plus the jit-parity API surface: static-argnum cache keying, donation
safety, and a stitched AdamW train step whose loss trajectory matches the
plain ``jax.jit`` trainer exactly.
"""
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import StitchOptions, UnsupportedPrimitiveError, stitch
from repro.train import AdamWConfig, adamw_init, make_stitched_train_step

OPTS = StitchOptions(max_blocks=32)
EAGER = replace(OPTS, jit_replay=False)

REPLAYS = pytest.mark.parametrize(
    "opts", [OPTS, EAGER], ids=["traced", "eager"]
)


def assert_tree_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb, strict=False):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def decode_loop(h, w):
    def step(carry, _):
        carry = jnp.tanh(carry @ w)
        return carry, carry.sum(axis=-1)

    return jax.lax.scan(step, h, None, length=6)


# --------------------------------------------------------------------------
# scan
# --------------------------------------------------------------------------


@REPLAYS
def test_scan_decode_loop_bitwise_vs_jit(opts):
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 16), scale=0.2), jnp.float32)

    st = stitch(decode_loop, options=opts)
    got = st(h, w)
    assert st.num_fallbacks == 0
    assert_tree_bitwise(got, jax.jit(decode_loop)(h, w))

    s = st.stats
    assert s.loop_calls == 1
    assert s.sub_compiles == 1
    assert s.sub_kernels >= 1


@REPLAYS
def test_scan_with_xs_and_reverse(opts):
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)

    def fn(init, xs):
        def step(c, x):
            c = c * 0.9 + x
            return c, c - x

        return jax.lax.scan(step, init, xs, reverse=True)

    init = jnp.ones((8,), jnp.float32)
    st = stitch(fn, options=opts)
    assert_tree_bitwise(st(init, xs), jax.jit(fn)(init, xs))
    assert st.num_fallbacks == 0


def test_two_identical_scans_share_one_compiled_body():
    def fn(a, w):
        c1, ys1 = decode_loop(a, w)
        c2, ys2 = decode_loop(a + 1.0, w)
        return c1 + c2, ys1 + ys2

    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 16), scale=0.2), jnp.float32)

    st = stitch(fn, options=OPTS)
    assert_tree_bitwise(st(a, w), jax.jit(fn)(a, w))
    s = st.stats
    assert s.loop_calls == 2
    assert s.sub_compiles == 1  # module-signature dedup: one body, two sites
    assert s.sub_call_sites == 2


def test_traced_replay_reduces_dispatches():
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 16), scale=0.2), jnp.float32)

    st = stitch(decode_loop, options=OPTS)
    st(h, w)
    s = st.stats
    assert s.replay_mode == "jit"
    assert s.traced_dispatches_per_call == 1   # the whole loop, one dispatch
    assert s.traced_dispatches_per_call < s.eager_dispatches_per_call


# --------------------------------------------------------------------------
# fori / while
# --------------------------------------------------------------------------


@REPLAYS
def test_fori_loop_static_bounds(opts):
    def fn(x):
        return jax.lax.fori_loop(0, 4, lambda i, c: c @ c * 0.5, x)

    x = jnp.eye(8, dtype=jnp.float32) * 1.5
    st = stitch(fn, options=opts)
    assert_tree_bitwise(st(x), jax.jit(fn)(x))
    assert st.num_fallbacks == 0


@REPLAYS
def test_while_loop_counted(opts):
    def fn(x):
        def cond(c):
            return c[0] < 5

        def body(c):
            i, v = c
            return i + 1, v * 1.1 + 0.25

        return jax.lax.while_loop(cond, body, (0, x))[1]

    x = jnp.linspace(0.0, 1.0, 12, dtype=jnp.float32)
    st = stitch(fn, options=opts)
    assert_tree_bitwise(st(x), jax.jit(fn)(x))
    assert st.num_fallbacks == 0


def test_data_dependent_while_raises():
    def fn(x):
        return jax.lax.while_loop(
            lambda v: jnp.sum(v) < 100.0, lambda v: v * 2.0, x
        )

    with pytest.raises(UnsupportedPrimitiveError) as err:
        stitch(fn, options=OPTS)(jnp.ones((4,), jnp.float32))
    assert err.value.primitive == "while"


# --------------------------------------------------------------------------
# cond
# --------------------------------------------------------------------------


@REPLAYS
@pytest.mark.parametrize("flag", [False, True])
def test_cond_inlines_via_select(opts, flag):
    def fn(pred, x):
        return jax.lax.cond(pred, lambda v: v * 2.0, lambda v: v - 1.0, x)

    x = jnp.arange(8, dtype=jnp.float32)
    pred = jnp.asarray(flag)
    st = stitch(fn, options=opts)
    assert_tree_bitwise(st(pred, x), jax.jit(fn)(pred, x))
    assert st.num_fallbacks == 0


def test_nway_switch_raises():
    def fn(i, x):
        return jax.lax.switch(
            i, [lambda v: v, lambda v: v * 2.0, lambda v: v * 3.0], x
        )

    with pytest.raises(UnsupportedPrimitiveError):
        stitch(fn, options=OPTS)(jnp.asarray(1), jnp.ones((4,), jnp.float32))


# --------------------------------------------------------------------------
# grad
# --------------------------------------------------------------------------


def mlp_loss(params, x, y):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - y) ** 2)


def _mlp_data(seed=0):
    rng = np.random.default_rng(seed)
    params = {
        "w1": jnp.asarray(rng.normal(size=(8, 16), scale=0.3), jnp.float32),
        "b1": jnp.zeros((16,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(16, 4), scale=0.3), jnp.float32),
        "b2": jnp.zeros((4,), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(32, 4)), jnp.float32)
    return params, x, y


@REPLAYS
def test_grad_mlp_bitwise_vs_jit(opts):
    params, x, y = _mlp_data()
    fn = jax.value_and_grad(mlp_loss)
    st = stitch(fn, options=opts)
    assert_tree_bitwise(st(params, x, y), jax.jit(fn)(params, x, y))
    assert st.num_fallbacks == 0


@REPLAYS
def test_grad_of_scan(opts):
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.normal(size=(8, 8), scale=0.2), jnp.float32)
    h = jnp.asarray(rng.normal(size=(2, 8)), jnp.float32)

    def loss(w, h):
        c, ys = decode_loop(h, w)
        return jnp.sum(c ** 2) + jnp.sum(ys)

    fn = jax.grad(loss)
    st = stitch(fn, options=opts)
    assert_tree_bitwise(st(w, h), jax.jit(fn)(w, h))
    assert st.num_fallbacks == 0
    assert st.stats.loop_calls >= 2  # forward scan + transposed reverse scan


# --------------------------------------------------------------------------
# jit-parity API: statics, donation
# --------------------------------------------------------------------------


def test_static_argnums_key_the_plan_cache():
    def fn(x, n):
        return x * float(n)

    st = stitch(fn, options=OPTS, static_argnums=(1,))
    x = jnp.ones((4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(st(x, 2)), 2 * np.ones(4))
    np.testing.assert_array_equal(np.asarray(st(x, 3)), 3 * np.ones(4))
    assert st.num_compiles == 2  # distinct static values -> distinct plans
    st(x, 2)
    assert st.num_compiles == 2  # cache hit on a seen static


def test_static_argnames_and_nonhashable_rejection():
    def fn(x, *, mode="a"):
        return x + (1.0 if mode == "a" else 2.0)

    st = stitch(fn, options=OPTS, static_argnames="mode")
    x = jnp.zeros((4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(st(x, mode="a")), np.ones(4))
    np.testing.assert_array_equal(np.asarray(st(x, mode="b")), 2 * np.ones(4))

    with pytest.raises(TypeError, match="hashable"):
        stitch(lambda x, c: x, options=OPTS, static_argnums=(1,))(x, [1, 2])


def test_donate_argnums_threads_to_plan():
    def fn(x, y):
        return x + y

    st = stitch(fn, options=OPTS, donate_argnums=(0,))
    x = jnp.ones((16,), jnp.float32)
    y = jnp.full((16,), 2.0, jnp.float32)
    out = st(x, y)
    np.testing.assert_array_equal(np.asarray(out), 3 * np.ones(16))
    assert st.num_fallbacks == 0


def test_static_donate_overlap_rejected():
    with pytest.raises(ValueError, match="intersect"):
        stitch(lambda x: x, static_argnums=(0,), donate_argnums=(0,))


# --------------------------------------------------------------------------
# stitched train step: one plan, trajectory parity with jax.jit
# --------------------------------------------------------------------------


def test_stitched_train_step_matches_jit_trajectory():
    from repro.train.optimizer import adamw_update

    opt_cfg = AdamWConfig()
    st = make_stitched_train_step(mlp_loss_batch, opt_cfg, options=OPTS)

    def ref_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(mlp_loss_batch)(params, batch)
        params, opt_state, om = adamw_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, **om}

    ref = jax.jit(ref_step)

    params, x, y = _mlp_data(seed=7)
    # independent buffers: the stitched step donates params/opt_state
    p_a = jax.tree.map(jnp.copy, params)
    p_b = jax.tree.map(jnp.copy, params)
    s_a, s_b = adamw_init(p_a), adamw_init(p_b)

    rng = np.random.default_rng(8)
    for _ in range(4):
        batch = (
            jnp.asarray(rng.normal(size=(32, 8)), jnp.float32),
            jnp.asarray(rng.normal(size=(32, 4)), jnp.float32),
        )
        p_a, s_a, m_a = st(p_a, s_a, batch)
        p_b, s_b, m_b = ref(p_b, s_b, batch)
        np.testing.assert_array_equal(
            np.asarray(m_a["loss"]), np.asarray(m_b["loss"])
        )

    assert_tree_bitwise(p_a, p_b)
    assert_tree_bitwise(tuple(s_a), tuple(s_b))
    assert st.num_fallbacks == 0
    assert st.num_compiles == 1  # the whole train step is ONE plan
    assert st.stats.stitched_kernels >= 1


def test_stitched_train_step_launches_fewer_kernels_than_unfused():
    """Forward, backward and AdamW compile as one plan that launches fewer
    kernels than the unfused XLA-style baseline of the same step."""
    st = make_stitched_train_step(mlp_loss_batch, AdamWConfig(), options=OPTS)
    params, x, y = _mlp_data(seed=7)
    st(params, adamw_init(params), (x, y))
    s = st.stats
    assert st.num_fallbacks == 0 and st.num_compiles == 1
    assert s.stitched_kernels + s.standalone_kernels == 11 < s.xla_baseline_kernels


def mlp_loss_batch(params, batch):
    x, y = batch
    return mlp_loss(params, x, y)
