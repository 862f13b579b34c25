"""IrEmitterStitched: generated Pallas kernels vs the pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np

from conftest import compile_and_compare
from repro.core import trace


def feeds_for(module, rng, lo=-2.0, hi=2.0):
    out = {}
    for p in module.parameters:
        if np.dtype(p.dtype) == np.int32:
            out[p.name] = rng.randint(0, 4, size=p.shape).astype(np.int32)
        else:
            out[p.name] = rng.uniform(lo, hi, size=p.shape).astype(
                np.dtype(p.dtype)
            )
    return out


def run(fn, specs, rng, **kw):
    m = trace(fn, *specs)
    return compile_and_compare(m, feeds_for(m, rng), **kw)


def test_softmax_stitched(rng):
    run(
        lambda b, x: b.softmax(x, dim=-1),
        [("x", (4, 8, 16), jnp.float32)],
        rng,
    )


def test_softmax_dot_fig3(rng):
    def f(b, scores, v):
        return b.dot(b.softmax(scores, dim=-1), v, fusable=True)

    run(
        f,
        [("scores", (2, 4, 8, 8), jnp.float32), ("v", (2, 4, 8, 4), jnp.float32)],
        rng,
    )


def test_rmsnorm_pattern(rng):
    def f(b, x, g):
        ms = b.reduce(b.square(x), (2,), "mean")
        inv = b.rsqrt(ms + 1e-6)
        return x * b.broadcast(inv, x.shape, (0, 1)) * b.broadcast(g, x.shape, (2,))

    run(f, [("x", (2, 8, 32), jnp.float32), ("g", (32,), jnp.float32)], rng)


def test_column_reduce(rng):
    """Column reductions are an explicit XLA pain point the paper targets."""
    def f(b, x):
        s = b.reduce(x, (0,), "sum")           # reduce the MAJOR dim
        return b.tanh(s)

    run(f, [("x", (16, 8), jnp.float32)], rng)


def test_transpose_inside_fusion(rng):
    def f(b, x):
        t = b.transpose(x, (0, 2, 1))
        return b.exp(t) + 1.0

    run(f, [("x", (4, 6, 8), jnp.float32)], rng)


def test_reshape_chain(rng):
    def f(b, x):
        y = b.reshape(x, (8, 12))
        z = b.exp(y)
        return b.reshape(z, (4, 2, 12)) * 2.0

    run(f, [("x", (4, 24), jnp.float32)], rng)


def test_concat_fusion(rng):
    def f(b, x, y):
        c = b.concat([b.exp(x), b.tanh(y)], dim=1)
        return c * 0.5

    run(f, [("x", (4, 8), jnp.float32), ("y", (4, 8), jnp.float32)], rng)


def test_multi_root_horizontal(rng):
    def f(b, w0, g0, w1, g1):
        return (w0 - g0 * 0.1, w1 - g1 * 0.1)

    run(
        f,
        [(n, (8, 8), jnp.float32) for n in ("w0", "g0", "w1", "g1")],
        rng,
    )


def test_broadcast_scalar_and_vector(rng):
    def f(b, x, s):
        return x * b.broadcast(s, x.shape, (1,)) + 3.0

    run(f, [("x", (4, 8), jnp.float32), ("s", (8,), jnp.float32)], rng)


def test_select_and_compare(rng):
    def f(b, x, y):
        return b.select(x > y, x, y) - b.minimum(x, y)

    run(f, [("x", (4, 8), jnp.float32), ("y", (4, 8), jnp.float32)], rng)


def test_iota_member(rng):
    def f(b, x):
        pos = b.iota((4, 8), dim=1, dtype=jnp.float32)
        return x + pos

    run(f, [("x", (4, 8), jnp.float32)], rng)


def test_gather_small_table(rng):
    def f(b, table, idx):
        g = b.gather(table, idx)
        return b.tanh(g)

    m = trace(f, ("table", (16, 8), jnp.float32), ("idx", (4,), jnp.int32))
    feeds = {
        "table": rng.randn(16, 8).astype("f4"),
        "idx": rng.randint(0, 16, size=(4,)).astype(np.int32),
    }
    compile_and_compare(m, feeds)


def test_library_dot_boundary(rng):
    def f(b, x, w):
        h = b.tanh(b.dot(x, w))          # LC layer between the two fusions
        return b.softmax(h, dim=-1)

    c = run(f, [("x", (4, 8), jnp.float32), ("w", (8, 8), jnp.float32)], rng)
    assert c.stats.library_calls == 1


def test_mean_reduce_and_log(rng):
    def f(b, x):
        mu = b.reduce(x, (1,), "mean")
        d = x - b.broadcast(mu, x.shape, (0,))
        return b.log(b.abs(d) + 1.0)

    run(f, [("x", (8, 16), jnp.float32)], rng)


def test_bf16_softmax(rng):
    def f(b, x):
        return b.softmax(x, dim=-1)

    m = trace(f, ("x", (4, 16), jnp.bfloat16))
    feeds = {"x": rng.randn(4, 16).astype(jnp.bfloat16)}
    compile_and_compare(m, feeds, rtol=2e-2, atol=2e-2)


def test_deep_chain_single_kernel(rng):
    def f(b, x):
        for _ in range(12):
            x = b.tanh(x * 1.01)
        return x

    c = run(f, [("x", (8, 8), jnp.float32)], rng)
    assert c.stats.stitched_kernels == 1
    assert c.stats.standalone_kernels == 0


def test_interpret_resolves_from_the_backend():
    from repro.core import StitchOptions, compile_module
    from repro.core.codegen import resolve_interpret

    assert resolve_interpret(None) is True       # the CPU has no TPU
    assert resolve_interpret(False) is False
    assert resolve_interpret(True) is True
    m = trace(lambda b, x: b.exp(x), ("x", (8, 128), jnp.float32))
    assert StitchOptions().interpret is None
    assert compile_module(m, StitchOptions()).stats.interpret is True


def test_rank0_operands_and_results_cross_the_kernel_as_blocks(rng):
    def f(b, x, s):
        scaled = b.exp(x) * b.broadcast(s, (8, 128), ())
        return b.reduce(scaled, (0, 1), "sum")

    m = trace(f, ("x", (8, 128), jnp.float32), ("s", (), jnp.float32))
    assert m.roots[0].shape == ()
    compile_and_compare(
        m, {"x": rng.randn(8, 128).astype("f4"), "s": np.float32(0.5)},
        rtol=1e-5, atol=1e-4,
    )
