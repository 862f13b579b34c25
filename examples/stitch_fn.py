"""repro.stitch on real jax.numpy functions — the frontend tour.

Four pure-jnp functions (attention, RMSNorm, a gated MLP, a masked softmax)
compiled end-to-end through the stitching pipeline, each validated against
``jax.jit`` of the same function; plus the three things the frontend
guarantees:

  * parity — the captured plan reproduces the hand-built StitchIR plan
    (same kernel counts on the ported NMT benchmark graph);
  * per-shape plan caching — a second same-shape call performs no
    recompile, a new shape recompiles at most once;
  * graceful partial coverage — unsupported primitives raise a named
    ``UnsupportedPrimitiveError``, or fall back to plain ``jax.jit`` with
    ``on_unsupported="fallback"``.

    PYTHONPATH=src python examples/stitch_fn.py
"""
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

from repro import (
    StitchOptions,
    UnsupportedPrimitiveError,
    compile_module,
    stitch,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
from graphs import JNP_FAMILIES, nmt_args

OPTS = StitchOptions(max_blocks=64)


# -- four pure-jnp workloads ------------------------------------------------

def attention(q, k, v):
    d = q.shape[-1]
    s = jnp.matmul(q, jnp.swapaxes(k, -1, -2)) * (1.0 / d ** 0.5)
    return jnp.matmul(jax.nn.softmax(s, axis=-1), v)


def rmsnorm(x, g):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + 1e-6) * g


def gated_mlp(x, w_gate, w_up):
    return jax.nn.silu(jnp.matmul(x, w_gate)) * jnp.matmul(x, w_up)


def masked_softmax(x, mask):
    z = jnp.where(mask, x, -1e9)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def check(name, fn, *args):
    stitched = stitch(fn, options=OPTS)
    out = stitched(*args)
    ref = jax.jit(fn)(*args)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    s = stitched.stats
    print(f"{name:16s}: {s.stitched_kernels} stitched + "
          f"{s.standalone_kernels} standalone kernels "
          f"(+{s.library_calls} library), XLA baseline "
          f"{s.xla_baseline_kernels} — matches jax.jit ✓")
    return stitched


def main():
    rng = np.random.RandomState(0)
    B, H, S, D = 2, 4, 16, 32
    q, k, v = (rng.randn(B, H, S, D).astype("f4") for _ in range(3))
    x = rng.randn(16, 64).astype("f4")
    g = rng.randn(64).astype("f4")
    w1, w2 = (rng.randn(64, 128).astype("f4") for _ in range(2))
    mask = rng.rand(16, 64) > 0.3

    check("attention", attention, q, k, v)
    check("rmsnorm", rmsnorm, x, g)
    check("gated_mlp", gated_mlp, x, w1, w2)
    sm = check("masked_softmax", masked_softmax, x, mask)

    # -- per-shape plan caching --------------------------------------------
    n0 = sm.num_compiles
    sm(x, mask)                                    # same shapes: cache hit
    assert sm.num_compiles == n0
    sm(x[:8], mask[:8])                            # new shape: one recompile
    assert sm.num_compiles == n0 + 1
    sm(x[:8], mask[:8])
    assert sm.num_compiles == n0 + 1
    print(f"plan cache      : {sm.num_compiles} compiles across "
          f"{len(sm._plans)} shape signatures ✓")

    # -- parity with the hand-built StitchIR path --------------------------
    fam = JNP_FAMILIES["NMT"]
    hand = compile_module(fam["module"](), OPTS)
    front = stitch(fam["fn"], options=OPTS)
    front(*nmt_args(rng))
    hk = hand.stats.stitched_kernels + hand.stats.standalone_kernels
    fk = front.stats.stitched_kernels + front.stats.standalone_kernels
    assert hk == fk, f"frontend {fk} kernels vs hand-built {hk}"
    print(f"NMT parity      : frontend plan == hand-built plan "
          f"({fk} kernel{'s' if fk != 1 else ''}) ✓")

    # -- unsupported primitives --------------------------------------------
    try:
        stitch(lambda t: jnp.cumsum(t, axis=-1))(x)
        raise AssertionError("expected UnsupportedPrimitiveError")
    except UnsupportedPrimitiveError as e:
        print(f"unsupported     : named error for '{e.primitive}' ✓")
    fb = stitch(
        lambda t: jnp.cumsum(t, axis=-1) + 1.0, on_unsupported="fallback"
    )
    np.testing.assert_allclose(
        np.asarray(fb(x)), np.cumsum(x, axis=-1) + 1.0, rtol=1e-5, atol=1e-5
    )
    print(f"fallback        : {fb.num_fallbacks} signature(s) via plain "
          f"jax.jit ✓")

    print()
    print(sm.report())


if __name__ == "__main__":
    main()
