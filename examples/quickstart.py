"""Quickstart: compile the paper's Figure-3 pattern straight from jax.numpy.

``repro.stitch`` is a ``jax.jit``-shaped entry point: it captures a real
JAX function via jaxpr, lowers it into StitchIR, runs the full pipeline
(Work/Span deep fusion → schedule tuning → VMEM planning → stitched Pallas
codegen), and caches the compiled plan per input-shape signature.  No
hand-built IR anywhere.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro import StitchOptions, stitch


@stitch(options=StitchOptions(max_blocks=32))
def attention(q, k, v):
    """The motivating example: BatchMatMul stitched with softmax."""
    d = q.shape[-1]
    scores = jnp.matmul(q, jnp.swapaxes(k, -1, -2)) * (1.0 / d ** 0.5)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)  # Figure 3:
    p = jnp.exp(scores)                                        # max, sub, exp,
    p = p / jnp.sum(p, axis=-1, keepdims=True)                 # sum, div
    return jnp.matmul(p, v)                                    # Dot.1


def main():
    B, H, S, D = 2, 4, 16, 32
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype("f4")) for _ in range(3))

    out = attention(q, k, v)              # traced + lowered + compiled + run
    s = attention.stats
    module = attention.lower()
    print(f"captured StitchIR : {len(module.instructions)} instructions "
          f"from the jaxpr of attention()")
    print(f"stitched kernels  : {s.stitched_kernels}")
    print(f"standalone        : {s.standalone_kernels}")
    print(f"XLA baseline      : {s.xla_baseline_kernels} kernels")
    print(f"fusion ratio      : {s.fusion_ratio:.3f}  "
          f"({(1 - s.fusion_ratio) * 100:.0f}% fewer launches)")
    for r in s.reports:
        print(f"  kernel {r.name}: {r.num_ops} ops, {r.blocks} blocks, "
              f"{r.scratch_bytes}B VMEM scratch "
              f"({r.shared_bytes}B shared), roots={r.roots}")

    # bit-validate against plain jax.jit of the SAME function
    ref = jax.jit(attention.__wrapped__)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    print("stitched kernels match jax.jit of the same function ✓")

    # per-shape plan caching: a second same-shape call performs no recompile
    before = attention.num_compiles
    attention(q, k, v)
    assert attention.num_compiles == before, "same-shape call recompiled!"
    print(f"plan cache holds  : {attention.num_compiles} compile(s) "
          f"after a repeated call ✓")


if __name__ == "__main__":
    main()
