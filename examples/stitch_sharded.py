"""Shard-aware compilation: one multi-device ExecutionPlan under shard_map.

A Megatron-style tensor-parallel MLP (column-parallel W1, row-parallel W2,
one ``lax.psum`` merging the partial block outputs) compiled through
``stitch(mesh=...)`` on an 8-device host-platform mesh:

  * the per-shard computation lowers to StitchIR with the psum as an
    ``all_reduce`` collective instruction — a deliberate schedule break the
    planner stitches compute around, never into a kernel;
  * the ShardingPass propagates layouts from the ``in_specs`` and salts
    every fusion signature, so per-shard kernels can never alias the
    full-shape kernels of the same function in the kernel cache;
  * the whole ExecutionPlan replays under ONE ``jax.jit(shard_map(...))`` —
    bit-identical to jitting the shard_map directly, with the same
    per-device kernel count as the single-device plan.

    PYTHONPATH=src python examples/stitch_sharded.py

It stays on the CPU on purpose: the mesh needs 8 devices, which the host
platform provides on any machine, and a one-chip TPU host cannot.  The same
path on four real chips is ``python chip_smoke.py --chips 4``.
"""
import os

# jax locks the device count on first init: set the flag before importing it
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

# the 8 host devices above, even where a TPU is attached (see docstring)
jax.config.update("jax_platform_name", "cpu")

from repro import StitchOptions, stitch  # noqa: E402
from repro.core.shard import wrap_shard_map  # noqa: E402

NUM_LAYERS = 4
B, D, F = 16, 64, 128


def mlp_stack(x, gains, w1s, w2s):
    """Pre-norm MLP blocks, written for ONE shard: each device holds a
    column slice of W1 and a row slice of W2, and the psum merges the
    per-device partial outputs back into the replicated residual stream."""
    for g, W1, W2 in zip(gains, w1s, w2s, strict=False):
        ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
        normed = x * jax.lax.rsqrt(ms + 1e-6) * g[None, :]
        y = jnp.matmul(jax.nn.silu(jnp.matmul(normed, W1)), W2)
        x = x + jax.lax.psum(y, "model")
    return x


def main():
    devices = jax.devices()
    assert len(devices) >= 8, "the XLA_FLAGS line above must run before jax init"
    mesh = Mesh(np.array(devices[:8]).reshape(8), ("model",))
    in_specs = (
        P(),                                 # x: replicated
        [P()] * NUM_LAYERS,                  # norm gains: replicated
        [P(None, "model")] * NUM_LAYERS,     # W1: column-parallel
        [P("model", None)] * NUM_LAYERS,     # W2: row-parallel
    )
    out_specs = P()

    sharded = stitch(
        mlp_stack,
        options=StitchOptions(max_blocks=64, fuse_dot=False),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )

    rng = np.random.RandomState(0)
    x = rng.randn(B, D).astype("f4")
    gains = [rng.randn(D).astype("f4") for _ in range(NUM_LAYERS)]
    w1s = [rng.randn(D, F).astype("f4") * 0.1 for _ in range(NUM_LAYERS)]
    w2s = [rng.randn(F, D).astype("f4") * 0.1 for _ in range(NUM_LAYERS)]

    out = sharded(x, gains, w1s, w2s)       # callers pass GLOBAL arrays

    oracle = jax.jit(wrap_shard_map(mlp_stack, mesh, in_specs, out_specs))(
        x, gains, w1s, w2s
    )
    assert bool(jnp.all(out == oracle)), "replay must be bit-identical"

    s = sharded.stats
    assert s.replay_mode == "sharded"
    assert s.collective_calls == NUM_LAYERS
    assert s.collective_breaks_spanned >= 1
    print(f"mesh            : 8x1 ({'x'.join(mesh.axis_names)}) host devices")
    print(f"kernels/device  : {s.stitched_kernels} stitched + "
          f"{s.standalone_kernels} standalone (+{s.library_calls} library)")
    print(f"collectives     : {s.collective_calls} all-reduce, "
          f"{s.collective_breaks_spanned} with stitched kernels on both "
          f"sides, {s.collective_time_s * 1e6:.1f}us modeled ICI time")
    print(f"sharded instrs  : {s.sharded_instrs} carrying a layout attr")
    print("oracle parity   : bit-identical to jax.jit(shard_map(fn)) ✓")


if __name__ == "__main__":
    main()
