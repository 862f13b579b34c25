"""The comparison that decides ``correct`` passes a sound run and fails a
broken one.

The stitch decode cell runs here on the CPU at a small size, past the
harness's look for a chip: sound, then with its timed path broken
underneath.  The fault that a stateless one-chip call can have is an
answer altered where it is produced.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


SEED = 2**31 + 977


def small():
    """The cell at a size the CPU runs in seconds; limits as committed."""
    cell = harness.load_cell("stitch.qwen1.5-0.5b.decode")
    cell.config.update(hidden_size=128, num_attention_heads=2,
                       intermediate_size=256, num_hidden_layers=2)
    cell.traffic.update(rows=8, context=128, context_min=16)
    return cell


def run(cell, hooks=None, seconds=0.5):
    devices = jax.devices()[:cell.chips]
    assert len(devices) == cell.chips
    return harness.driver(cell).run(cell, seed=SEED, seconds=seconds, trace=False,
                                    devices=devices, t0=time.perf_counter(),
                                    hooks=hooks)


def _altered(f):
    """The stitched call with its first output nudged where it is made."""
    def call(*args):
        out = f(*args)
        leaves, tree = jax.tree.flatten(out)
        leaves[0] = leaves[0] + 1e-3 * jnp.max(jnp.abs(leaves[0]))
        return jax.tree.unflatten(tree, leaves)
    return call


def test_stitch_cell_sound_and_altered():
    cell = small()
    ok = run(cell)
    assert ok["correct"] and ok["attempted"] > 0
    assert ok["checks"]["out_err"]["value"] <= ok["checks"]["out_err"]["limit"]
    bad = run(cell, {"wrap": _altered})
    assert not bad["correct"]
