"""The cell's control fails its comparison: the plain reference one step
of precision below the configuration's, put in the program's place.

The stitch decode control (every contraction in three bfloat16 passes) is
computed at the cell's widths and depth with a quarter of its rows.  It
rounds with ``lax.reduce_precision``, so it computes here what it computes
on the chip.
"""
import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.precision import dot_bf16x3, einsum_bf16x3  # noqa: E402
from bench.stitch_cell import max_rel_err  # noqa: E402


def test_stitch_decode_control_fails():
    """At the cell's widths and depth, with 8 of its 32 rows."""
    cell = harness.load_cell("stitch.qwen1.5-0.5b.decode")
    prog = harness.module(cell, "programs", cell.traffic["program"] + ".py")
    cfg, tr = cell.config, dict(cell.traffic, rows=8)
    with jax.default_matmul_precision("highest"):
        args = prog.make_args(cfg, tr, 2**31 + 5, 1)[0]
        ref = jax.jit(prog.reference(cfg, tr))(*args)
        ctl = jax.jit(prog.reference(cfg, tr, dot=dot_bf16x3,
                                     einsum=einsum_bf16x3))(*args)
    assert max_rel_err(ctl, ref) > 2 * tr["limits"]["out_err"]
