"""The tensor-parallel stitch cell's files: the program's operation and byte
counts, the driver's comparison on a sound and on broken runs, how it
finds the K/V kernels in a trace, and one full-width layer compiled for a
described TPU v5e 2x2.

The cell runs here on 4 of the 8 virtual CPU devices at a small size, past
the harness's look for a chip.  The compile describes the chips with a
compile-only topology, inside a fixture, and runs nothing.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.stitch_tp_cell import (  # noqa: E402
    COLLECTIVE_OPS,
    cache_kernels,
    cache_params,
    kernel_seconds,
)

from repro import StitchOptions, stitch  # noqa: E402

CELL = "stitch-tp.granite-20b-code.decode"
SEED = 2**31 + 4099


def small():
    """The cell at a size the CPU runs in seconds; limits as committed."""
    cell = harness.load_cell(CELL)
    cell.config.update(n_embd=256, n_head=8, n_inner=1024, n_layer=2)
    cell.traffic.update(rows=8, context=256, context_min=16, context_median=64)
    return cell


def run(cell, hooks=None):
    devices = jax.devices()[:cell.chips]
    assert len(devices) == cell.chips == 4
    return harness.driver(cell).run(cell, seed=SEED, seconds=0.5, trace=False,
                                    devices=devices, t0=time.perf_counter(),
                                    hooks=hooks)


def test_gptbigcode_decode_counts_by_hand():
    cell = harness.load_cell(CELL)
    prog = harness.module(cell, "programs", cell.traffic["program"] + ".py")
    cfg = dict(cell.config, n_embd=16, n_head=4, n_inner=32, n_layer=3,
               tensor_parallel=2)
    tr = dict(cell.traffic, rows=2, context=5)
    flops, nbytes = prog.cost(cfg, tr)
    # per layer and chip: 2 rows x (q 16x8 + k, v 16x4 each + o 8x16 + fc
    # 16x16 + proj 16x16) x 2, plus scores and weighted sum of 2 heads of 4
    # over 5 positions
    per_layer = 2 * 2 * (128 + 64 + 64 + 128 + 256 + 256) + 2 * 2 * (2 * 8 * 5)
    assert flops == 3 * per_layer
    weights = 3 * (128 + 8 + 2 * (64 + 4) + 128 + 16 + 4 * 16 + 256 + 16 + 256 + 16)
    kv = 3 * 2 * 2 * 5 * 4
    io = 2 * 16 + 2 * 5 + 2 * 16 + 3 * 2 * 2 * 4
    assert nbytes == 4 * (weights + kv + io)
    assert prog.attention_bytes(cfg, tr) == 4 * kv
    # the per-chip share of the global arguments, by the specs
    ins, _ = prog.specs(cfg, tr)
    shapes = prog.arg_shapes(cfg, tr)
    local = 0
    for s, spec in zip(jax.tree.leaves(shapes),
                       jax.tree.leaves(ins, is_leaf=lambda x: isinstance(x, P)),
                       strict=True):
        split = 2 if any(e is not None for e in spec) else 1
        local += int(np.prod(s.shape)) // split
    assert local == weights + kv + 2 * 16 + 2 * 5


def test_lengths_are_lognormal_and_clipped():
    cell = harness.load_cell(CELL)
    prog = harness.module(cell, "programs", cell.traffic["program"] + ".py")
    tr = dict(cell.traffic, rows=4096)
    n = prog.lengths(cell.config, tr, SEED)
    assert n.min() >= tr["context_min"] and n.max() <= tr["context"]
    assert abs(np.median(n) - tr["context_median"]) < 0.1 * tr["context_median"]
    assert (prog.lengths(cell.config, tr, SEED) == n).all()


def _altered(f):
    """The stitched call with its first output nudged where it is made."""
    def call(*args):
        out = f(*args)
        leaves, tree = jax.tree.flatten(out)
        leaves[0] = leaves[0] + 1e-3 * jnp.max(jnp.abs(leaves[0]))
        return jax.tree.unflatten(tree, leaves)
    return call


def _bias_before_psum(cfg, tr):
    """A wrong Megatron split: each chip adds the row-parallel projections'
    biases before the all-reduce, which then sums them once per chip."""
    fn = harness.module(small(), "programs", "gptbigcode_decode.py").program(cfg, tr)
    tp = cfg["tensor_parallel"]

    def wrong(x, mask, layers):
        return fn(x, mask, [dict(lp, bo=tp * lp["bo"], bproj=tp * lp["bproj"])
                            for lp in layers])

    return wrong


def test_tp_cell_sound_and_broken():
    cell = small()
    ok = run(cell)
    assert ok["correct"] and ok["attempted"] > 0
    assert ok["checks"]["fallbacks"]["value"] == 0
    assert ok["checks"]["out_err"]["value"] <= ok["checks"]["out_err"]["limit"]
    assert not run(cell, {"wrap": _altered})["correct"]
    assert not run(cell, {"program": _bias_before_psum})["correct"]


def test_cache_kernels_are_found_by_name():
    cell = small()
    prog = harness.module(cell, "programs", "gptbigcode_decode.py")
    cfg, tr = cell.config, cell.traffic
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("model",))
    args = prog.make_args(cfg, tr, SEED, 1, mesh=mesh)[0]
    names = cache_params(args)
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    assert names == {f"arg{i}" for i, (path, _) in enumerate(leaves)
                     if getattr(path[-1], "key", None) in ("k", "v")}
    assert len(names) == 2 * cfg["n_layer"]
    ins, outs = prog.specs(cfg, tr)
    st = stitch(prog.program(cfg, tr), mesh=mesh, in_specs=ins, out_specs=outs)
    with jax.default_matmul_precision("highest"):
        st(*args)
    found = cache_kernels(st, names)
    kernels = st.lower().compile().executable.kernels.values()
    assert found and set(found) == {k.name for k in kernels
                                    if {i.name for i in k.inputs} & names}
    assert all(n.startswith("stitch_") for n in found)
    events = {"device": {
        "0": [["stitch_00000001 f32[8,2,256]", 0, 10], ["fusion f32[8,256]", 10, 30],
              ["stitch_00000002 (f32[8,2,1], f32[8,2,32])", 30, 45]],
        "1": [["stitch_00000001 f32[8,2,256]", 5, 25]]}}
    got = kernel_seconds(events, ["stitch_00000001", "stitch_00000002"], (0, 40))
    assert got == (10 + 10 + 20) / 2 * 1e-9


def test_collectives_are_found_by_opcode():
    """XLA names an all-reduce after the JAX primitive, so only the opcode
    tells a collective; an operation that merely reads one is not one (HLO
    text as a v5e trace records it)."""
    lay = "{1,0:T(8,128)S(1)}"
    coll = [
        f"%psum.56 = f32[64,6144]{lay} all-reduce(f32[64,6144]{lay} %fusion.1), "
        "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.0",
        "%all-gather-start.2 = (f32[8,128]{1,0}, f32[32,128]{1,0}) "
        "all-gather-start(f32[8,128]{1,0} %p), dimensions={0}",
    ]
    other = [
        f"%stitch_85844ece.7 = (f32[64,6144]{lay}, f32[64,6144]{lay}) "
        f"custom-call(f32[64,6144]{lay} %custom-call.12, f32[64,6144]{lay} %psum.56)",
        f"%fusion.201 = (f32[64]{{0:T(128)S(1)}}, f32[64,6144]{lay}) "
        f"fusion(f32[64,6144]{lay} %all-reduce.3), kind=kLoop",
    ]
    assert all(COLLECTIVE_OPS.search(t) for t in coll)
    assert not any(COLLECTIVE_OPS.search(t) for t in other)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """AOT compiles for described chips cannot be read back from the
    persistent cache: keep it off while this test runs."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def test_granite_tp_layer_replay_compiles_for_v5e_2x2(topo, no_cache):
    """One full-width layer of the tensor-parallel decode program through
    ``stitch(mesh=...)`` over the described 2x2: every kernel and both
    all-reduces in one sharded replay, and each kernel that reads a K/V
    cache runs over a grid of one block per row."""
    import re

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.shard import layout_to_pspec

    cell = harness.load_cell(CELL)
    prog = harness.module(cell, "programs", cell.traffic["program"] + ".py")
    cfg, tr = dict(cell.config, n_layer=1), cell.traffic
    mesh = Mesh(np.array(topo.devices), ("model",))
    ins, outs = prog.specs(cfg, tr)
    shapes = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        prog.arg_shapes(cfg, tr), ins, is_leaf=lambda x: isinstance(x, P))
    opts = StitchOptions(interpret=False, **tr["options"])
    with jax.default_matmul_precision("highest"):
        compiled = stitch(prog.program(cfg, tr), options=opts, mesh=mesh,
                          in_specs=ins, out_specs=outs).lower(*shapes).compile()
        ex = compiled.executable
        args = [jax.ShapeDtypeStruct(
            ex._global_shape(name, shape), np.dtype(dtype),
            sharding=NamedSharding(mesh, layout_to_pspec(ex.param_layouts.get(name))))
            for name, _, dtype, shape in ex.execution_plan._param_binds]
        text = ex._sharded_fn.lower(*args).compile().as_text()
    stats = compiled.stats
    assert stats.interpret is False and stats.collective_calls == 2
    assert stats.collective_bytes == 2 * tr["rows"] * cfg["n_embd"] * 4
    assert text.count("tpu_custom_call") == stats.stitched_kernels
    assert len(re.findall(r" all-reduce(?:-start)?\(", text)) == 2
    caches = cache_params(shapes)
    readers = [k for k in ex.kernels.values() if {i.name for i in k.inputs} & caches]
    assert len({k.name for k in readers}) == 2
    assert all(k.blocks == tr["rows"] for k in readers)
