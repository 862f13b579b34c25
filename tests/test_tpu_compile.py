"""Every stitched kernel of the chip smoke's programs, and of five paper
graphs, compiles for a TPU v5e.

Nothing runs: each kernel is AOT-compiled by the TPU compiler for a
described (not attached) v5e chip, which refuses what interpret mode
accepts — value ``dynamic_slice``, rank-0 blocks, blocks off the (8, 128)
tiling, too much VMEM, batched matmuls Mosaic cannot lower.  The graphs are
one regression each: ReduceTowers (rank-0 results), Speech (tiling), NMT
(batched dots), StitchPipe (a multi-phase stitched kernel) and W2V (rank-1
blocks and an in-kernel gather).  One full-width layer of the decode
benchmark's program checks that its K and V reach their kernels in the
layout the chip gives them, with no relayout copy.
"""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from graphs import ALL_GRAPHS, nmt_fn, softmax_transpose_fn, swiglu_fn
from repro import StitchOptions, stitch
from repro.core import compile_module
from repro.core.codegen import VMEM_HEADROOM
from repro.core.memory import SCOPED_VMEM_BYTES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.programs import qwen_decode  # noqa: E402

#: the chip smoke's stitch programs at its widths (f32)
SMOKE_PROGRAMS = {
    "swiglu": (swiglu_fn, ((512, 1024), [(1024,)] * 2, [(1024, 2816)] * 2,
                           [(1024, 2816)] * 2, [(2816, 1024)] * 2)),
    "attention": (nmt_fn, ((1, 16, 512, 64),) * 3 + ((512, 512),)),
    "softmax_transpose": (softmax_transpose_fn, ((512, 1024), (1024,))),
}

REGRESSION_GRAPHS = ("ReduceTowers", "Speech", "NMT", "StitchPipe", "W2V")

OPTS = StitchOptions(interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernels(compiled, sharding) -> int:
    """AOT-compile each distinct kernel of a CompiledModule; return the count."""
    assert compiled.stats.interpret is False
    kernels = {id(k.fn): k for k in compiled.executable.kernels.values()}
    assert kernels
    for name, k in compiled.executable.kernels.items():
        if kernels.pop(id(k.fn), None) is None:
            continue
        args = [
            jax.ShapeDtypeStruct(tuple(i.shape), np.dtype(i.dtype), sharding=sharding)
            for i in k.inputs
        ]
        text = jax.jit(k.fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, f"kernel {name} is not a Mosaic kernel"
    return len(compiled.executable.kernels)


@pytest.mark.parametrize("name", sorted(SMOKE_PROGRAMS))
def test_smoke_program_kernels_compile_for_v5e(name, one_chip):
    fn, shapes = SMOKE_PROGRAMS[name]
    # placed on the described chip, so the plan reads each parameter in the
    # layout the chip gives it, as the smoke's plans do
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip), shapes,
        is_leaf=lambda s: isinstance(s, tuple) and all(isinstance(d, int) for d in s),
    )
    compiled = stitch(fn, options=OPTS).lower(*args).compile()
    assert _compile_kernels(compiled, one_chip) >= 1


@pytest.mark.parametrize("name", REGRESSION_GRAPHS)
def test_graph_kernels_compile_for_v5e(name, one_chip):
    compiled = compile_module(ALL_GRAPHS[name](), OPTS)
    assert _compile_kernels(compiled, one_chip) >= 1


def _entry(hlo: str) -> str:
    start = hlo.index("\nENTRY")
    return hlo[start: hlo.index("\n}", start)]


def _param_copies(entry: str):
    """``copy`` instructions of an HLO entry computation whose operand is
    one of its parameters: XLA relaying a parameter out for a consumer."""
    params = set(re.findall(r"%([\w.\-]+) = \S+ parameter\(\d+\)", entry))
    return [
        line.strip() for line in entry.splitlines()
        if (m := re.search(r" copy\((?:[^%)]*)%([\w.\-]+)\)", line)) and m.group(1) in params
    ]


def test_decode_layer_reads_kv_in_the_v5e_layout(one_chip):
    """One full-width layer of the decode program: K and V, f32[32, 16,
    1024, 64] laid out {2,3,1,0} on a v5e, reach their kernels through a
    bitcast, with no relayout copy in the replay segment.  Each of the two
    kernels reads one row's 16 heads, 4 MiB, a block: 32 blocks, inside the
    chip's default scoped VMEM."""
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/qwen1.5-0.5b-f32-5layers.json")))
    cfg["num_hidden_layers"] = 1
    traffic = json.load(open(os.path.join(ROOT, "bench/traffic/decode.json")))
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        qwen_decode.arg_shapes(cfg, traffic),
    )
    fn = qwen_decode.program(cfg, traffic)
    compiled = stitch(fn, options=StitchOptions(interpret=False)).lower(*shapes).compile()
    assert compiled.stats.native_layout_operands == 2
    assert compiled.stats.dot_batch_blocks == 2
    kv = {k.name: k for k in compiled.executable.kernels.values() if any(k.native)}
    assert len(kv) == 2
    for k in kv.values():
        assert k.blocks == 32
        assert k.plan.vmem_need + VMEM_HEADROOM <= SCOPED_VMEM_BYTES
    ep = compiled.executable.execution_plan
    (seg,) = ep._segments
    seg.build(lambda: None)
    params = {slot: (shape, dtype) for _, slot, dtype, shape in ep._param_binds}
    args = []
    for s in seg.in_slots:
        shape, dtype = params.get(s) or (ep._template[s].shape, ep._template[s].dtype)
        args.append(jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype), sharding=one_chip))
    entry = _entry(seg.fn.lower(*args).compile().as_text())
    assert "tpu_custom_call" in entry
    assert _param_copies(entry) == []
    kv = re.findall(r"%([\w.\-]+) = f32\[32,16,1024,64\]\{2,3,1,0:T\(8,128\)\} parameter", entry)
    assert len(kv) == 2
    for name in kv:
        assert re.search(rf"bitcast\(%{re.escape(name)}\)", entry)
