"""One full-width layer of the stitch decode program of the benchmark
compiles for a TPU v5e.

Nothing runs: the chip is described by a compile-only TPU topology (a v5e
2x2, of which one device is used), not attached.  Each distinct kernel of
the decode plan is compiled for one device.
"""
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

from repro import StitchOptions, stitch  # noqa: E402


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
    """AOT compiles for a described chip cannot be read back from the
    persistent cache: keep it off while these tests run."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _one_layer(name, **cfg_over):
    cell = harness.load_cell(name)
    prog = harness.module(cell, "programs", cell.traffic["program"] + ".py")
    cfg = dict(cell.config, **cfg_over)
    return cell, prog, cfg


def test_qwen_decode_layer_compiles_for_v5e(topo, no_cache):
    cell, prog, cfg = _one_layer("stitch.qwen1.5-0.5b.decode", num_hidden_layers=1)
    one = SingleDeviceSharding(topo.devices[0])
    fn = prog.program(cfg, cell.traffic)
    opts = StitchOptions(interpret=False, **cell.traffic["options"])
    compiled = stitch(fn, options=opts).lower(*prog.arg_shapes(cfg, cell.traffic)).compile()
    assert compiled.stats.interpret is False
    seen = set()
    for k in compiled.executable.kernels.values():
        if id(k.fn) in seen:
            continue
        seen.add(id(k.fn))
        args = [jax.ShapeDtypeStruct(tuple(i.shape), np.dtype(i.dtype), sharding=one)
                for i in k.inputs]
        assert "tpu_custom_call" in jax.jit(k.fn).lower(*args).compile().as_text()
    assert len(seen) >= 10
