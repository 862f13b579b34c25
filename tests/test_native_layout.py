"""Stitched kernels read a parameter in its device's default layout when
that layout swaps the two minor dims (``codegen.stamp_native_layouts``).

A CPU lays every array out row-major, so these tests force the decision by
replacing the layout query with one that answers as a v5e does for
``f32[rows, heads, context, 64]``: minor-to-major ``(2, 3, 1, 0)``.  The
kernels run in the Pallas interpreter.  ``tests/test_tpu_compile.py``
compiles the real decision for a described v5e.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import StitchOptions, stitch
from repro.core import codegen, compile_module, reference_execute, trace
from repro.core.schedule import chunk_shape, native_block

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.programs import qwen_decode  # noqa: E402

#: one layer at a reduced size: 4 rows, 2 heads of 64, context 256
CFG = {"hidden_size": 128, "num_attention_heads": 2, "intermediate_size": 256,
       "num_hidden_layers": 1, "rms_norm_eps": 1e-6, "rope_theta": 1e6}
TRAFFIC = {"rows": 4, "context": 256, "context_min": 32}


def _swap_rank4(dtype, shape, device):
    """A v5e's answer for K/V-shaped arrays, row-major for the rest."""
    n = len(shape)
    if n == 4 and shape[-1] < 128:
        return (2, 3, 1, 0)
    return tuple(range(n - 1, -1, -1))


def _swap_all(dtype, shape, device):
    """Minor dims swapped for every array (asked only at rank 2 or more)."""
    n = len(shape)
    return (n - 2, n - 1) + tuple(range(n - 3, -1, -1))


def _decode(layers=1):
    cfg = dict(CFG, num_hidden_layers=layers)
    fn = qwen_decode.program(cfg, TRAFFIC)
    args = qwen_decode.make_args(cfg, TRAFFIC, seed=7, variants=1)[0]
    return fn, args


def _compile(fn, args):
    lowered = stitch(fn).lower(*args)
    compiled = lowered.compile()
    feeds = dict(zip(lowered.param_names, jax.tree_util.tree_leaves(args), strict=True))
    return lowered, compiled, feeds


def _assert_close(out, ref):
    assert set(out) == set(ref)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(out[k], np.float64), np.asarray(ref[k], np.float64),
            rtol=2e-5, atol=2e-5, err_msg=f"root {k} diverged",
        )


@pytest.mark.parametrize("query", [_swap_rank4, _swap_all], ids=["kv", "all"])
def test_reduced_decode_matches_reference_in_native_layout(monkeypatch, query):
    monkeypatch.setattr(codegen, "default_minor_to_major", query)
    fn, args = _decode()
    lowered, compiled, feeds = _compile(fn, args)
    stats = compiled.stats
    assert stats.interpret is True
    if query is _swap_rank4:
        assert stats.native_layout_operands == 2    # K and V of the one layer
    else:
        assert stats.native_layout_operands > 2
    assert {p.name for p in lowered.module.parameters
            if p.attrs.get("native_layout")} >= {
        n for n, leaf in feeds.items() if np.ndim(leaf) == 4
    }
    _assert_close(compiled(feeds), reference_execute(lowered.module, feeds))


def test_native_layout_renames_only_the_kernels_it_changes(monkeypatch):
    fn, args = _decode()
    _, plain, _ = _compile(fn, args)
    monkeypatch.setattr(codegen, "default_minor_to_major", _swap_rank4)
    _, native, _ = _compile(fn, args)
    before = {k.name for k in plain.executable.kernels.values()}
    swapped = {k.name for k in native.executable.kernels.values() if any(k.native)}
    after = {k.name for k in native.executable.kernels.values() if not any(k.native)}
    assert len(swapped) == 2                        # scores and weighted sum
    assert after < before and not swapped & before


def test_eager_and_jitted_replays_are_bit_identical_in_native_layout(monkeypatch):
    monkeypatch.setattr(codegen, "default_minor_to_major", _swap_rank4)
    fn, args = _decode(layers=2)
    _, compiled, feeds = _compile(fn, args)
    assert compiled.stats.native_layout_operands == 4
    jitted = compiled.executable.jit_execute(feeds)
    eager = compiled.executable.execute_eager(feeds)
    assert set(jitted) == set(eager)
    for k in jitted:
        np.testing.assert_array_equal(np.asarray(jitted[k]), np.asarray(eager[k]))


def test_swapped_block_off_the_tiling_stays_row_major():
    """A (8, 256) block of a (16, 256) parameter tiles; swapped it is a
    (256, 8) block of (256, 16), whose lane dim is neither whole nor a
    multiple of 128."""
    m = trace(lambda b, x: b.exp(x), ("x", (16, 256), jnp.float32))
    x = m.parameters[0]
    x.attrs["native_layout"] = True
    assert native_block(x, (16, 256))
    assert not native_block(x, (8, 256))
    assert native_block(x, (16, 128))
    assert not native_block(x, (16, 256), windowed=True)
    del x.attrs["native_layout"]
    assert not native_block(x, (16, 256))


@pytest.mark.parametrize("max_blocks", [2, 1])
def test_parameter_held_whole_across_blocks_stays_row_major(monkeypatch, max_blocks):
    """``w`` (1, 2048) is held whole while ``x`` (64, 2048) goes in row
    blocks: with a grid of blocks each block would window ``w`` at a
    traced offset, on its lane dim once swapped, so both stay row-major
    (``x`` for its tiling); with one block both are read swapped.  (At
    512 KiB ``x`` is large enough that the tuner takes the grid it may.)"""
    monkeypatch.setattr(codegen, "default_minor_to_major", _swap_all)

    def f(b, x, w):
        return b.exp(x) * b.broadcast(b.reshape(w, (2048,)), (64, 2048), (1,))

    m = trace(f, ("x", (64, 2048), jnp.float32), ("w", (1, 2048), jnp.float32))
    rng = np.random.RandomState(0)
    feeds = {p.name: rng.uniform(-1, 1, p.shape).astype(np.float32) for p in m.parameters}
    compiled = compile_module(
        m, StitchOptions(max_blocks=max_blocks), device=jax.devices()[0]
    )
    (k,) = {id(k): k for k in compiled.executable.kernels.values()}.values()
    assert k.blocks == max_blocks
    assert k.native == ((False, False) if max_blocks > 1 else (True, True))
    _assert_close(compiled(feeds), reference_execute(m, feeds))


def test_row_blocked_parameter_stays_row_major(monkeypatch):
    """Softmax rows of a (384, 640) parameter in blocks of (192, 640):
    the parameter is stamped, but its swapped block would break the
    tiling (192 lanes of 384)."""
    monkeypatch.setattr(codegen, "default_minor_to_major", _swap_all)
    m = trace(lambda b, x: b.softmax(x, dim=-1), ("x", (384, 640), jnp.float32))
    feeds = {"x": np.random.RandomState(0).uniform(-1, 1, (384, 640)).astype(np.float32)}
    compiled = compile_module(m, StitchOptions(max_blocks=2), device=jax.devices()[0])
    assert m.parameters[0].attrs.get("native_layout")
    (k,) = {id(k): k for k in compiled.executable.kernels.values()}.values()
    assert k.blocks > 1 and k.native == (False,)
    assert compiled.stats.native_layout_operands == 0
    _assert_close(compiled(feeds), reference_execute(m, feeds))


def test_cpu_query_changes_nothing():
    fn, args = _decode()
    lowered, compiled, feeds = _compile(fn, args)
    assert compiled.stats.native_layout_operands == 0
    assert not any(p.attrs.get("native_layout") for p in lowered.module.parameters)
    assert not any(any(k.native) for k in compiled.executable.kernels.values())
    # the same module compiled with no device at all: the same signatures
    plain = compile_module(lowered.module, StitchOptions())
    assert [r.signature for r in compiled.stats.reports] == [
        r.signature for r in plain.stats.reports
    ]


def test_cpu_plan_of_a_full_width_layer_keeps_its_kernel_names():
    """The row-major attention kernels of the decode benchmark keep their
    names on a CPU, where nothing is stamped: the weighted sum
    ``stitch_62d7c765`` as the recorded chip slice in ``tests/bench/data``
    carries it, and the scores ``stitch_e7f40eca``, which took in the
    reshape of its result once a block of 8 heads' whole K matrices (4 MiB
    padded) became legal; the slice carries it before that, as
    ``stitch_aa85ce72``."""
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/qwen1.5-0.5b-f32-5layers.json")))
    cfg["num_hidden_layers"] = 1
    traffic = json.load(open(os.path.join(ROOT, "bench/traffic/decode.json")))
    compiled = stitch(qwen_decode.program(cfg, traffic)).lower(
        *qwen_decode.arg_shapes(cfg, traffic)
    ).compile()
    assert compiled.stats.native_layout_operands == 0
    names = {k.name for k in compiled.executable.kernels.values()}
    assert {"stitch_e7f40eca", "stitch_62d7c765"} <= names



def test_dot_batch_blocks_count_kernels_that_read_several_matrices_a_block():
    """``CompileStats``, ``launch_stats()`` and ``report()`` count the
    kernel instances whose block of a batched-dot operand holds whole
    matrices of more than one batch element: here K's or V's two heads."""
    fn, args = _decode(layers=2)
    st = stitch(fn)
    st(*args)
    ex = st._last.compiled.executable
    readers = [
        k for k in ex.kernels.values()
        if k.solution is not None and k.solution.dot_batch_block
    ]
    assert readers
    for k in readers:
        kv = [i for i in k.inputs if len(i.shape) == 4 and i.shape[2] == TRAFFIC["context"]]
        if kv[0].id in k.solution.assignment:      # the signature's representative
            assert chunk_shape(kv[0].shape, k.solution.sched(kv[0]))[1:] == kv[0].shape[1:]
    assert st.stats.dot_batch_blocks == ex.launch_stats().dot_batch_blocks == len(readers)
    assert f"dot batch blocks : {len(readers)} kernel(s)" in st.report()
