"""Schedule spec + Table-1 propagation rules (paper §4.1/§4.2)."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    GraphBuilder,
    REPLICATED,
    Sched,
    Unsatisfiable,
    blocks_of,
    candidate_schedules,
    chunk_shape,
    propagate,
    resolve_schedules,
)
from repro.core.perf_library import PerfLibrary
from repro.core.schedule import ROW, COLUMN, block_index, reshape_legal, tile_legal
from repro.core.tuning import tune


# ---------------------------------------------------------------- blocks math
def test_blocks_and_chunks_row():
    s = Sched("chunked", 1, 2, ROW)
    assert blocks_of((4, 6, 8), s) == 4 * 2
    assert chunk_shape((4, 6, 8), s) == (1, 3, 8)


def test_blocks_and_chunks_column():
    s = Sched("chunked", 1, 3, COLUMN)
    assert blocks_of((4, 6, 8), s) == 3 * 8
    assert chunk_shape((4, 6, 8), s) == (4, 2, 1)


@given(
    st.lists(st.integers(1, 6), min_size=1, max_size=4),
    st.data(),
)
@settings(max_examples=80, deadline=None)
def test_block_index_covers_workspace(dims, data):
    """Property: the blocks×chunk grid tiles the whole output space exactly."""
    shape = tuple(dims)
    cands = candidate_schedules(shape, max_blocks=1 << 12)
    sched = data.draw(st.sampled_from(cands))
    if sched.kind != "chunked":
        return
    blocks = blocks_of(shape, sched)
    cs = chunk_shape(shape, sched)
    seen = np.zeros(shape, dtype=int)
    for b in range(blocks):
        idx = block_index(shape, sched, b)
        sl = tuple(
            slice(i * c, (i + 1) * c) for i, c in zip(idx, cs, strict=False)
        )
        seen[sl] += 1
    assert (seen == 1).all(), f"{sched} does not tile {shape}"


# ---------------------------------------------------------------- propagation
def _instr(builder_fn):
    b = GraphBuilder()
    return builder_fn(b).instr


def test_elementwise_passes_row_and_column():
    i = _instr(lambda b: b.exp(b.parameter("x", (4, 8), jnp.float32)))
    for t in (ROW, COLUMN):
        s = Sched("chunked", 0, 2, t)
        assert propagate(i, s) == [s]


def test_reduce_row_requires_split_left_of_reduce_dims():
    i = _instr(
        lambda b: b.reduce(b.parameter("x", (4, 6, 8), jnp.float32), (2,), "sum")
    )
    # output (4,6); split on dim 0 -> input split 0 < reduce dim 2: Row OK
    (got,) = propagate(i, Sched("chunked", 0, 4, ROW))
    assert got == Sched("chunked", 0, 4, ROW)
    # Column with split left of the reduce dims is rejected
    with pytest.raises(Unsatisfiable):
        propagate(i, Sched("chunked", 0, 4, COLUMN))


def test_reduce_column_requires_split_right_of_reduce_dims():
    i = _instr(
        lambda b: b.reduce(b.parameter("x", (4, 6, 8), jnp.float32), (0,), "sum")
    )
    # output (6,8); out dim 1 -> input dim 2 > reduce dim 0: Column OK
    (got,) = propagate(i, Sched("chunked", 1, 2, COLUMN))
    assert got == Sched("chunked", 2, 2, COLUMN)
    with pytest.raises(Unsatisfiable):
        propagate(i, Sched("chunked", 1, 2, ROW))


def test_transpose_rules():
    i = _instr(
        lambda b: b.transpose(b.parameter("x", (4, 6, 8), jnp.float32), (0, 2, 1))
    )
    # moved dims = {1,2}; split 0 < 1 -> Row passes unchanged
    (got,) = propagate(i, Sched("chunked", 0, 2, ROW))
    assert got == Sched("chunked", 0, 2, ROW)
    with pytest.raises(Unsatisfiable):
        propagate(i, Sched("chunked", 1, 2, ROW))
    with pytest.raises(Unsatisfiable):
        propagate(i, Sched("chunked", 1, 2, COLUMN))


def test_dot_requires_batch_split():
    i = _instr(
        lambda b: b.dot(
            b.parameter("l", (4, 8, 16), jnp.float32),
            b.parameter("r", (4, 16, 8), jnp.float32),
            fusable=True,
        )
    )
    got = propagate(i, Sched("chunked", 0, 2, ROW))
    assert got == [Sched("chunked", 0, 2, ROW)] * 2
    with pytest.raises(Unsatisfiable):
        propagate(i, Sched("chunked", 1, 2, ROW))  # M dim is not a batch dim


def test_reshape_row_remaps_contiguous_runs():
    i = _instr(
        lambda b: b.reshape(b.parameter("x", (4, 6, 8), jnp.float32), (24, 8))
    )
    # out (24,8) split 0 sword 4 -> run = 6*8 elements = input (s=0, sword=4)?
    # run=48 -> input suffix(1)=48 -> c=1, s'=0, w'=4
    (got,) = propagate(i, Sched("chunked", 0, 4, ROW))
    assert got.sched_type == ROW and blocks_of((4, 6, 8), got) == 4


def test_broadcast_maps_or_replicates():
    i = _instr(
        lambda b: b.broadcast(
            b.parameter("x", (6,), jnp.float32), (4, 6, 8), (1,)
        )
    )
    (got,) = propagate(i, Sched("chunked", 1, 2, ROW))
    assert got == Sched("chunked", 0, 2, ROW)       # split maps to operand dim
    (got,) = propagate(i, Sched("chunked", 0, 2, ROW))
    assert got == REPLICATED                        # split not in dims


def test_concat_rules():
    i = _instr(
        lambda b: b.concat(
            [b.parameter("a", (4, 3), jnp.float32), b.parameter("b", (4, 5), jnp.float32)],
            dim=1,
        )
    )
    got = propagate(i, Sched("chunked", 0, 4, ROW))
    assert len(got) == 2 and all(g.sched_type == ROW for g in got)
    with pytest.raises(Unsatisfiable):
        propagate(i, Sched("chunked", 1, 2, ROW))


# ------------------------------------------------------------- resolution
def test_softmax_resolution_all_chunked_on_batch_split():
    b = GraphBuilder()
    x = b.parameter("x", (4, 8, 16), jnp.float32)
    y = b.softmax(x, dim=-1)
    m = b.module
    members = [i for i in m.instructions if i.opcode != "parameter"]
    roots = [y.instr]
    sol = resolve_schedules(members, roots, {y.instr.id: Sched("chunked", 0, 4, ROW)})
    assert sol.blocks == 4
    # every member aligns with the launch grid (no forced replication)
    for mem in members:
        assert sol.sched(mem).kind == "chunked", mem


def test_resolution_rejects_oversized_replication():
    b = GraphBuilder()
    x = b.parameter("x", (512, 1024), jnp.float32)   # 2 MiB
    s = b.reduce(x, (0,), "sum")                     # (1024,)
    y = b.broadcast(s, (512, 1024), (1,)) * x
    m = b.module
    members = [i for i in m.instructions if i.opcode != "parameter"]
    # split on dim 0: the column-reduce input would need full replication of x
    with pytest.raises(Unsatisfiable):
        resolve_schedules(
            members, [y.instr], {y.instr.id: Sched("chunked", 0, 512, ROW)},
            replicate_limit=64 * 1024,
        )


def test_dot_matrix_block_may_pass_replicate_limit():
    """MQA scores: one row's whole K, (1, 8192, 128) f32 = 4 MiB, is the
    finest block the batched dot allows, so it passes the 512 KiB
    replicate limit; the same block of an elementwise op does not."""
    b = GraphBuilder()
    q = b.parameter("q", (64, 12, 128), jnp.float32)
    k = b.parameter("k", (64, 8192, 128), jnp.float32)
    s = b.dot(q, b.transpose(k, (0, 2, 1)), fusable=True)
    members = [i for i in b.module.instructions if i.opcode != "parameter"]
    sol = resolve_schedules(members, [s.instr], {s.instr.id: Sched("chunked", 0, 64, ROW)})
    assert sol.blocks == 64

    b = GraphBuilder()
    x = b.parameter("x", (64, 8192, 128), jnp.float32)
    y = b.exp(x)
    with pytest.raises(Unsatisfiable, match="limit"):
        resolve_schedules([y.instr], [y.instr], {y.instr.id: Sched("chunked", 0, 64, ROW)})


def _scores(q_shape, k_shape, native: bool):
    """Masked attention scores and their row max, ``max(q @ k.T + mask)``,
    with K stamped ``native_layout`` (a v5e's layout) when ``native``."""
    b = GraphBuilder()
    q = b.parameter("q", q_shape, jnp.float32)
    k = b.parameter("k", k_shape, jnp.float32)
    if native:
        k.instr.attrs["native_layout"] = True
    n = len(k_shape)
    s = b.dot(q, b.transpose(k, tuple(range(n - 2)) + (n - 1, n - 2)), fusable=True)
    mask = b.parameter("mask", s.shape, jnp.float32)
    top = b.reduce(s + mask, (n - 1,), "max", keepdims=True)
    members = [i for i in b.module.instructions if i.opcode != "parameter"]
    return members, top.instr, k.instr


QWEN = ((32, 16, 1, 64), (32, 16, 1024, 64))
GRANITE = ((64, 12, 128), (64, 8192, 128))


@pytest.mark.parametrize("shapes,native,blocks,k_block", [
    # qwen's K in a v5e's layout: one row's 16 heads, 4 MiB as (16, 64, 1024)
    (QWEN, True, 32, (1, 16, 1024, 64)),
    # row-major, the same block pads 64 lanes to 128: 8 MiB, over the cap
    (QWEN, False, 32, None),
    # two rows: 8 MiB whatever the layout
    (QWEN, True, 16, None),
    # granite's MQA cache: one row's matrix is 4 MiB, two rows 8 MiB
    (GRANITE, False, 64, (1, 8192, 128)),
    (GRANITE, False, 32, None),
])
def test_dot_block_of_whole_matrices_up_to_the_cap(shapes, native, blocks, k_block):
    """A batched dot's operand may take whole matrices of one or more batch
    elements a block, up to ``DOT_BLOCK_LIMIT`` as VMEM holds the block."""
    members, root, k = _scores(*shapes, native)
    sched = Sched("chunked", 0, blocks, ROW)
    if k_block is None:
        with pytest.raises(Unsatisfiable, match="limit"):
            resolve_schedules(members, [root], {root.id: sched})
        return
    sol = resolve_schedules(members, [root], {root.id: sched})
    assert sol.blocks == blocks
    assert chunk_shape(k.shape, sol.sched(k)) == k_block
    assert sol.dot_batch_block == (np.prod(k_block[:-2]) > 1)


@pytest.mark.parametrize("shapes,native,blocks", [
    (QWEN, True, 32),       # the fewest grid steps that fit the cap
    (QWEN, False, 64),      # row-major: 8 heads a block, 4 MiB padded
    (GRANITE, False, 64),   # one row a block, as before
])
def test_tuner_takes_the_fewest_blocks_of_whole_matrices(shapes, native, blocks):
    """The same bytes read in fewer, larger blocks: the latency model
    charges the grid's steps and one block's exposed copies, so it picks
    the coarsest schedule the cap allows."""
    members, root, _ = _scores(*shapes, native)
    tuned = tune(members, [root], PerfLibrary(), max_blocks=4096)
    assert tuned.solution.blocks == blocks


# ------------------------------------------------------------- TPU tiling
@pytest.mark.parametrize("shape,chunk,dtype,legal", [
    ((400, 40), (400, 40), np.float32, True),      # whole array
    ((400, 40), (50, 40), np.float32, False),      # 50 rows: not 8-aligned
    ((400, 40), (80, 40), np.float32, True),
    ((512, 1024), (512, 128), np.float32, True),
    ((512, 1024), (512, 64), np.float32, False),   # 64 lanes of 1024
    ((32, 128), (8, 128), np.float32, True),
    ((32, 128), (8, 128), jnp.bfloat16, False),    # 16-bit: 16 sublanes
    ((32, 128), (16, 128), jnp.bfloat16, True),
    ((1024,), (64,), np.float32, False),           # rank 1: whole tiles
    ((1024,), (256,), np.float32, False),          # XLA tiles f32[1024] by 1024
    ((2048,), (1024,), np.float32, True),
    ((4, 8, 128), (1, 8, 128), np.float32, True),  # leading dims are free
])
def test_tile_legal(shape, chunk, dtype, legal):
    assert tile_legal(shape, chunk, dtype) is legal


@pytest.mark.parametrize("shape", [(400, 40), (1024,), (4, 16, 512, 64), (64, 100)])
def test_candidate_schedules_are_tile_legal(shape):
    cands = candidate_schedules(shape)
    assert cands
    assert all(tile_legal(shape, chunk_shape(shape, c)) for c in cands)


@pytest.mark.parametrize("src,dst,legal", [
    ((16, 128), (2048,), False),          # lanes change
    ((512, 1024), (512, 16, 64), False),
    ((1, 16, 512, 64), (16, 512, 64), True),   # leading dims only
    ((8, 128), (1, 8, 128), True),
    ((16, 128), (2, 8, 128), True),       # whole 8-row tiles both sides
])
def test_reshape_legal(src, dst, legal):
    assert reshape_legal(src, dst) is legal


def _lane_changing_reshape():
    b = GraphBuilder()
    x = b.parameter("x", (16, 128), jnp.float32)
    y = b.reshape(b.exp(x), (2048,))
    members = [i for i in b.module.instructions if i.opcode != "parameter"]
    return members, y.instr


def test_untileable_reshape_root_exits_the_kernel():
    members, root = _lane_changing_reshape()
    sol = resolve_schedules(members, [root], {root.id: REPLICATED})
    assert root.id in sol.exits
    # the kernel writes the reshape's operand; the reshape runs outside
    shape, sched = sol.block(root)
    assert shape == (16, 128) and sched.kind == "replicated"


def test_untileable_reshape_without_exits_is_unsatisfiable():
    members, root = _lane_changing_reshape()
    with pytest.raises(Unsatisfiable, match="in-kernel reshape"):
        resolve_schedules(members, [root], {root.id: REPLICATED}, allow_exits=False)


def test_resolution_rejects_untileable_block():
    b = GraphBuilder()
    x = b.parameter("x", (400, 40), jnp.float32)
    y = b.exp(x)
    members = [y.instr]
    with pytest.raises(Unsatisfiable, match="tiling"):
        resolve_schedules(members, [y.instr], {y.instr.id: Sched("chunked", 0, 8, ROW)})
