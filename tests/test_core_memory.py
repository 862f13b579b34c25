"""VMEM (shared-memory) planning: requirements, shrinking, dominance sharing
(paper §5.1)."""
import jax.numpy as jnp
import pytest

from repro.core import GraphBuilder, MemoryInfeasible, Sched, plan_memory, resolve_schedules
from repro.core.memory import ALLOC, INLINE, SHARE, dominance_tree, dominates, vmem_bytes
from repro.core.schedule import ROW, block_vmem_bytes


def _resolve(b, root, split=0, sword=1):
    m = b.module
    members = [i for i in m.instructions if i.opcode != "parameter"]
    roots = [r for r in m.roots]
    sol = resolve_schedules(
        members, roots, {r.id: Sched("chunked", split, sword, ROW) for r in roots}
    )
    return members, roots, sol


def test_nonroot_reduce_requires_alloc():
    b = GraphBuilder()
    x = b.parameter("x", (4, 8), jnp.float32)
    s = b.reduce(x, (1,), "sum")
    y = b.broadcast(s, (4, 8), (0,)) + x
    members, roots, sol = _resolve(b, y)
    plan = plan_memory(members, roots, sol)
    assert plan.action(s.instr) == ALLOC
    assert plan.action(y.instr) == INLINE


def test_expensive_multiuser_allocated_cheap_singleuser_inlined():
    b = GraphBuilder()
    x = b.parameter("x", (4, 8), jnp.float32)
    e = b.exp(x)              # expensive, 2 users
    a = e + x                 # cheap, 1 user
    _ = a * e
    members, roots, sol = _resolve(b, None)
    plan = plan_memory(members, roots, sol)
    assert plan.action(e.instr) == ALLOC
    assert plan.action(a.instr) == INLINE


def test_expensive_feeding_dot_through_bitcast_allocated():
    """The paper's Divide.1 -> Bitcast.1 -> Dot.1 case (Fig. 3)."""
    b = GraphBuilder()
    x = b.parameter("x", (2, 4, 8), jnp.float32)
    v = b.parameter("v", (2, 8, 4), jnp.float32)
    d = b.exp(x) / 2.0                        # expensive, single user
    bc = b.bitcast(d, (2, 4, 8))
    _ = b.dot(bc, v, fusable=True)
    members, roots, sol = _resolve(b, None)
    plan = plan_memory(members, roots, sol)
    assert plan.action(d.instr) == ALLOC


def test_shrinking_order_cheap_multiuser_first():
    b = GraphBuilder()
    x = b.parameter("x", (64, 64), jnp.float32)   # 32 KiB once lane-padded
    cheap = x + x                                  # cheap multi-user
    e = b.exp(x)                                   # expensive multi-user
    _ = cheap * e + (cheap - e)
    members, roots, sol = _resolve(b, None)
    # budget fits the double-buffered I/O blocks plus only one buffer: the
    # cheap one is dropped first
    io = plan_memory(members, roots, sol).io_bytes
    plan = plan_memory(members, roots, sol, vmem_limit=io + 40 * 1024)
    assert plan.action(cheap.instr) == INLINE
    assert plan.action(e.instr) == ALLOC
    assert plan.num_shrinks == 1
    assert plan.shrunk == [cheap.instr.name]


def test_required_over_budget_raises_feedback():
    b = GraphBuilder()
    x = b.parameter("x", (64, 64), jnp.float32)
    s = b.reduce(x, (1,), "sum")                   # required buffer
    _ = b.broadcast(s, (64, 64), (0,)) + x
    members, roots, sol = _resolve(b, None)
    with pytest.raises(MemoryInfeasible):
        plan_memory(members, roots, sol, vmem_limit=16)


def test_dominance_tree_on_diamond():
    b = GraphBuilder()
    x = b.parameter("x", (4, 4), jnp.float32)
    e = b.exp(x)                   # diamond top
    lhs, rhs = e + 1.0, e * 2.0
    root = lhs / rhs                   # diamond bottom (root)
    m = b.module
    members = [i for i in m.instructions if i.opcode != "parameter"]
    idom = dominance_tree(members, [root.instr])
    assert dominates(root.instr.id, e.instr.id, idom)      # root dominates all
    assert not dominates(lhs.instr.id, e.instr.id, idom)   # side of diamond no
    assert not dominates(rhs.instr.id, e.instr.id, idom)


def test_space_sharing_dominator_reuses_dead_slot():
    """exp.2 dominates exp.1 in a two-stage chain -> SHARE (paper Fig. 3)."""
    b = GraphBuilder()
    x = b.parameter("x", (8, 16), jnp.float32)
    e1 = b.exp(x)                                  # expensive, 2 users
    r1 = b.reduce(e1, (1,), "sum")
    m1 = e1 * b.broadcast(r1, (8, 16), (0,))
    e2 = b.exp(m1)                                 # expensive, 2 users
    r2 = b.reduce(e2, (1,), "sum")
    _ = e2 * b.broadcast(r2, (8, 16), (0,))
    members, roots, sol = _resolve(b, None)
    plan = plan_memory(members, roots, sol)
    assert plan.action(e1.instr) == ALLOC
    assert plan.entries[e2.instr.id].action == SHARE
    assert plan.entries[e2.instr.id].slot == plan.entries[e1.instr.id].slot
    assert plan.shared_bytes > 0 and plan.shared_ratio > 0


def test_no_sharing_between_concurrently_live_buffers():
    b = GraphBuilder()
    x = b.parameter("x", (8, 16), jnp.float32)
    e1 = b.exp(x)
    e2 = b.log(b.abs(x) + 1.0)
    r1 = b.reduce(e1, (1,), "sum")
    r2 = b.reduce(e2, (1,), "sum")
    # both e1 and e2 used again AFTER both reduces -> overlapping live ranges
    _ = (e1 + e2) * b.broadcast(r1 + r2, (8, 16), (0,))
    members, roots, sol = _resolve(b, None)
    plan = plan_memory(members, roots, sol)
    slots = {
        plan.entries[i.instr.id].slot
        for i in (e1, e2)
        if plan.entries[i.instr.id].action in (ALLOC, SHARE)
    }
    assert len(slots) == 2, "live buffers must not share a slot"


@pytest.mark.parametrize("shape,dtype,nbytes", [
    ((8, 128), jnp.float32, 4096),
    ((50, 40), jnp.float32, 56 * 128 * 4),     # padded to (56, 128)
    ((16, 128), jnp.bfloat16, 4096),
    ((8, 128), jnp.bfloat16, 16 * 128 * 2),    # 16-bit tiles hold 16 rows
    ((40,), jnp.float32, 8 * 128 * 4),         # rank 1 lays out as one row
    ((), jnp.float32, 8 * 128 * 4),            # rank 0 travels as (1, 1)
    ((3, 8, 128), jnp.float32, 3 * 4096),
])
def test_vmem_bytes_pads_to_the_tiling(shape, dtype, nbytes):
    assert vmem_bytes(shape, dtype) == nbytes


def test_io_blocks_are_double_buffered_in_the_plan():
    b = GraphBuilder()
    x = b.parameter("x", (64, 128), jnp.float32)
    y = b.exp(x) + x
    members, roots, sol = _resolve(b, y, sword=4)   # (16, 128) blocks
    plan = plan_memory(members, roots, sol)
    # one input and one output block, 8 KiB each, two buffers apiece
    assert plan.io_bytes == 2 * 2 * 16 * 128 * 4
    assert plan.vmem_need >= plan.io_bytes
    with pytest.raises(MemoryInfeasible):
        plan_memory(members, roots, sol, vmem_limit=plan.io_bytes - 1)


@pytest.mark.parametrize("native,nbytes", [
    (True, 16 * 64 * 1024 * 4),      # stored (1, 16, 64, 1024): no padding
    (False, 16 * 1024 * 128 * 4),    # row-major: 64 lanes padded to 128
])
def test_native_layout_block_is_counted_at_its_stored_shape(native, nbytes):
    """qwen's K block (1, 16, 1024, 64): 4 MiB read in a v5e's layout,
    8 MiB padded row-major.  The plan counts I/O blocks twice."""
    b = GraphBuilder()
    q = b.parameter("q", (32, 16, 1, 64), jnp.float32)
    k = b.parameter("k", (32, 16, 1024, 64), jnp.float32)
    if native:
        k.instr.attrs["native_layout"] = True
    s = b.dot(q, b.transpose(k, (0, 1, 3, 2)), fusable=True)
    assert block_vmem_bytes(k.instr, (1, 16, 1024, 64)) == nbytes
    if not native:
        return            # over DOT_BLOCK_LIMIT: no schedule takes it
    members, roots, sol = _resolve(b, s, sword=32)
    plan = plan_memory(members, roots, sol)
    q_block, s_block = 16 * 8 * 128 * 4, 16 * 8 * 1024 * 4
    assert plan.io_bytes == 2 * (nbytes + q_block + s_block)


def test_planner_and_codegen_share_the_native_layout_predicate():
    from repro.core import codegen, memory, schedule

    assert codegen.native_block is schedule.native_block
    assert memory.block_vmem_bytes is schedule.block_vmem_bytes
