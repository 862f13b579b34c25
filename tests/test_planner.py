"""Cost-guided fusion planner: floor property, oracle parity, adversarial
graphs, planner-aware cache keys, and versioned on-disk tuning records."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import compile_and_compare, make_feeds as _feeds
from repro.core import (
    FusionConfig,
    GraphBuilder,
    KernelCache,
    StitchOptions,
    compile_module,
    deep_fuse,
    reference_execute,
    trace,
)
from graphs import (
    broadcast_towers_graph,
    reduce_towers_graph,
    stacked_transformer_graph,
)


def _kernels(comp):
    return comp.stats.stitched_kernels + comp.stats.standalone_kernels




# ----------------------------------------------------- adversarial graphs
@pytest.mark.parametrize("graph_fn", [reduce_towers_graph, broadcast_towers_graph])
def test_planner_beats_greedy_on_adversarial_graphs(graph_fn):
    """The sink-pack candidate commits the tower union as ONE kernel at
    planning time — the horizontal-merge post-pass has nothing left to do."""
    m = graph_fn()
    greedy = compile_module(m, StitchOptions(max_blocks=64, planner="greedy"))
    cost = compile_module(m, StitchOptions(max_blocks=64, planner="cost"))
    assert _kernels(cost) < _kernels(greedy)
    assert _kernels(cost) == 1
    s = cost.stats
    assert s.planner_mode == "cost"
    assert s.plans_explored > 0
    assert s.planner_packs > 0
    assert s.planner_merges == 0     # packed at plan time, not post-merged
    assert s.launches_saved_vs_greedy > 0
    assert s.launches_saved_vs_unfused > 0
    assert 0 < s.planner_predicted_s < s.greedy_predicted_s


def test_planner_never_emits_more_kernels_than_greedy():
    """Across every benchmark graph the planner's launch count is <= greedy's
    (split candidates are only taken when the model says they pay, and none
    of these graphs rewards paying a launch to split)."""
    from graphs import ALL_GRAPHS

    for name, fn in ALL_GRAPHS.items():
        m = fn()
        greedy = compile_module(m, StitchOptions(max_blocks=64, planner="greedy"))
        cost = compile_module(m, StitchOptions(max_blocks=64, planner="cost"))
        assert _kernels(cost) <= _kernels(greedy), name


# ------------------------------------------------------- oracle parity
@pytest.mark.parametrize("mode", ["greedy", "cost"])
@pytest.mark.parametrize(
    "graph_fn", [reduce_towers_graph, broadcast_towers_graph]
)
def test_planner_modes_match_reference_oracle(graph_fn, mode, rng):
    m = graph_fn()
    compile_and_compare(m, _feeds(m, rng), max_blocks=64, planner=mode)


def test_merged_multi_root_kernel_executes_correctly(rng):
    """The packed ReduceTowers kernel carries one root per tower; every
    tower's scalar must still match the oracle bit-for-tolerance."""
    m = reduce_towers_graph(num_towers=4)
    comp = compile_and_compare(m, _feeds(m, rng), max_blocks=64)
    assert comp.stats.planner_packs > 0
    assert comp.stats.stitched_kernels == 1


# -------------------------------------------------------- floor property
def _random_module(seed: int):
    rng = np.random.RandomState(seed)
    b = GraphBuilder(f"rand{seed}")
    shape = [(4, 8), (2, 4, 8), (8, 16)][seed % 3]
    pool = [b.parameter(f"p{i}", shape, jnp.float32) for i in range(2)]
    for k in range(int(rng.randint(3, 14))):
        kind = rng.choice(["unary", "binary", "reduce_bcast", "scalar"])
        x = pool[rng.randint(len(pool))]
        if kind == "unary":
            pool.append(b.unary(str(rng.choice(["exp", "tanh", "square"])), x))
        elif kind == "binary":
            y = pool[rng.randint(len(pool))]
            if y.shape == x.shape:
                pool.append(x + y)
        elif kind == "scalar":
            pool.append(x * float(rng.uniform(-2, 2)))
        else:
            dim = int(rng.randint(x.ndim))
            r = b.reduce(x, (dim,), "sum")
            kept = tuple(i for i in range(x.ndim) if i != dim)
            pool.append(b.broadcast(r, x.shape, kept) + x)
    return b.module


@pytest.mark.parametrize("seed", range(12))
def test_planner_floor_property(seed):
    """The committed plan's modeled latency never exceeds the greedy plan's:
    greedy is always in the candidate set and merges must strictly pay."""
    m = _random_module(seed)
    plan = deep_fuse(m, FusionConfig(planner="cost"))
    st = plan.planner
    assert st.mode == "cost"
    assert st.predicted_s <= st.greedy_predicted_s + 1e-12
    assert st.planned_kernels == plan.num_kernels


@pytest.mark.parametrize("seed", range(6))
def test_planner_plan_invariants(seed):
    """Planner output obeys the same structural invariants as greedy."""
    m = _random_module(seed + 100)
    plan = deep_fuse(m, FusionConfig(planner="cost"))
    pos = {i.id: k for k, i in enumerate(m.instructions)}
    seen = set()
    for f in plan.fusions:
        for mem in f.members:
            assert mem.id not in seen, "instruction fused twice"
            seen.add(mem.id)
        order = [pos[mem.id] for mem in f.members]
        assert order == sorted(order)
    for s in plan.standalone:
        assert s.id not in seen
        seen.add(s.id)
    uncovered = [
        i
        for i in m.instructions
        if i.id not in seen and i.opcode not in ("parameter", "constant")
    ]
    from repro.core.fusion import constant_like

    assert all(constant_like(i) for i in uncovered)


def test_planner_merges_single_op_towers(rng):
    """Singleton seeds are scored too: N independent single-reduce towers
    are the purest launch-bound missed-merge pathology."""
    b = GraphBuilder("single_op_towers")
    for i in range(4):
        x = b.parameter(f"x{i}", (16, 32), jnp.float32)
        _ = b.reduce(x, (0, 1), "sum")
    m = b.module
    greedy = deep_fuse(m, FusionConfig(planner="greedy"))
    cost = deep_fuse(m, FusionConfig(planner="cost"))
    assert greedy.num_kernels == 4
    assert cost.num_kernels < greedy.num_kernels
    assert cost.planner.packs_taken + cost.planner.merges_taken > 0
    compile_and_compare(m, _feeds(m, rng), max_blocks=64)


def test_planner_respects_injected_consistency_checker():
    """Split, pack, and merge commits all go through the SchdConsistent
    extension point.  Greedy never builds a multi-reduce kernel on
    ReduceTowers (one reduce per tower); a checker refusing them must also
    veto the planner's tower packs and merges, which would otherwise put
    all reduces into one kernel."""

    def at_most_one_reduce(roots, members):
        return sum(1 for mem in members if mem.opcode == "reduce") <= 1

    m = reduce_towers_graph(num_towers=4)
    cost = deep_fuse(
        m, FusionConfig(planner="cost", consistency=at_most_one_reduce)
    )
    for f in cost.fusions:
        n_reduce = sum(1 for mem in f.members if mem.opcode == "reduce")
        assert n_reduce <= 1, f
    assert cost.planner.merges_taken == 0
    assert cost.planner.packs_taken == 0
    # without the checker the same graph packs down to one kernel
    free = deep_fuse(m, FusionConfig(planner="cost"))
    assert free.planner.packs_taken > 0


def test_greedy_mode_reproduces_original_algorithm():
    """planner='greedy' explores nothing and commits one fusion per seed."""
    m = reduce_towers_graph()
    plan = deep_fuse(m, FusionConfig(planner="greedy"))
    st = plan.planner
    assert st.mode == "greedy"
    assert st.plans_explored == st.plans_rejected == 0
    assert st.splits_taken == st.merges_taken == 0
    assert plan.num_kernels == st.greedy_kernels


# ------------------------------------------------- cache interaction
def test_stacked_cache_hit_rate_unchanged_by_planner():
    """The planner must not split the stacked-transformer layer fusions:
    the KernelCache hit rate is identical to greedy's."""
    m1 = stacked_transformer_graph(num_layers=8)
    m2 = stacked_transformer_graph(num_layers=8)
    greedy = compile_module(m1, StitchOptions(max_blocks=32, planner="greedy"))
    cost = compile_module(m2, StitchOptions(max_blocks=32, planner="cost"))
    assert cost.stats.cache_hit_rate == greedy.stats.cache_hit_rate
    assert cost.stats.unique_kernels == greedy.stats.unique_kernels
    assert _kernels(cost) == _kernels(greedy)


def test_cache_not_shared_across_planner_modes(rng):
    """Signatures are salted with the planner mode: a greedy-built entry
    must not serve a cost-guided compile (partitions may differ)."""
    cache = KernelCache()
    m = stacked_transformer_graph(num_layers=3)
    compile_module(
        stacked_transformer_graph(num_layers=3),
        StitchOptions(max_blocks=32, planner="greedy"),
        kernel_cache=cache,
    )
    comp2 = compile_module(
        m, StitchOptions(max_blocks=32, planner="cost"), kernel_cache=cache
    )
    # identical middle layers may still hit EACH OTHER within this compile,
    # but nothing may be served by the greedy-salted entries: the cost
    # compile must tune and emit its own representatives.
    assert comp2.stats.kernels_emitted == comp2.stats.unique_kernels > 0
    feeds = _feeds(m, rng)
    out = comp2(feeds)
    ref = reference_execute(m, feeds)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=2e-5, atol=2e-5
        )


# ------------------------------------------- versioned on-disk records
def _compile_with_disk(tmp_path, n_layers=3):
    path = str(tmp_path / "kernels.json")
    opts = StitchOptions(max_blocks=32, kernel_cache_path=path)
    compile_module(stacked_transformer_graph(num_layers=n_layers), opts)
    return path, opts


def test_versioned_records_roundtrip(tmp_path):
    path, opts = _compile_with_disk(tmp_path)
    with open(path) as f:
        store = json.load(f)
    assert store, "tuning records must persist"
    from repro.core.signature import SCHEMA_VERSION

    for rec in store.values():
        assert rec["version"] == SCHEMA_VERSION
    comp2 = compile_module(stacked_transformer_graph(num_layers=3), opts)
    assert comp2.stats.tuning_disk_hits == comp2.stats.kernel_cache_misses > 0


def test_stale_version_records_are_discarded(tmp_path, rng):
    path, opts = _compile_with_disk(tmp_path)
    with open(path) as f:
        store = json.load(f)
    for rec in store.values():
        rec["version"] = 1          # a previous schema generation
    with open(path, "w") as f:
        json.dump(store, f)
    comp2 = compile_module(stacked_transformer_graph(num_layers=3), opts)
    assert comp2.stats.tuning_disk_hits == 0      # stale rows never hint
    m = stacked_transformer_graph(num_layers=3)
    feeds = _feeds(m, rng)
    out = compile_module(m, opts)(feeds)
    ref = reference_execute(m, feeds)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=2e-5, atol=2e-5
        )


def test_corrupt_records_are_discarded_not_raised(tmp_path):
    path, opts = _compile_with_disk(tmp_path)
    with open(path) as f:
        store = json.load(f)
    from repro.core.signature import SCHEMA_VERSION

    for key in store:
        store[key] = {"version": SCHEMA_VERSION, "roots": "garbage"}
    with open(path, "w") as f:
        json.dump(store, f)

    # a cache opened over the corrupt store evicts rows instead of raising
    cache = KernelCache(path)
    assert cache.tuning_hint(next(iter(store))) is None
    assert cache.stale_discards >= 1

    # and a full compile over the corrupt store retunes cleanly (this also
    # rewrites fresh, valid records on save)
    comp2 = compile_module(stacked_transformer_graph(num_layers=3), opts)
    assert comp2.stats.tuning_disk_hits == 0
    assert comp2.stats.stitched_kernels > 0
