"""Measured-cost autotuning: the timing harness, the versioned
MeasuredCostStore, the planner's measured-over-analytic preference, and the
cold-start guarantees (empty / wrong-device / stale-schema stores must fall
back to analytic costs without changing any plan)."""
import json
import os

import numpy as np
import pytest

from conftest import compile_and_compare, make_feeds as _feeds
from repro.core import (
    MeasuredCost,
    MeasuredCostStore,
    StitchOptions,
    compile_module,
    device_fingerprint,
    emit_group,
    measure_callable,
    measure_group,
    measure_kernel,
)
from repro.core.measure import MEASURE_SCHEMA_VERSION
from repro.core.perf_library import JsonStore
from graphs import (
    ALL_GRAPHS,
    reduce_towers_graph,
    stitch_pipeline_graph,
)


def _kernels(comp):
    return comp.stats.stitched_kernels + comp.stats.standalone_kernels


def _fusable_members(module):
    return [
        i
        for i in module.instructions
        if i.opcode not in ("parameter", "constant") and not i.is_library_call
    ]


# ----------------------------------------------------------- store basics
def test_store_roundtrip(tmp_path):
    path = str(tmp_path / "measured.json")
    fp = device_fingerprint()
    s = MeasuredCostStore(path, device_fp=fp)
    assert s.get("sig") is None and s.misses == 1
    s.put("sig", 1.5e-3, model_s=2e-6, repeats=5)
    s.save()

    s2 = MeasuredCostStore(path, device_fp=fp)
    rec = s2.get("sig")
    assert rec == MeasuredCost(cost_s=1.5e-3, model_s=2e-6, repeats=5)
    assert s2.hits == 1 and s2.misses == 0 and len(s2) == 1


def test_stale_schema_version_rows_evicted_not_raised(tmp_path):
    path = str(tmp_path / "measured.json")
    fp = device_fingerprint()
    s = MeasuredCostStore(path, device_fp=fp)
    s.put("sig", 1e-3)
    s.save()
    with open(path) as f:
        rows = json.load(f)
    for rec in rows.values():
        rec["version"] = MEASURE_SCHEMA_VERSION - 1
    with open(path, "w") as f:
        json.dump(rows, f)

    s2 = MeasuredCostStore(path, device_fp=fp)
    assert s2.get("sig") is None
    assert s2.stale_discards == 1 and s2.misses == 1
    assert len(s2) == 0                       # evicted, not just skipped


def test_wrong_device_rows_evicted(tmp_path):
    """A row whose key matches but whose device field disagrees (e.g. the
    file was hand-merged from another machine) is evicted on read."""
    path = str(tmp_path / "measured.json")
    fp = device_fingerprint()
    s = MeasuredCostStore(path, device_fp=fp)
    s.put("sig", 1e-3)
    s.save()
    with open(path) as f:
        rows = json.load(f)
    for rec in rows.values():
        rec["device"] = "0" * 16
    with open(path, "w") as f:
        json.dump(rows, f)

    s2 = MeasuredCostStore(path, device_fp=fp)
    assert s2.get("sig") is None and s2.stale_discards == 1


@pytest.mark.parametrize(
    "payload",
    [
        {"cost_s": "garbage"},
        {"cost_s": 0.0},                      # non-positive time is corrupt
        {"cost_s": float("nan")},
        {},                                   # missing fields entirely
        "not even a dict",
    ],
)
def test_corrupt_rows_evicted_not_raised(tmp_path, payload):
    path = str(tmp_path / "measured.json")
    fp = device_fingerprint()
    s = MeasuredCostStore(path, device_fp=fp)
    s.put("sig", 1e-3)
    s.save()
    with open(path) as f:
        rows = json.load(f)
    key = next(iter(rows))
    if isinstance(payload, dict):
        rows[key] = {
            "version": MEASURE_SCHEMA_VERSION, "device": fp, **payload
        }
    else:
        rows[key] = payload
    with open(path, "w") as f:
        json.dump(rows, f)

    s2 = MeasuredCostStore(path, device_fp=fp)
    assert s2.get("sig") is None
    assert s2.stale_discards == 1


def test_device_fingerprint_varies_with_interpret_flag():
    assert device_fingerprint(interpret=True) != device_fingerprint(
        interpret=False
    )


# ------------------------------------------------- atomic save (crash sim)
def test_atomic_save_survives_crash_mid_write(tmp_path, monkeypatch):
    """A crash mid-``json.dump`` must leave the previous store intact and no
    scratch file behind — the temp-file + ``os.replace`` protocol."""
    path = str(tmp_path / "store.json")
    s = JsonStore(path)
    s.put("k", 1)
    s.save()

    s.put("k2", 2)

    def exploding_dump(obj, f, **kw):
        f.write('{"torn')                     # a torn prefix hits the disk
        raise RuntimeError("simulated crash mid-write")

    monkeypatch.setattr(json, "dump", exploding_dump)
    with pytest.raises(RuntimeError):
        s.save()
    monkeypatch.undo()

    with open(path) as f:
        assert json.load(f) == {"k": 1}       # previous save, not torn bytes
    stray = [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]
    assert stray == []                        # scratch file cleaned up

    s.save()                                  # and a later save recovers
    with open(path) as f:
        assert json.load(f) == {"k": 1, "k2": 2}


def test_atomic_save_ignores_preexisting_partial_tmp(tmp_path):
    """Scratch names are unique (mkstemp): junk left by a crashed writer at
    a guessable ``path + '.tmp'`` can never be replaced over the store."""
    path = str(tmp_path / "store.json")
    with open(path + ".tmp", "w") as f:
        f.write('{"torn')
    s = JsonStore(path)
    s.put("k", 1)
    s.save()
    with open(path) as f:
        assert json.load(f) == {"k": 1}


def test_measured_store_save_is_atomic(tmp_path, monkeypatch):
    """The tuning store rides the same protocol as the kernel cache."""
    path = str(tmp_path / "measured.json")
    fp = device_fingerprint()
    s = MeasuredCostStore(path, device_fp=fp)
    s.put("sig", 1e-3)
    s.save()

    s.put("sig2", 2e-3)

    def exploding_dump(obj, f, **kw):
        raise RuntimeError("simulated crash")

    monkeypatch.setattr(json, "dump", exploding_dump)
    with pytest.raises(RuntimeError):
        s.save()
    monkeypatch.undo()

    s2 = MeasuredCostStore(path, device_fp=fp)
    assert s2.get("sig") is not None          # old store still readable


# ------------------------------------------------------ the timing harness
def test_measure_callable_median_with_warmup():
    calls = []

    def fn(x):
        calls.append(1)
        return x

    t = measure_callable(fn, [np.ones(4)], repeats=3, warmup=2)
    assert t >= 0.0
    assert len(calls) == 5                    # 2 warmup + 3 timed


def test_emit_and_measure_single_schedule_group():
    m = reduce_towers_graph(num_towers=1)
    members = _fusable_members(m)
    kernel = emit_group(members, max_blocks=64)
    assert kernel is not None and not kernel.stitched
    t = measure_kernel(kernel, repeats=2)
    assert t > 0.0
    assert measure_group(members, repeats=1, max_blocks=64) > 0.0


def test_emit_and_measure_stitched_group():
    """StitchPipe's fusable chain has no single consistent schedule — the
    harness must fall back to the multi-phase stitched lowering, so
    stitched-vs-split alternatives are both measurable."""
    m = stitch_pipeline_graph()
    members = _fusable_members(m)
    kernel = emit_group(members, max_blocks=64)
    assert kernel is not None and kernel.stitched
    assert measure_kernel(kernel, repeats=1) > 0.0


def test_measure_group_returns_none_for_infeasible_groups():
    m = stitch_pipeline_graph()
    members = _fusable_members(m)
    # a 1-byte VMEM budget can stage neither scratch nor interface buffers:
    # no lowering exists, exactly the sets the scorer returns None for
    assert emit_group(members, vmem_limit=1) is None
    assert measure_group(members, vmem_limit=1) is None


# ----------------------------------------------------- options / fingerprint
def test_measure_repeats_validated():
    with pytest.raises(ValueError, match="measure_repeats"):
        StitchOptions(measure_repeats=0)


def test_autotune_knobs_salt_options_fingerprint():
    from repro.core.pipeline import _options_fingerprint

    base = StitchOptions(max_blocks=64)
    assert _options_fingerprint(base) != _options_fingerprint(
        StitchOptions(max_blocks=64, autotune=True)
    )
    assert _options_fingerprint(base) != _options_fingerprint(
        StitchOptions(max_blocks=64, measure_repeats=9)
    )
    assert _options_fingerprint(base) != _options_fingerprint(
        StitchOptions(max_blocks=64, tuning_store_path="/tmp/t.json")
    )


# --------------------------------------------------- autotune, end to end
def test_autotune_measures_and_persists(tmp_path):
    path = str(tmp_path / "measured.json")
    opts = StitchOptions(
        max_blocks=64, autotune=True, measure_repeats=2,
        tuning_store_path=path,
    )
    c1 = compile_module(reduce_towers_graph(num_towers=1), opts)
    assert c1.stats.measurements_taken > 0
    assert c1.stats.measured_hits == 0        # cold store
    assert c1.stats.model_error_pct is not None

    with open(path) as f:
        rows = json.load(f)
    assert rows
    fp = device_fingerprint(interpret=opts.interpret)
    for key, rec in rows.items():
        assert key.startswith(fp + "|")
        assert rec["version"] == MEASURE_SCHEMA_VERSION
        assert rec["device"] == fp
        assert rec["cost_s"] > 0.0

    c2 = compile_module(reduce_towers_graph(num_towers=1), opts)
    assert c2.stats.measured_hits > 0         # warm store served the planner


def test_warm_store_flips_plan_decision():
    """THE closed-loop assertion: interpret-mode measurements (milliseconds)
    contradict the analytic model (microseconds) about whether packing two
    towers into one kernel pays.  Cold, the planner trusts the model and
    packs; warm, the store's measured cost of the packed kernel loses to the
    analytic per-tower split costs and the SAME graph re-plans to 2 kernels
    — the store entry provably flipped the decision."""
    opts = StitchOptions(max_blocks=64, autotune=True, measure_repeats=2)
    store = MeasuredCostStore()
    cold = compile_module(reduce_towers_graph(num_towers=2), opts,
                          measured_store=store)
    assert _kernels(cold) == 1                # analytic: packing wins
    assert cold.stats.measurements_taken > 0

    warm = compile_module(reduce_towers_graph(num_towers=2), opts,
                          measured_store=store)
    assert warm.stats.measured_hits > 0
    assert _kernels(warm) == 2                # measured: packing loses

    # and the flipped plan still computes the right answer
    rng = np.random.RandomState(0)
    m = reduce_towers_graph(num_towers=2)
    compile_and_compare(
        m, _feeds(m, rng), max_blocks=64, autotune=True,
    )


def test_read_only_store_reuses_autotuned_measurements(tmp_path):
    """tuning_store_path WITHOUT autotune reads measurements but never takes
    new ones — the measure salt deliberately excludes the autotune knobs."""
    path = str(tmp_path / "measured.json")
    warm_opts = StitchOptions(
        max_blocks=64, autotune=True, measure_repeats=2,
        tuning_store_path=path,
    )
    compile_module(reduce_towers_graph(num_towers=2), warm_opts)

    ro_opts = StitchOptions(max_blocks=64, tuning_store_path=path)
    ro = compile_module(reduce_towers_graph(num_towers=2), ro_opts)
    assert ro.stats.measurements_taken == 0
    assert ro.stats.measured_hits > 0
    assert _kernels(ro) == 2                  # measured costs still flip it


def test_frontend_autotune_kwarg(tmp_path):
    import jax.numpy as jnp
    from repro import stitch

    @stitch(autotune=True)
    def f(x):
        return jnp.tanh(x * 0.5) + x

    x = np.random.RandomState(0).rand(8, 16).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(f(x)), np.tanh(x * 0.5) + x, rtol=2e-5, atol=2e-5
    )
    assert f.options.autotune
    assert f.stats.measurements_taken > 0
    assert f._measured_store is not None and len(f._measured_store) > 0


# ------------------------------------------- cold-start property (10 graphs)
def _plan_shape(comp):
    """Structural view of a compiled plan, independent of the options salt
    (reports carry ``salt + sha256``; the raw hash is the last 64 chars)."""
    return sorted(
        (
            r.num_ops,
            r.blocks,
            round(r.cost_s, 15),
            # root names carry global instruction counters (sub.8 vs sub.50
            # across fresh builds of the same graph): keep the opcode part
            tuple(n.rsplit(".", 1)[0] for n in r.roots),
            r.num_phases,
            r.signature[-64:],
        )
        for r in comp.stats.reports
    ), comp.stats.stitched_kernels, comp.stats.standalone_kernels


def _tampered_store(tmp_path, name, graph_fn, opts, kind: str):
    """A store that LOOKS warm for this graph but must serve nothing:
    empty, wrong-device rows, or stale-schema rows."""
    if kind == "empty":
        return MeasuredCostStore()
    path = str(tmp_path / f"{name}-{kind}.json")
    warm = StitchOptions(
        **{**opts.__dict__, "autotune": True, "measure_repeats": 1,
           "tuning_store_path": path}
    )
    compile_module(graph_fn(), warm)
    with open(path) as f:
        rows = json.load(f)
    assert rows
    for rec in rows.values():
        if kind == "stale_version":
            rec["version"] = MEASURE_SCHEMA_VERSION - 1
        elif kind == "device_mismatch":
            rec["device"] = "0" * 16
    with open(path, "w") as f:
        json.dump(rows, f)
    store = MeasuredCostStore(
        path, device_fp=device_fingerprint(interpret=opts.interpret)
    )
    return store


@pytest.mark.parametrize("planner", ["greedy", "cost"])
def test_cold_start_plans_identical_to_analytic(tmp_path, planner):
    """Empty store, DeviceSpec-fingerprint mismatch, and schema-version bump
    must all degrade to pure analytic planning: on every bench graph, both
    planner modes, the plan is structurally identical to a no-store compile
    and no measurement ever serves (measured_hits == 0)."""
    for name, graph_fn in ALL_GRAPHS.items():
        opts = StitchOptions(max_blocks=64, planner=planner)
        ref = compile_module(graph_fn(), opts)
        ref_shape = _plan_shape(ref)
        for kind in ("empty", "device_mismatch", "stale_version"):
            store = _tampered_store(tmp_path, name, graph_fn, opts, kind)
            comp = compile_module(graph_fn(), opts, measured_store=store)
            assert comp.stats.measured_hits == 0, (name, kind)
            assert comp.stats.measurements_taken == 0, (name, kind)
            assert _plan_shape(comp) == ref_shape, (name, kind)
