"""The per-layer metrics that read the program's own spans: ``host_ms.stitch``
from a profiler trace, ``capture_s.stitch`` and ``replay_build_s.stitch``
from ``repro.tracing.totals()``; and the spans as a CPU profile holds them."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, program_spans  # noqa: E402

from repro import stitch  # noqa: E402
from repro.tracing import reset, totals  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "stitch.qwen1.5-0.5b.decode.spans.json")
NO_SPANS = os.path.join(DATA, "stitch.qwen1.5-0.5b.decode.json")   # recorded without them


def reader(name):
    return harness._module(os.path.join(ROOT, "bench", "metrics", f"{name}.py"))


def _program(x, w):
    return jax.nn.softmax(jax.nn.relu(x @ w + 1.0) * 0.5, axis=-1)


def _args():
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.standard_normal((8, 128)), jnp.float32),
            jnp.asarray(rng.standard_normal((128, 128)) * 0.05, jnp.float32))


def _children(spans, parent, name):
    return [s for s in spans if s[0] == name and parent[1] <= s[1] and s[2] <= parent[2]]


def test_profile_holds_call_spans_inside_the_benchmark_span(tmp_path):
    """Under a CPU profile, each ``stitch_call`` holds one ``repro.call``,
    which holds one ``repro.prepare``, one ``repro.bind`` and one
    ``repro.dispatch`` per replay segment, on a ``/host:`` plane."""
    st = stitch(_program)
    args = _args()
    jax.block_until_ready(st(*args))
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("stitch_call"):
            jax.block_until_ready(st(*args))
    jax.profiler.stop_trace()
    ev = program_spans.events(str(tmp_path))
    assert program_spans.events(str(tmp_path)) is ev
    spans = ev["spans"]
    outer = [s for s in spans if s[0] == "stitch_call"]
    assert len(outer) == 3
    for s in outer:
        (call,) = _children(spans, s, "repro.call")
        assert len(_children(spans, call, "repro.prepare")) == 1
        assert len(_children(spans, call, "repro.bind")) == 1
        assert (len(_children(spans, call, "repro.dispatch"))
                == st.stats.traced_dispatches_per_call)
        assert not _children(spans, s, "repro.replay_build")
    calls = program_spans.inside(spans, "stitch_call", "repro.call")
    want = sum(b - a for _, a, b in calls) / 3 * 1e-6
    assert reader("host_ms.stitch").mean_ms(ev) == pytest.approx(want)


def test_host_ms_on_recorded_chip_slice():
    """A slice of a traced run on a TPU v5e: the reader gives the mean
    ``repro.call`` length inside ``stitch_call`` computed by hand, and each
    call has the expected span structure."""
    with open(RECORDED) as f:
        rec = json.load(f)
    spans = sorted(rec["events"]["spans"] + rec["program_spans"], key=lambda s: s[1])
    ev = {"device": rec["events"]["device"], "spans": spans}
    # the three repro.call spans last 2,399,820, 2,455,710 and 2,361,490 ns
    by_hand = (2_399_820 + 2_455_710 + 2_361_490) / 3 / 1e6
    assert rec["expected"]["host_ms"] == pytest.approx(by_hand, rel=1e-12)
    assert reader("host_ms.stitch").mean_ms(ev) == pytest.approx(by_hand, rel=1e-12)
    for s in (s for s in spans if s[0] == "stitch_call"):
        (call,) = _children(spans, s, "repro.call")
        for name in ("repro.prepare", "repro.bind", "repro.dispatch"):
            assert len(_children(spans, call, name)) == 1, name


def test_host_ms_is_none_without_program_spans(tmp_path):
    with open(NO_SPANS) as f:
        rec = json.load(f)
    r = reader("host_ms.stitch")
    assert r.mean_ms(rec["events"]) is None
    assert r.read({}) is None
    assert program_spans.events(str(tmp_path)) is None


def test_setup_readers_sum_totals():
    reset()
    try:
        st = stitch(_program)
        jax.block_until_ready(st(*_args()))
        t = totals()
        assert reader("capture_s.stitch").read({}) == pytest.approx(
            t["repro.trace"][1] + t["repro.lower"][1])
        assert reader("replay_build_s.stitch").read({}) == t["repro.replay_build"][1]
        reset()
        assert reader("capture_s.stitch").read({}) is None
        assert reader("replay_build_s.stitch").read({}) is None
    finally:
        reset()


def test_setup_readers_without_the_program(monkeypatch):
    """Against a program without ``repro.tracing`` the readers give None
    and do not raise."""
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader("capture_s.stitch").read({}) is None
    assert reader("replay_build_s.stitch").read({}) is None
