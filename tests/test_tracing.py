"""``repro.tracing``: the span helper, the spans of the compile and call
paths, and the stable kernel names of stitched programs."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import StitchOptions, stitch
from repro.core.codegen import kernel_name
from repro.tracing import reset, span, totals

CALL_SPANS = {"repro.call", "repro.prepare", "repro.bind", "repro.dispatch"}
SETUP_SPANS = {"repro.trace", "repro.lower", "repro.compile", "repro.replay_build"}


def _mlp(x, w1, w2):
    h = jax.nn.relu(x @ w1 + 1.0)
    h = jax.nn.relu(h @ w2 + 1.0)
    return jax.nn.softmax(h * 0.5, axis=-1)


def _args():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((128, 128)) * 0.05, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((128, 128)) * 0.05, jnp.float32)
    return x, w1, w2


@pytest.fixture
def fresh():
    reset()
    yield
    reset()


def test_nested_spans_and_totals(fresh):
    with span("outer") as outer:
        for _ in range(3):
            with span("inner") as inner:
                pass
    t = totals()
    assert t["outer"][0] == 1 and t["inner"][0] == 3
    assert t["outer"][1] == pytest.approx(outer.seconds)
    assert inner.seconds > 0 and t["inner"][1] <= t["outer"][1]
    reset()
    assert totals() == {}


def test_span_counts_a_raising_block(fresh):
    with pytest.raises(ValueError):
        with span("fails"):
            raise ValueError("inside")
    assert totals()["fails"][0] == 1


def test_compile_time_and_pass_times_are_the_spans(fresh):
    st = stitch(_mlp)
    st(*_args())
    t = totals()
    stats = st.stats
    assert t["repro.compile"] == (1, stats.compile_time_s)
    passes = {k[len("repro.pass."):]: v for k, v in t.items()
              if k.startswith("repro.pass.")}
    assert set(passes) == set(stats.pass_times)
    for name, (count, seconds) in passes.items():
        assert count == 1 and seconds == stats.pass_times[name]


def test_first_call_opens_setup_spans_once(fresh):
    st = stitch(_mlp)
    st(*_args())
    t = totals()
    assert SETUP_SPANS | (CALL_SPANS - {"repro.dispatch"}) <= set(t)
    for name in SETUP_SPANS:
        assert t[name][0] == 1, name
    assert sum(t[n][1] for n in ("repro.trace", "repro.lower", "repro.compile",
                                 "repro.replay_build")) <= t["repro.call"][1]


def test_warm_call_opens_only_call_spans(fresh):
    st = stitch(_mlp)
    args = _args()
    st(*args)
    reset()
    st(*args)
    t = totals()
    assert set(t) == CALL_SPANS
    assert t["repro.call"][0] == t["repro.prepare"][0] == t["repro.bind"][0] == 1
    assert t["repro.dispatch"][0] == st.stats.traced_dispatches_per_call
    inner = t["repro.prepare"][1] + t["repro.bind"][1] + t["repro.dispatch"][1]
    assert inner <= t["repro.call"][1]


def test_fallback_call_opens_no_replay_spans(fresh):
    def f(x):
        return jnp.sort(x)

    st = stitch(f, on_unsupported="fallback")
    st(jnp.ones((8, 128)))
    assert st.num_fallbacks == 1
    t = totals()
    assert {"repro.call", "repro.prepare", "repro.trace"} <= set(t)
    assert not {"repro.bind", "repro.dispatch", "repro.replay_build"} & set(t)


def _one_segment(st, args):
    """The first replay segment of ``st``'s plan and its arguments."""
    ep = st._last.compiled.executable.execution_plan
    seg = ep._segments[0]
    feeds = dict(zip(st._last.lowered.param_names, jax.tree_util.tree_leaves(args),
                     strict=True))
    buf = list(ep._template)
    for (_, slot, _, _), v in zip(ep._param_binds, ep._bind_feeds(feeds), strict=True):
        buf[slot] = v
    return seg, [buf[s] for s in seg.in_slots]


def test_compiled_segment_names_every_kernel_and_op():
    """Each stitched kernel is ``stitch_<8 hex>`` of its fusion signature,
    instances of one kernel share the name, and the compiled segment holds
    every kernel's and standalone op's name."""
    def layers(x, w):
        for _ in range(3):
            x = jax.nn.relu(x @ w + 1.0) * 0.5
        return jnp.tanh(x)

    rng = np.random.default_rng(1)
    args = (jnp.asarray(rng.standard_normal((8, 128)), jnp.float32),
            jnp.asarray(rng.standard_normal((128, 128)) * 0.05, jnp.float32))
    st = stitch(layers, options=StitchOptions(fuse_dot=False))
    st(*args)
    exe = st._last.compiled.executable
    kernels = list(exe.kernels.values())
    assert kernels
    for k in kernels:
        assert re.fullmatch(r"stitch_[0-9a-f]{8}", k.name)
        assert k.name == kernel_name(k.fusion)
    by_fn = {}
    for k in kernels:
        by_fn.setdefault(id(k.fn), set()).add(k.name)
    assert all(len(names) == 1 for names in by_fn.values())
    assert len(by_fn) < len(kernels)          # the first two layers share one
    seg, seg_args = _one_segment(st, args)
    text = seg.fn.lower(*seg_args).compile().as_text()
    assert any(not hasattr(step, "kernel") for step in seg.steps)
    for step in seg.steps:
        assert f"/{step.name}/" in text
        if hasattr(step, "kernel"):
            assert f"/{step.name}/{step.kernel.name}" in text


def test_sharded_replay_spans(fresh):
    """The one multi-device dispatch of a sharded plan: its first call is
    ``repro.replay_build``, later calls ``repro.dispatch``; the global feeds
    are checked and converted in ``repro.bind``."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("model",))

    def fn(x):
        return jax.lax.psum(jnp.tanh(x) * 2.0, "model")

    st = stitch(fn, mesh=mesh, in_specs=(P("model"),), out_specs=P())
    x = jnp.arange(64, dtype=jnp.float32) / 64
    st(x)
    assert totals()["repro.replay_build"][0] == 1
    assert "repro.dispatch" not in totals()
    reset()
    st(x)
    assert set(totals()) == {"repro.call", "repro.prepare", "repro.bind",
                             "repro.dispatch"}
