"""The stitched call path binds a call's ``jax.Array`` arguments as they are.

The plan key reads a ``jax.Array``'s own shape and dtype, and the bind hands
an array of the parameter's dtype and shape on unconverted, on one device
and through ``stitch(mesh=...)`` (the 8 host-platform CPU devices that
conftest.py forces).  Numpy, Python and wrongly typed feeds are still
converted; ``feeds_passed`` / ``feeds_converted`` count the two.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import stitch
from repro.core import executor
from repro.core.shard import wrap_shard_map
from repro.frontend import api


def _f(x, y, s):
    return jnp.tanh(x * s + y), jnp.sum(x, axis=-1)


def _mlp(x, w1, w2):
    return jnp.tanh(jax.lax.psum(jax.nn.gelu(x @ w1) @ w2, "model"))


_MLP_SPECS = dict(
    in_specs=(P(), P(None, "model"), P("model", None)), out_specs=P()
)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("model",))


def _f_args(rng):
    return (
        jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
        jnp.asarray(rng.normal(size=(16, 128)), jnp.float32),
        jnp.float32(0.5) + jnp.zeros((), jnp.float32),   # not weakly typed
    )


def _mlp_args(rng):
    return (
        jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
        jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
        jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
    )


def _case(path, rng):
    """(stitched function, jax.Array arguments, jax.jit oracle)."""
    if path == "single":
        return stitch(_f), _f_args(rng), jax.jit(_f)
    mesh = _mesh()
    oracle = jax.jit(wrap_shard_map(
        _mlp, mesh, _MLP_SPECS["in_specs"], _MLP_SPECS["out_specs"]))
    return stitch(_mlp, mesh=mesh, **_MLP_SPECS), _mlp_args(rng), oracle


class _CountingJnp:
    """``jax.numpy`` with counted ``asarray`` and ``result_type``."""

    def __init__(self):
        self.calls = collections.Counter()

    def __getattr__(self, name):
        fn = getattr(jnp, name)
        if name not in ("asarray", "result_type"):
            return fn

        def counted(*args, **kwargs):
            self.calls[name] += 1
            return fn(*args, **kwargs)

        return counted


def _equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _replay_args(st, monkeypatch):
    """Record what the compiled replay is called with: a list that gets
    one entry (the replay's positional arguments) per dispatch."""
    ex = st._last.compiled.executable
    seen = []

    def recorder(fn):
        def call(*args):
            seen.append(args)
            return fn(*args)
        return call

    if ex.mesh is not None:
        monkeypatch.setattr(ex, "_sharded_fn", recorder(ex._sharded_fn))
    else:
        for seg in ex.execution_plan._segments:
            monkeypatch.setattr(seg, "fn", recorder(seg.fn))
    return seen


@pytest.mark.parametrize("path", ["single", "mesh"])
def test_array_call_runs_no_asarray_and_no_result_type(path, rng, monkeypatch):
    st, args, oracle = _case(path, rng)
    want = st(*args)                     # compiles and builds the replay
    counting = _CountingJnp()
    monkeypatch.setattr(api, "jnp", counting)
    monkeypatch.setattr(executor, "jnp", counting)
    out = st(*args)
    assert counting.calls == {}
    _equal(out, want)
    _equal(out, oracle(*args))
    # a numpy argument takes the converting path through the same patch
    st(np.asarray(args[0]), *args[1:])
    assert counting.calls["asarray"] == 1
    assert counting.calls["result_type"] == 1


@pytest.mark.parametrize("path", ["single", "mesh"])
def test_replay_is_handed_the_callers_arrays(path, rng, monkeypatch):
    st, args, _ = _case(path, rng)
    st(*args)
    seen = _replay_args(st, monkeypatch)
    st(*args)
    ex = st._last.compiled.executable
    ep = ex.execution_plan
    if ex.mesh is not None:
        (handed,) = seen
    else:
        slot_leaf = {
            slot: i for i, (_, slot, _, _) in enumerate(ep._param_binds)
        }
        handed = [None] * len(args)
        for seg, seg_args in zip(ep._segments, seen, strict=True):
            for slot, v in zip(seg.in_slots, seg_args, strict=True):
                if slot in slot_leaf:
                    handed[slot_leaf[slot]] = v
    assert len(handed) == len(args)
    assert all(h is a for h, a in zip(handed, args, strict=True))


def test_numpy_python_and_wrong_dtype_feeds_are_converted(rng):
    x, y, _ = _f_args(rng)
    st = stitch(_f)
    calls = [
        (np.asarray(x), y, 0.5),                          # numpy, Python float
        (np.asarray(x, np.float64), np.asarray(y), 0.5),  # float64 numpy
        (x, y, jnp.asarray(0.5)),                         # weakly typed array
    ]
    for args in calls:
        before = st._last.compiled.stats.feeds_converted if st._last else 0
        _equal(st(*args), jax.jit(_f)(*args))
        assert st._last.compiled.stats.feeds_converted > before
    assert st.num_compiles == 1


def test_direct_executable_feeds_of_another_dtype_are_converted(rng):
    x, y, s = _f_args(rng)
    st = stitch(_f)
    st(x, y, s)
    ex = st._last.compiled.executable
    names = st._last.lowered.param_names
    xi = jnp.asarray(np.round(np.asarray(x) * 4), jnp.int32)
    xf = xi.astype(jnp.float32)
    for run in (ex.jit_execute, ex.execute_eager):
        got = run(dict(zip(names, (xi, y, s), strict=True)))
        want = run(dict(zip(names, (xf, y, s), strict=True)))
        _equal([got[k] for k in sorted(got)], [want[k] for k in sorted(want)])
    _equal(st(xf, y, s), jax.jit(_f)(xf, y, s))


def test_numpy_and_array_calls_share_one_plan(rng):
    x, y, s = _f_args(rng)
    st = stitch(_f)
    _equal(st(np.asarray(x), np.asarray(y), np.asarray(s)), st(x, y, s))
    assert st.num_compiles == 1
    assert len(st._plans) == 1


@pytest.mark.parametrize("path", ["single", "mesh"])
def test_missing_and_misshapen_feeds_still_raise(path, rng):
    st, args, _ = _case(path, rng)
    st(*args)
    ex = st._last.compiled.executable
    names = st._last.lowered.param_names
    feeds = dict(zip(names, args, strict=True))
    runners = (ex.jit_execute, ex.execute_eager)
    for run in runners:
        with pytest.raises(KeyError, match=f"missing feed for parameter {names[0]}"):
            run({k: v for k, v in feeds.items() if k != names[0]})
        bad = dict(feeds)
        bad[names[0]] = jnp.zeros((3, 3), jnp.float32)   # right dtype, wrong shape
        with pytest.raises(ValueError, match="feed shape"):
            run(bad)


@pytest.mark.parametrize("path", ["single", "mesh"])
def test_feed_counters(path, rng):
    st, args, _ = _case(path, rng)
    n = len(args)
    st(*args)
    st(*args)
    ex = st._last.compiled.executable
    for stats in (st.stats, ex.launch_stats()):
        assert (stats.feeds_passed, stats.feeds_converted) == (2 * n, 0)
    st(np.asarray(args[0]), *args[1:])
    for stats in (st.stats, ex.launch_stats()):
        assert (stats.feeds_passed, stats.feeds_converted) == (3 * n - 1, 1)
    # a launch_stats() snapshot holds still; CompileStats reads live
    snap = ex.launch_stats()
    st(*args)
    assert snap.feeds_passed == 3 * n - 1
    assert st.stats.feeds_passed == 4 * n - 1
