"""Traced ExecutionPlan replay: jit/eager oracle parity, dispatch
accounting, buffer-release correctness, and feed validation."""
import numpy as np
import pytest

from conftest import make_feeds as _feeds
from graphs import ALL_GRAPHS
from repro.core import GraphBuilder, StitchOptions, compile_module, trace

OPTS = StitchOptions(max_blocks=64)


# ------------------------------------------------------- oracle parity
@pytest.mark.parametrize("name", sorted(ALL_GRAPHS))
def test_jit_replay_bit_identical_to_eager(name, rng):
    """The acceptance bar: traced replay == eager loop, bit for bit, on
    every benchmark graph (segment boundaries at layout-hazardous library
    calls + optimization barriers make this hold by construction)."""
    module = ALL_GRAPHS[name]()
    comp = compile_module(module, OPTS)
    feeds = _feeds(module, rng)
    eager = comp.executable.execute_eager(feeds)
    traced = comp.executable.jit_execute(feeds)
    traced2 = comp.executable.jit_execute(feeds)   # steady-state call
    assert set(eager) == set(traced)
    for k in eager:
        e = np.asarray(eager[k])
        assert np.array_equal(e, np.asarray(traced[k]), equal_nan=True), (
            f"{name}/{k}: traced replay diverged from the eager oracle"
        )
        assert np.array_equal(e, np.asarray(traced2[k]), equal_nan=True), (
            f"{name}/{k}: second traced call diverged (donation reuse?)"
        )


def test_dispatch_accounting_and_reduction():
    """Traced replay must never dispatch more than eager, and graphs that
    fuse to one kernel must replay as ONE dispatch."""
    for name, fn in ALL_GRAPHS.items():
        comp = compile_module(fn(), OPTS)
        s = comp.stats
        assert 1 <= s.traced_dispatches_per_call <= max(
            1, s.eager_dispatches_per_call
        )
        assert s.replay_dispatch_reduction >= 0
        if s.eager_dispatches_per_call == 1:
            assert s.traced_dispatches_per_call == 1
    # the multi-step graphs are where the launch win lives
    comp = compile_module(ALL_GRAPHS["BiRNN"](), OPTS)
    s = comp.stats
    assert s.traced_dispatches_per_call < s.eager_dispatches_per_call


def test_default_call_routes_through_traced_replay(rng):
    module = ALL_GRAPHS["Stacked"]()
    comp = compile_module(module, OPTS)
    assert comp.stats.replay_mode == "jit"
    comp(_feeds(module, rng))
    st = comp.executable.launch_stats()
    assert st.traced_calls == 1 and st.eager_calls == 0
    assert st.jit_traces >= 1


def test_jit_replay_disabled_keeps_eager_loop(rng):
    module = ALL_GRAPHS["Stacked"]()
    comp = compile_module(
        module, StitchOptions(max_blocks=64, jit_replay=False)
    )
    assert comp.stats.replay_mode == "eager"
    comp(_feeds(module, rng))
    st = comp.executable.launch_stats()
    assert st.eager_calls == 1 and st.traced_calls == 0
    assert st.jit_traces == 0


def test_steady_state_traces_once(rng):
    """Retracing on every call would re-pay compilation: segment traces
    must not grow after the first call."""
    module = ALL_GRAPHS["RNN"]()
    comp = compile_module(module, OPTS)
    feeds = _feeds(module, rng)
    comp(feeds)
    first = comp.executable.launch_stats().jit_traces
    comp(feeds)
    comp(feeds)
    assert comp.executable.launch_stats().jit_traces == first


def test_donation_covers_only_runtime_owned_intermediates():
    """Dead-after-segment intermediates are donated; parameter and
    folded-constant buffers never are (the caller / the template still
    holds them — donating one would invalidate it for the next call)."""
    comp = compile_module(ALL_GRAPHS["Stacked"](), OPTS)
    assert comp.stats.donated_buffers > 0
    ep = comp.executable.execution_plan
    template_slots = {
        s for s, v in enumerate(ep._template) if v is not None
    }
    param_slots = {slot for _, slot, _, _ in ep._param_binds}
    for seg in ep._segments:
        for i in seg.donate:
            slot = seg.in_slots[i]
            assert slot in seg.released, "donated input must be dead after"
            assert slot not in template_slots
            assert slot not in param_slots


def test_repeated_calls_with_jax_array_feeds(rng):
    """Steady-state serving pattern: device-resident feeds reused across
    calls must survive donation (regression: donated param buffers used to
    be deleted out from under the caller)."""
    import jax.numpy as jnp

    module = ALL_GRAPHS["Stacked"]()
    comp = compile_module(module, OPTS)
    feeds = {k: jnp.asarray(v) for k, v in _feeds(module, rng).items()}
    out1 = comp(feeds)
    out2 = comp(feeds)                 # same jax arrays, second call
    for k in out1:
        assert np.array_equal(np.asarray(out1[k]), np.asarray(out2[k]))


# ----------------------------------------------------- release behavior
def _leaked_slots(ep):
    root_slots = {s for _, s in ep._root_binds}
    released = [s for step in ep.steps for s in step.release]
    assert len(released) == len(set(released)), "slot released twice"
    written = set()
    for step in ep.steps:
        written.update(
            step.out_slots if hasattr(step, "out_slots") else [step.out_slot]
        )
    return written - set(released) - root_slots


def test_no_leaked_slots_on_benchmark_graphs():
    """Every slot a step writes is either a module root or released at
    some step — nothing may sit in the buffer table for the whole run."""
    for name, fn in ALL_GRAPHS.items():
        comp = compile_module(fn(), OPTS)
        leaked = _leaked_slots(comp.executable.execution_plan)
        assert not leaked, f"{name}: slots never released: {leaked}"


class _FakeKernel:
    """Stand-in for a deduped/packed StitchedKernel whose output list is a
    superset of what this instance's consumers read."""

    def __init__(self, inputs, outputs, fn):
        self.inputs = inputs
        self.outputs = outputs
        self._fn = fn

    def __call__(self, *args):
        return self._fn(*args)


def test_dead_kernel_output_released_at_producing_step(rng):
    """Buffer-leak regression (ISSUE satellite): a multi-output kernel
    slot with no reader is never in ``last_read``; it must be released at
    the step that produces it, not held for the whole run."""
    import jax.numpy as jnp

    from repro.core.executor import ExecutionPlan, _KernelStep
    from repro.core.fusion import FusedComputation, FusionPlan

    b = GraphBuilder("dead_out")
    x = b.parameter("x", (8, 8), np.float32)
    a = b.tanh(x)
    e = b.exp(a)
    g = e + a                      # the only sink
    module = b.module
    f1 = FusedComputation([a.instr, e.instr], name="k1")
    f2 = FusedComputation([g.instr], name="k2")
    kernels = {
        # k1 emits BOTH values; k2 recomputes exp(a) internally (as a
        # packed/replicated kernel would) so e's slot has no reader
        "k1": _FakeKernel(
            [x.instr], [a.instr, e.instr],
            lambda xv: (jnp.tanh(xv), jnp.exp(jnp.tanh(xv))),
        ),
        "k2": _FakeKernel(
            [a.instr], [g.instr], lambda av: (jnp.exp(av) + av,)
        ),
    }
    plan = FusionPlan([f1, f2], [], module)
    ep = ExecutionPlan(module, plan, kernels)

    e_slot = next(
        s
        for step in ep.steps
        if type(step) is _KernelStep and len(step.out_slots) == 2
        for s in step.out_slots[1:]
    )
    producer = next(
        step
        for step in ep.steps
        if type(step) is _KernelStep and e_slot in step.out_slots
    )
    assert e_slot in producer.release, (
        "dead multi-output kernel slot must be freed at its producing step"
    )
    assert not _leaked_slots(ep)
    # the plan still computes the module, and both replay modes agree
    feeds = {"x": rng.randn(8, 8).astype(np.float32)}
    ref = np.exp(np.tanh(feeds["x"])) + np.tanh(feeds["x"])
    eager = ep.execute(feeds)
    traced = ep.jit_execute(feeds)
    (key,) = eager.keys()
    np.testing.assert_allclose(
        np.asarray(eager[key]), ref, rtol=1e-5, atol=1e-6
    )
    assert np.array_equal(np.asarray(eager[key]), np.asarray(traced[key]))


def test_eager_release_drops_buffers(rng):
    """The eager loop must end with only root slots populated (observed
    through a probe subclass of list used as the buffer table)."""
    module = ALL_GRAPHS["Stacked"]()
    comp = compile_module(module, OPTS)
    ep = comp.executable.execution_plan
    feeds = _feeds(module, rng)
    ep.execute(feeds)  # warm
    # replicate execute() with a final-buffer snapshot
    buf = list(ep._template)
    for (name, slot, dtype, shape), v in zip(
        ep._param_binds, ep._bind_feeds(feeds)
    , strict=False):
        buf[slot] = v
    from repro.core.executor import _KernelStep
    from repro.core.ir import apply_op

    for step in ep.steps:
        if type(step) is _KernelStep:
            outs = step.kernel(*[buf[s] for s in step.arg_slots])
            for s, o in zip(step.out_slots, outs, strict=False):
                buf[s] = o
        else:
            buf[step.out_slot] = apply_op(
                step.instr, *[buf[s] for s in step.arg_slots]
            )
        for s in step.release:
            buf[s] = None
    root_slots = {s for _, s in ep._root_binds}
    template_slots = {s for s, v in enumerate(ep._template) if v is not None}
    live = {s for s, v in enumerate(buf) if v is not None}
    assert live <= root_slots | template_slots, (
        f"non-root buffers still live after the run: "
        f"{live - root_slots - template_slots}"
    )


# ------------------------------------------------------ feed validation
def test_missing_feed_raises_named_error(rng):
    """execute()/jit_execute() name the missing parameter like
    reference_execute does — not a bare KeyError from a dict lookup."""
    module = ALL_GRAPHS["LR"]()
    comp = compile_module(module, OPTS)
    feeds = _feeds(module, rng)
    missing = sorted(feeds)[0]
    del feeds[missing]
    for runner in (comp.executable.execute_eager, comp.executable.jit_execute):
        with pytest.raises(KeyError, match=f"missing feed for parameter {missing}"):
            runner(feeds)


def test_bad_feed_shape_raises(rng):
    module = ALL_GRAPHS["LR"]()
    comp = compile_module(module, OPTS)
    feeds = _feeds(module, rng)
    name = sorted(feeds)[0]
    feeds[name] = np.zeros((3, 3), np.float32)
    with pytest.raises(ValueError, match="feed shape"):
        comp.executable.jit_execute(feeds)


def test_multi_root_builder_graph_parity(rng):
    """Hand-built two-sink module (not from the benchmark set): both
    replay modes agree with each other bit-for-bit."""
    def f(b, x, y):
        s = b.tanh(x + y)
        t = b.reduce(s, (1,), "sum")
        u = b.exp(b.broadcast(t, (16, 16), (0,)) - s)
        return s * 2.0, u          # two sinks -> two module roots

    module = trace(
        f, ("x", (16, 16), np.float32), ("y", (16, 16), np.float32)
    )
    comp = compile_module(module, OPTS)
    feeds = _feeds(module, rng)
    eager = comp.executable.execute_eager(feeds)
    traced = comp.executable.jit_execute(feeds)
    assert len(eager) >= 2
    for k in eager:
        assert np.array_equal(np.asarray(eager[k]), np.asarray(traced[k]))


@pytest.mark.parametrize("jit_replay", [True, False], ids=["traced", "eager"])
def test_standalone_transpose_folds_into_library_dot(jit_replay, rng):
    """dot(x^T, w) and dot(g, w^T) read x and w directly: no transpose step
    runs, and the result is XLA's own transposed-operand dot, bit for bit."""
    import jax
    import jax.numpy as jnp

    def f(b, x, w, g):
        xw = b.dot(b.transpose(x, (1, 0)), w, fusable=False)      # (16, 4)
        gw = b.dot(g, b.transpose(w, (1, 0)), fusable=False)      # (2, 8)
        return xw, gw

    m = trace(f, ("x", (8, 16), jnp.float32), ("w", (8, 4), jnp.float32),
              ("g", (2, 4), jnp.float32))
    comp = compile_module(m, StitchOptions(jit_replay=jit_replay))
    ep = comp.executable.execution_plan
    assert not any(
        getattr(s, "instr", None) is not None and s.instr.opcode == "transpose"
        for s in ep.steps
    )
    feeds = {k: rng.randn(*s).astype("f4")
             for k, s in (("x", (8, 16)), ("w", (8, 4)), ("g", (2, 4)))}
    out = comp(feeds)
    want = jax.jit(lambda x, w, g: (
        jax.lax.dot_general(x, w, (((0,), (0,)), ((), ()))),
        jax.lax.dot_general(g, w, (((1,), (1,)), ((), ()))),
    ))(feeds["x"], feeds["w"], feeds["g"])
    for r, w_ in zip(m.roots, want, strict=True):
        assert np.array_equal(np.asarray(out[r.name]), np.asarray(w_))
