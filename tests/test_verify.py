"""Verifier mutation corpus: every seeded breakage must be rejected with its
expected rule id, and clean compiles must stay diagnostic-free.

Each test compiles a known-good state with ``verify="off"``, corrupts ONE
artifact (module, fusion plan, schedule solution, shard attrs, cache entry,
or execution plan), and asserts the matching family catches it with the
documented rule id — the verifier's contract is *which* invariant broke,
not just that something did.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from graphs import ALL_GRAPHS
from repro.core import (
    CompilationState,
    FusedComputation,
    GraphBuilder,
    KernelCache,
    StitchOptions,
    VerificationError,
    compile_module,
    default_pipeline,
    trace,
    verify_execution_plan,
    verify_module,
)
from repro.core.perf_library import PerfLibrary
from repro.core.verify import (
    RULES,
    resolve_verify_mode,
    verify_fusion_groups,
    verify_planned_entries,
    verify_shard_attrs,
)


def _rmsnorm_module():
    def f(b, x, g):
        ms = b.reduce(b.square(x), (1,), "mean")
        inv = b.rsqrt(ms + 1e-6)
        return x * b.broadcast(inv, x.shape, (0,)) * b.broadcast(g, x.shape, (1,))

    return trace(f, ("x", (8, 32), jnp.float32), ("g", (32,), jnp.float32))


def _compiled_state(module=None, **opt_kwargs):
    opts = StitchOptions(
        max_blocks=opt_kwargs.pop("max_blocks", 32), verify="off", **opt_kwargs
    )
    state = CompilationState(
        module=module if module is not None else _rmsnorm_module(),
        options=opts,
        library=PerfLibrary(),
        kernel_cache=KernelCache(),
    )
    default_pipeline().run(state)
    return state


def _by_opcode(module, opcode):
    return next(i for i in module.instructions if i.opcode == opcode)


def _rules(diags):
    return {d.rule for d in diags}


# ----------------------------------------------------------- IR family
def test_clean_module_has_no_diagnostics():
    assert verify_module(_rmsnorm_module()) == []


def test_ir005_shape_corruption():
    m = _rmsnorm_module()
    _by_opcode(m, "reduce").shape = (7,)
    rules = _rules(verify_module(m))
    assert "IR005" in rules


def test_ir006_dtype_corruption():
    m = _rmsnorm_module()
    # an elementwise op must carry its operand's dtype
    ew = next(i for i in m.instructions if i.opcode == "elementwise")
    ew.dtype = np.dtype(np.int32)
    assert "IR006" in _rules(verify_module(m))


def test_ir003_broken_back_edge():
    m = _rmsnorm_module()
    red = _by_opcode(m, "reduce")
    # drop the producer's user back-edge: operand list says A uses B, but
    # B's users no longer name A
    red.operands[0].users.remove(red)
    assert "IR003" in _rules(verify_module(m))


def test_ir002_storage_order_broken():
    m = _rmsnorm_module()
    instrs = m.instructions
    # move the last instruction (the root) to the front: its operands now
    # sit after it in storage order
    instrs.insert(0, instrs.pop())
    assert "IR002" in _rules(verify_module(m))


def test_ir001_dangling_operand():
    m = _rmsnorm_module()
    red = _by_opcode(m, "reduce")
    m.instructions.remove(red.operands[0])
    assert "IR001" in _rules(verify_module(m))


def test_ir004_duplicate_id():
    m = _rmsnorm_module()
    m.instructions[-1].id = m.instructions[0].id
    assert "IR004" in _rules(verify_module(m))


def test_module_verify_raises_verification_error():
    m = _rmsnorm_module()
    _by_opcode(m, "reduce").shape = (7,)
    with pytest.raises(VerificationError) as exc:
        m.verify()
    assert isinstance(exc.value, ValueError)  # pre-existing caller contract
    assert any(d.rule == "IR005" for d in exc.value.diagnostics)


def test_every_diagnostic_rule_is_documented():
    m = _rmsnorm_module()
    _by_opcode(m, "reduce").shape = (7,)
    for d in verify_module(m):
        assert d.rule in RULES


# --------------------------------------------------------- plan family
def _partition(module, members):
    """One fusion of `members`, everything else standalone (coverage-clean)."""
    member_ids = {m.id for m in members}
    standalone = [
        i
        for i in module.instructions
        if i.opcode != "parameter" and i.id not in member_ids
    ]
    return [FusedComputation(members=list(members), name="bad")], standalone


def test_plan001_cycle_through_outside():
    m = _rmsnorm_module()
    square = _by_opcode(m, "elementwise")  # x*x, feeds the reduce chain
    root = m.roots[0]
    fusions, standalone = _partition(m, [square, root])
    assert "PLAN001" in _rules(verify_fusion_groups(fusions, standalone, m))


def test_plan003_collective_in_kernel_body():
    b = GraphBuilder("coll")
    x = b.parameter("x", (8, 8), jnp.float32)
    y = b.square(x)
    ar = b.all_reduce(y, ("data",))
    b.tanh(ar)
    m = b.module
    ar_instr = _by_opcode(m, "all_reduce")
    members = [y.instr, ar_instr]
    fusions, standalone = _partition(m, members)
    assert "PLAN003" in _rules(verify_fusion_groups(fusions, standalone, m))


def test_plan003_library_call_in_kernel_body():
    b = GraphBuilder("lib")
    x = b.parameter("x", (8, 8), jnp.float32)
    w = b.parameter("w", (8, 8), jnp.float32)
    h = b.dot(b.square(x), w)
    b.tanh(h)
    m = b.module
    dot = _by_opcode(m, "dot")
    fusions, standalone = _partition(m, [dot])
    assert "PLAN003" in _rules(verify_fusion_groups(fusions, standalone, m))


def test_plan004_array_constant_in_kernel_body():
    b = GraphBuilder("const")
    x = b.parameter("x", (8,), jnp.float32)
    c = b.constant(np.ones((8,), np.float32))
    y = x + c
    m = b.module
    fusions, standalone = _partition(m, [c.instr, y.instr])
    assert "PLAN004" in _rules(verify_fusion_groups(fusions, standalone, m))


def test_plan002_component_spans_lc_roof():
    b = GraphBuilder("span")
    x = b.parameter("x", (8, 8), jnp.float32)
    w = b.parameter("w", (8, 8), jnp.float32)
    s = b.square(x)
    h = b.dot(s, w)  # LC layer between s and the root
    b.binary("add", s, b.tanh(h))  # root consumes s directly: skip edge
    m = b.module
    root = m.roots[0]
    fusions, standalone = _partition(m, [s.instr, root])
    assert "PLAN002" in _rules(verify_fusion_groups(fusions, standalone, m))


def test_plan009_coverage_gap_and_duplicate():
    m = _rmsnorm_module()
    red = _by_opcode(m, "reduce")
    covered = [
        i
        for i in m.instructions
        if i.opcode != "parameter" and i.id != red.id
    ]
    # gap: reduce covered 0x
    assert "PLAN009" in _rules(verify_fusion_groups([], covered, m))
    # duplicate: reduce covered 2x
    assert "PLAN009" in _rules(
        verify_fusion_groups([], covered + [red, red], m)
    )


def test_plan005_unsound_solution():
    state = _compiled_state()
    planned = [p for p in state.planned if p.is_representative]
    assert planned, "expected at least one planned fusion"
    p = planned[0]
    sol = p.entry.stitched or p.entry.solution
    assert sol is not None
    if p.entry.stitched is not None:
        assignment = p.entry.stitched.phases[0].solution.assignment
    else:
        assignment = sol.assignment
    assignment.pop(next(iter(assignment)))
    assert "PLAN005" in _rules(verify_planned_entries(state))


def test_plan006_memory_over_budget():
    state = _compiled_state()
    state.options.vmem_limit = 16  # nothing fits in 16 bytes
    assert "PLAN006" in _rules(verify_planned_entries(state))


def test_exec005_stale_signature():
    state = _compiled_state()
    p = next(p for p in state.planned if p.raw_signature is not None)
    p.raw_signature = "0" * len(p.raw_signature)
    assert "EXEC005" in _rules(verify_planned_entries(state))


# -------------------------------------------------------- shard family
_MESH = (("model", 2),)


def _sharded_reduce_module():
    b = GraphBuilder("shard")
    x = b.parameter("x", (4, 8), jnp.float32)
    r = b.reduce(b.square(x), (1,), "sum")  # contracts the sharded dim
    b.tanh(r)
    return b.module


def test_plan007_stale_shard_stamp():
    from repro.core.shard import propagate_layouts

    m = _sharded_reduce_module()
    layouts = {"x": (None, ("model",))}
    propagate_layouts(m, _MESH, layouts)
    # corrupt one stamp: claim dim 0 is sharded where dim 1 is
    sq = _by_opcode(m, "elementwise")
    sq.attrs["shard"] = (("model",), None)
    assert "PLAN007" in _rules(verify_shard_attrs(m, _MESH, layouts))


def test_plan007_layout_conflict():
    b = GraphBuilder("conflict")
    x = b.parameter("x", (8, 8), jnp.float32)
    y = b.parameter("y", (8, 8), jnp.float32)
    b.binary("add", x, y)
    m = b.module
    layouts = {"x": (("model",), None), "y": (None, ("model",))}
    assert "PLAN007" in _rules(verify_shard_attrs(m, _MESH, layouts))


def test_plan008_partial_sum_at_root():
    from repro.core.shard import propagate_layouts

    m = _sharded_reduce_module()
    layouts = {"x": (None, ("model",))}
    propagate_layouts(m, _MESH, layouts)  # honest stamps, no collective
    rules = _rules(verify_shard_attrs(m, _MESH, layouts))
    assert "PLAN008" in rules
    assert "PLAN007" not in rules  # the stamps themselves are consistent


# --------------------------------------------------- ExecutionPlan family
def _stacked_module(n=2):
    def f(b, x, *weights):
        gs, Ws = weights[:n], weights[n:]
        for g, W in zip(gs, Ws, strict=False):
            ms = b.reduce(b.square(x), (1,), "mean")
            inv = b.rsqrt(ms + 1e-6)
            normed = (
                x * b.broadcast(inv, x.shape, (0,)) * b.broadcast(g, x.shape, (1,))
            )
            x = x + b.tanh(b.dot(normed, W))
        return x

    specs = [("x", (8, 32), jnp.float32)]
    specs += [(f"g{i}", (32,), jnp.float32) for i in range(n)]
    specs += [(f"W{i}", (32, 32), jnp.float32) for i in range(n)]
    return trace(f, *specs)


def _execution_plan():
    state = _compiled_state(_stacked_module())
    ep = state.executable.execution_plan
    assert verify_execution_plan(ep) == []  # clean before mutation
    return ep


def test_exec001_read_before_write():
    ep = _execution_plan()
    bogus = max(s for st in ep.steps for s in st.arg_slots) + 100
    ep.steps[0].arg_slots = [bogus] + list(ep.steps[0].arg_slots)[1:]
    assert "EXEC001" in _rules(verify_execution_plan(ep))


def test_exec002_use_after_release():
    ep = _execution_plan()
    # find a slot some later step reads, and release it at the first step
    victim = None
    for k in range(len(ep.steps) - 1, 0, -1):
        reads = set(ep.steps[k].arg_slots)
        if reads:
            victim = next(iter(reads))
            break
    assert victim is not None
    ep.steps[0].release = list(ep.steps[0].release) + [victim]
    assert "EXEC002" in _rules(verify_execution_plan(ep))


def test_exec003_release_of_root_slot():
    ep = _execution_plan()
    root_slot = ep._root_binds[0][1]
    ep.steps[-1].release = list(ep.steps[-1].release) + [root_slot]
    assert "EXEC003" in _rules(verify_execution_plan(ep))


def test_exec004_donated_live_slot():
    from repro.core.executor import _JitSegment

    ep = _execution_plan()
    seg = next(s for s in ep._segments if isinstance(s, _JitSegment))
    live = [
        i for i, s in enumerate(seg.in_slots) if s not in seg.released
    ]
    assert live, "expected an in_slot that stays live"
    seg.donate = list(seg.donate) + [live[0]]
    assert "EXEC004" in _rules(verify_execution_plan(ep))


def test_exec004_donated_protected_slot():
    from repro.core.executor import _JitSegment

    ep = _execution_plan()
    param_slots = {slot for _, slot, _, _ in ep._param_binds}
    protected = param_slots - set(ep.donated_param_slots)
    seg = hit = None
    for s in ep._segments:
        if isinstance(s, _JitSegment):
            for i, sl in enumerate(s.in_slots):
                if sl in protected:
                    seg, hit = s, i
                    break
        if seg is not None:
            break
    assert seg is not None, "expected a segment reading a parameter slot"
    seg.donate = list(seg.donate) + [hit]
    assert "EXEC004" in _rules(verify_execution_plan(ep))


# ------------------------------------------------- modes, stats, overhead
def test_verify_off_leaves_no_trace():
    m = _rmsnorm_module()
    comp = compile_module(m, StitchOptions(max_blocks=32, verify="off"))
    assert "verify" not in comp.stats.pass_times
    assert comp.stats.verify_mode == "off"
    assert comp.stats.verify_boundaries == 0


def test_verify_checkpoint_is_default_single_boundary():
    m = _rmsnorm_module()
    comp = compile_module(m, StitchOptions(max_blocks=32))
    assert comp.stats.verify_mode == "checkpoint"
    assert comp.stats.verify_boundaries == 1
    assert "verify" in comp.stats.pass_times


def test_verify_strict_checks_every_boundary():
    m = _rmsnorm_module()
    comp = compile_module(m, StitchOptions(max_blocks=32, verify="strict"))
    assert comp.stats.verify_mode == "strict"
    assert comp.stats.verify_boundaries == 8  # one per default pass
    assert comp.stats.verify_warnings == 0


def test_env_var_overrides_option(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "strict")
    m = _rmsnorm_module()
    comp = compile_module(m, StitchOptions(max_blocks=32, verify="off"))
    assert comp.stats.verify_mode == "strict"
    assert comp.stats.verify_boundaries == 8


def test_bad_env_value_rejected(monkeypatch):
    monkeypatch.setenv("REPRO_VERIFY", "paranoid")
    with pytest.raises(ValueError, match="REPRO_VERIFY"):
        resolve_verify_mode(StitchOptions())


def test_bad_option_value_rejected():
    with pytest.raises(ValueError, match="verify"):
        compile_module(_rmsnorm_module(), StitchOptions(verify="bogus"))


def test_pipeline_raises_on_seeded_corruption():
    """End-to-end: a pass that corrupts the module fails its own boundary."""
    from repro.core.pipeline import FusionPass

    class CorruptingPass(FusionPass):
        def run(self, state):
            super().run(state)
            red = next(
                i for i in state.module.instructions if i.opcode == "reduce"
            )
            red.shape = (7,)

    from repro.core.pipeline import (
        AutotunePass, CodegenPass, FinalizePass, MemoryPass, PassPipeline,
        SchedulePass, ShardingPass, SubModulePass,
    )

    pipe = PassPipeline([
        SubModulePass(), ShardingPass(), CorruptingPass(), SchedulePass(),
        MemoryPass(), CodegenPass(), AutotunePass(), FinalizePass(),
    ])
    state = CompilationState(
        module=_rmsnorm_module(),
        options=StitchOptions(max_blocks=32, verify="strict"),
        library=PerfLibrary(),
        kernel_cache=KernelCache(),
    )
    with pytest.raises(VerificationError) as exc:
        pipe.run(state)
    assert any(d.rule == "IR005" for d in exc.value.diagnostics)
    assert all(d.pass_name == "fusion" for d in exc.value.diagnostics)


# ----------------------------------------------------- property: clean IR
def _random_graph(rng):
    """Seeded random DAG over GraphBuilder — the non-hypothesis twin of
    ``test_core_property.random_module``."""
    b = GraphBuilder("fuzz")
    shape = [(4, 8), (2, 4, 8), (8,)][rng.randint(3)]
    pool = [
        b.parameter(f"p{i}", shape, jnp.float32)
        for i in range(rng.randint(1, 4))
    ]
    for _ in range(rng.randint(3, 18)):
        kind = rng.randint(4)
        x = pool[rng.randint(len(pool))]
        if kind == 0:
            fn = ["exp", "tanh", "abs", "sigmoid", "square"][rng.randint(5)]
            pool.append(b.unary(fn, x))
        elif kind == 1:
            same = [t for t in pool if t.shape == x.shape]
            y = same[rng.randint(len(same))]
            fn = ["add", "mul", "sub", "max", "min"][rng.randint(5)]
            pool.append(b.binary(fn, x, y))
        elif kind == 2:
            pool.append(x * float(rng.uniform(-2, 2)))
        else:
            if x.ndim < 2:
                continue
            dim = rng.randint(x.ndim)
            r = b.reduce(x, (dim,), ["sum", "max", "mean"][rng.randint(3)])
            kept = tuple(i for i in range(x.ndim) if i != dim)
            pool.append(b.broadcast(r, x.shape, kept) + x)
    if all(t.instr.opcode == "parameter" for t in pool):
        b.exp(pool[0])
    return b.module


@pytest.mark.parametrize("planner", ["cost", "greedy"])
def test_random_graphs_compile_clean_under_strict(planner):
    rng = np.random.RandomState(7)
    for _ in range(8):
        comp = compile_module(
            _random_graph(rng),
            StitchOptions(max_blocks=32, planner=planner, verify="strict"),
        )
        assert comp.stats.verify_boundaries == 8
        assert comp.stats.verify_warnings == 0


#: kernels (stitched + standalone) each benchmark graph compiles to at
#: max_blocks=64 under each planner: a planner or pass change that alters
#: a plan's launches fails here by graph name (update the pin if intended)
GRAPH_KERNELS = {
    "cost": {
        "LR": 6, "W2V": 1, "RNN": 7, "BiRNN": 7, "Speech": 3, "NMT": 1,
        "Stacked": 9, "ReduceTowers": 1, "BcastHeavy": 1, "StitchPipe": 1,
    },
    "greedy": {
        "LR": 6, "W2V": 1, "RNN": 7, "BiRNN": 7, "Speech": 3, "NMT": 1,
        "Stacked": 9, "ReduceTowers": 6, "BcastHeavy": 5, "StitchPipe": 9,
    },
}


@pytest.mark.parametrize("planner", ["cost", "greedy"])
@pytest.mark.parametrize("name", list(ALL_GRAPHS))
def test_benchmark_graph_compiles_clean_under_strict(name, planner):
    comp = compile_module(
        ALL_GRAPHS[name](),
        StitchOptions(max_blocks=64, planner=planner, verify="strict"),
    )
    s = comp.stats
    assert s.verify_boundaries == 8
    assert s.stitched_kernels + s.standalone_kernels == GRAPH_KERNELS[planner][name]


try:  # the hypothesis variant explores the same space adversarially
    from hypothesis import given, settings

    from test_core_property import random_module

    @given(random_module())
    @settings(max_examples=10, deadline=None)
    def test_hypothesis_graphs_compile_clean_under_strict(module):
        for planner in ("cost", "greedy"):
            comp = compile_module(
                module,
                StitchOptions(max_blocks=32, planner=planner, verify="strict"),
            )
            assert comp.stats.verify_boundaries == 8
            assert comp.stats.verify_warnings == 0
except ImportError:  # pragma: no cover — container without hypothesis
    pass
