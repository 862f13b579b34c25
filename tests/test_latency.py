"""The unified LatencyModel: single device spec, per-op/per-fusion time."""
import jax.numpy as jnp
import pytest

from repro.core import (
    DeviceSpec,
    GraphBuilder,
    LatencyModel,
    deep_fuse,
    trace,
)
from repro.core.latency import TPU_V5E, instr_flops, instr_hbm_bytes
from repro.core.schedule import REPLICATED, ROW, Sched, any_satisfiable


# --------------------------------------------------- single source of truth
def test_perf_library_spec_is_the_latency_spec():
    from repro.core import perf_library

    assert perf_library.TpuSpec is DeviceSpec
    assert perf_library.TPU_V5E is TPU_V5E
    assert perf_library.CostModel is LatencyModel
    lib = perf_library.PerfLibrary()
    assert isinstance(lib.model, LatencyModel)
    assert lib.model.spec is TPU_V5E


def test_tuning_uses_shared_trivial_convention():
    from repro.core import latency, tuning

    assert tuning._is_trivial is latency.is_trivial


# ----------------------------------------------------------- per-op model
def _exp_module(shape=(64, 128)):
    return trace(lambda b, x: b.exp(x), ("x", shape, jnp.float32))


def test_op_time_positive_and_monotone_in_size():
    model = LatencyModel()
    small = _exp_module((8, 128)).instructions[-1]
    big = _exp_module((512, 128)).instructions[-1]
    t_small = model.op_time(small, REPLICATED, 1)
    t_big = model.op_time(big, REPLICATED, 1)
    assert 0 < t_small < t_big


def test_kernel_time_charges_launch_and_grid_steps():
    model = LatencyModel()
    assert model.kernel_time(1, 0.0) == pytest.approx(
        TPU_V5E.launch_overhead_s + TPU_V5E.grid_step_overhead_s
    )
    assert model.kernel_time(64, 0.0) > model.kernel_time(1, 0.0)


def test_kernel_time_exposes_one_blocks_share_of_the_copies():
    model = LatencyModel()
    io = 8 * 1024 * 1024
    for blocks in (1, 4, 32):
        assert model.kernel_time(blocks, 0.0, io) - model.kernel_time(blocks, 0.0) == (
            pytest.approx(io / blocks / TPU_V5E.hbm_bw)
        )


def test_op_time_reads_a_chunked_operand_once_whatever_the_grid():
    """The grid's blocks read a chunked operand once between them, so a
    finer grid moves no fewer bytes."""
    model = LatencyModel()
    instr = _exp_module((512, 1024)).instructions[-1]
    t1, t8, t64 = (
        model.op_time(instr, Sched("chunked", 0, w, ROW), w) for w in (1, 8, 64)
    )
    assert t1 == pytest.approx(t8) == pytest.approx(t64)
    assert model.op_time(instr, REPLICATED, 8) == pytest.approx(8 * t1)


def test_standalone_time_includes_launch_overhead():
    model = LatencyModel()
    instr = _exp_module((8, 128)).instructions[-1]
    assert model.standalone_time(instr) > TPU_V5E.launch_overhead_s
    # parameters/constants never launch
    param = _exp_module((8, 128)).instructions[0]
    assert param.opcode == "parameter"
    assert model.standalone_time(param) == 0.0


def test_flops_and_bytes_helpers():
    m = trace(
        lambda b, x, w: b.dot(x, w),
        ("x", (4, 8), jnp.float32),
        ("w", (8, 16), jnp.float32),
    )
    dot = m.instructions[-1]
    assert instr_flops(dot) == 2.0 * 4 * 16 * 8
    assert instr_hbm_bytes(dot) == (4 * 16 + 4 * 8 + 8 * 16) * 4


# ------------------------------------------------------- per-fusion model
def _chain_fusion():
    m = trace(
        lambda b, x: b.sigmoid(b.exp(x) * 2.0 + 1.0),
        ("x", (16, 128), jnp.float32),
    )
    plan = deep_fuse(m)
    assert len(plan.fusions) == 1
    return plan.fusions[0]


def test_fusion_time_beats_standalone_sum_on_a_chain():
    """Fusing a chain saves launches and intermediate HBM round-trips."""
    model = LatencyModel()
    f = _chain_fusion()
    sol = any_satisfiable(f.members, f.roots)
    assert sol is not None
    fused = model.fusion_time(f.members, f.roots, sol)
    unfused = sum(model.standalone_time(m) for m in f.members)
    assert 0 < fused < unfused


def test_fusion_time_charges_replication_duplication():
    """A replicated member of a multi-block kernel recomputes per block."""
    model = LatencyModel()
    f = _chain_fusion()
    sol = any_satisfiable(f.members, f.roots)
    base = model.fusion_time(f.members, f.roots, sol)
    # force every member replicated under a many-block launch
    import dataclasses

    repl_sol = dataclasses.replace(
        sol,
        blocks=16,
        assignment={k: REPLICATED for k in sol.assignment},
    )
    assert model.fusion_time(f.members, f.roots, repl_sol) > base


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def test_device_spec_by_device_kind():
    from repro.core.latency import DEVICE_SPECS, device_spec

    assert device_spec(_FakeDevice("tpu", "TPU v5 lite")) is TPU_V5E
    assert DEVICE_SPECS["TPU v5 lite"] is TPU_V5E
    # the CPU plans for the v5e by name
    assert device_spec(_FakeDevice("cpu", "cpu")) is TPU_V5E
    assert LatencyModel().spec is TPU_V5E


def test_device_spec_unknown_kind_raises_naming_it():
    from repro.core.latency import device_spec

    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        device_spec(_FakeDevice("tpu", "TPU v9 imaginary"))
