"""Shard-aware compilation: collective IR ops, layout propagation, the
sharded frontend, and the one-multi-device-ExecutionPlan replay.

Runs on the 8 host-platform CPU devices conftest.py forces (the
``--xla_force_host_platform_device_count=8`` flag set before jax init);
every parity check compares the stitched plan bit-for-bit against the
``jax.jit(shard_map(fn))`` oracle.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from graphs import TP_FAMILIES
from repro.core.compiler import StitchOptions, compile_module
from repro.core.ir import GraphBuilder, infer_shape
from repro.core.shard import (
    mesh_axes_of,
    propagate_layouts,
    spec_to_layout,
    wrap_shard_map,
)
from repro.core.signature import fusion_signature

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device host-platform fixture"
)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("model",))


MESH_AXES = (("model", 8),)


# ------------------------------------------------------ collective IR ops
def test_collective_shape_inference():
    assert infer_shape("all_reduce", [(4, 8)], {"axes": ("model",)}) == (4, 8)
    assert infer_shape(
        "all_gather", [(4, 8)], {"axes": ("model",), "dim": 1, "group_size": 8}
    ) == (4, 64)
    assert infer_shape(
        "reduce_scatter",
        [(4, 64)],
        {"axes": ("model",), "dim": 1, "group_size": 8},
    ) == (4, 8)
    with pytest.raises(ValueError, match="divisible"):
        infer_shape(
            "reduce_scatter",
            [(4, 9)],
            {"axes": ("model",), "dim": 1, "group_size": 8},
        )


def test_is_collective_flag():
    b = GraphBuilder("m")
    x = b.parameter("x", (4, 8))
    r = b.all_reduce(x, "model")
    assert r.instr.is_collective and not x.instr.is_collective
    assert not r.instr.is_library_call


# --------------------------------------------------- layout propagation
def _tp_module():
    """Row-parallel dot: x replicated, w k-sharded -> partial -> all_reduce."""
    b = GraphBuilder("tp")
    x = b.parameter("x", (8, 4))
    w = b.parameter("w", (4, 16))
    y = b.dot(x, w)
    r = b.all_reduce(y, "model")
    b.unary("tanh", r)
    return b.module


def test_propagate_layouts_partial_tracking():
    m = _tp_module()
    stats = propagate_layouts(
        m, MESH_AXES, {"x": (None, ("model",)), "w": (("model",), None)}
    )
    by_name = {i.name: i for i in m.instructions}
    dot = next(i for i in m.instructions if i.opcode == "dot")
    ar = next(i for i in m.instructions if i.opcode == "all_reduce")
    tanh = next(i for i in m.instructions if i.opcode == "elementwise")
    # k-sharded contraction: the dot output is a pending partial sum …
    assert dot.attrs["partial"] == ("model",)
    # … the all_reduce clears it, and nothing downstream carries it
    assert "partial" not in ar.attrs and "partial" not in tanh.attrs
    assert stats["collective_ops"] == 1
    assert by_name["x"].attrs["shard"] == (None, ("model",))


def test_propagate_layouts_conflict_raises():
    b = GraphBuilder("c")
    x = b.parameter("x", (8, 8))
    y = b.parameter("y", (8, 8))
    b.binary("add", x, y)
    with pytest.raises(ValueError, match="conflict"):
        propagate_layouts(
            b.module,
            MESH_AXES + (("data", 2),),
            {"x": (("model",), None), "y": (("data",), None)},
        )


def test_propagate_layouts_validates_mesh():
    b = GraphBuilder("v")
    x = b.parameter("x", (8, 8))
    b.all_reduce(x, "nonexistent")
    with pytest.raises(ValueError, match="mesh has axes"):
        propagate_layouts(b.module, MESH_AXES, {})

    b2 = GraphBuilder("v2")
    x2 = b2.parameter("x", (8, 8))
    b2.all_gather(x2, "model", dim=1, group_size=4)  # mesh size is 8
    with pytest.raises(ValueError, match="group_size"):
        propagate_layouts(b2.module, MESH_AXES, {})


# ------------------------------------------- collectives break schedules
def test_collective_is_a_schedule_break():
    m = _tp_module()
    opts = StitchOptions(mesh_axes=MESH_AXES)
    compiled = compile_module(m, opts)
    plan = compiled.executable.plan
    standalone_colls = [s for s in plan.standalone if s.is_collective]
    assert len(standalone_colls) == 1
    # collectives are ICI traffic, never kernels: excluded from every count
    assert plan.num_collectives == 1
    assert all(
        not any(mm.is_collective for mm in f.members) for f in plan.fusions
    )
    assert compiled.stats.collective_calls == 1
    assert compiled.stats.collective_time_s > 0


# ------------------------------------------------- cache never aliases
def test_fusion_signature_salted_by_shard_layout():
    from repro.core.fusion import FusedComputation

    def col_parallel():
        b = GraphBuilder("cp")
        x = b.parameter("x", (8, 4))
        w = b.parameter("w", (4, 16))     # per-shard slice of (4, 128)
        b.unary("tanh", b.dot(x, w))
        return b.module

    m1, m2 = col_parallel(), col_parallel()
    # m2 is the SAME local computation, but as one shard of a column-parallel
    # matmul — the stamped layout must keep its kernels from aliasing m1's
    propagate_layouts(m2, MESH_AXES, {"w": (None, ("model",))})
    tanh1 = next(i for i in m1.instructions if i.opcode == "elementwise")
    tanh2 = next(i for i in m2.instructions if i.opcode == "elementwise")
    assert tanh2.attrs["shard"] == (None, ("model",))
    sig1 = fusion_signature(FusedComputation(members=[tanh1]))
    sig2 = fusion_signature(FusedComputation(members=[tanh2]))
    assert sig1 != sig2


def test_measure_salt_covers_mesh():
    from repro.core.pipeline import _measure_salt

    assert _measure_salt(StitchOptions()) != _measure_salt(
        StitchOptions(mesh_axes=MESH_AXES)
    )


# ------------------------------------------------------ sharded frontend
def test_unlowered_collective_raises_named_error():
    from repro.frontend.jaxpr_lower import (
        UnsupportedPrimitiveError,
        lower_sharded_jaxpr,
    )

    mesh = _mesh()

    def bad(x):
        return jax.lax.ppermute(
            x, "model", [(i, (i + 1) % 8) for i in range(8)]
        )

    closed = jax.make_jaxpr(
        wrap_shard_map(bad, mesh, (P("model"),), P("model"))
    )(jnp.arange(8.0))
    with pytest.raises(UnsupportedPrimitiveError, match="ppermute"):
        lower_sharded_jaxpr(closed)


def test_sharded_capture_requires_single_shard_map():
    from repro.frontend.jaxpr_lower import (
        UnsupportedPrimitiveError,
        lower_sharded_jaxpr,
    )

    closed = jax.make_jaxpr(lambda x: x + 1.0)(jnp.arange(4.0))
    with pytest.raises(UnsupportedPrimitiveError, match="shard_map"):
        lower_sharded_jaxpr(closed)


def _mlp(x, w1, w2):
    h = jax.nn.gelu(x @ w1)
    return jnp.tanh(jax.lax.psum(h @ w2, "model"))


_MLP_SPECS = dict(
    in_specs=(P(), P(None, "model"), P("model", None)), out_specs=P()
)


def _mlp_args(rng):
    return (
        jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
        jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
        jnp.asarray(rng.normal(size=(64, 16)), jnp.float32),
    )


def test_stitch_sharded_bitwise_parity(rng):
    from repro import stitch

    mesh = _mesh()
    sharded = stitch(_mlp, mesh=mesh, **_MLP_SPECS)
    args = _mlp_args(rng)
    out = sharded(*args)
    oracle = jax.jit(
        wrap_shard_map(_mlp, mesh, _MLP_SPECS["in_specs"], _MLP_SPECS["out_specs"])
    )(*args)
    assert jnp.all(out == oracle), "sharded replay must be bit-identical"
    s = sharded.stats
    assert s.replay_mode == "sharded"
    assert s.collective_calls == 1
    assert s.sharded_instrs > 0
    # the Megatron MLP stitches compute on BOTH sides of the all-reduce
    assert s.collective_breaks_spanned >= 1
    # plan cache: second call recompiles nothing and stays bit-identical
    assert jnp.all(sharded(*args) == oracle) and sharded.num_compiles == 1


#: per-device kernels of each tensor-parallel family's sharded plan
TP_KERNELS = {"NMT_TP": 4, "Stacked_TP": 17}


@pytest.mark.parametrize("family", sorted(TP_FAMILIES))
def test_sharded_plan_launches_no_more_than_single_device(rng, family):
    """Sharding never costs launches: the per-device plan commits no more
    kernels than the single-device plan of the same computation, and at
    least one all-reduce keeps stitched kernels on both of its sides."""
    from repro import stitch

    fam = TP_FAMILIES[family]
    opts = StitchOptions(max_blocks=64, **fam["options"])
    specs = fam["specs"]()
    tp_fn = functools.partial(fam["fn"], axis="model")
    args = fam["args"](rng)
    mesh = _mesh()
    single = stitch(fam["fn"], options=opts)
    single(*args)
    sharded = stitch(tp_fn, options=opts, mesh=mesh, **specs)
    out = sharded(*args)
    oracle = jax.jit(
        wrap_shard_map(tp_fn, mesh, specs["in_specs"], specs["out_specs"])
    )(*args)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oracle), rtol=2e-5, atol=2e-5
    )
    ss, st = single.stats, sharded.stats
    perdev = st.stitched_kernels + st.standalone_kernels
    assert perdev == TP_KERNELS[family]
    assert perdev <= ss.stitched_kernels + ss.standalone_kernels
    assert st.collective_breaks_spanned >= 1


def test_stitch_sharded_all_gather_reduce_scatter(rng):
    from repro import stitch

    mesh = _mesh()

    def fn(x):
        g = jax.lax.all_gather(x, "model", axis=0, tiled=True)
        return jax.lax.psum_scatter(
            g * 2.0, "model", scatter_dimension=0, tiled=True
        )

    specs = dict(in_specs=(P("model"),), out_specs=P("model"))
    sharded = stitch(fn, mesh=mesh, **specs)
    x = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    out = sharded(x)
    oracle = jax.jit(wrap_shard_map(fn, mesh, specs["in_specs"], specs["out_specs"]))(x)
    assert jnp.all(out == oracle)
    assert sharded.stats.collective_calls == 2


def test_stitch_mesh_argument_validation():
    from repro import stitch

    with pytest.raises(ValueError, match="in_specs"):
        stitch(_mlp, mesh=_mesh())
    with pytest.raises(ValueError, match="mesh"):
        stitch(_mlp, in_specs=(P(),), out_specs=P())
    with pytest.raises(ValueError, match="donate"):
        stitch(_mlp, mesh=_mesh(), donate_argnums=0, **_MLP_SPECS)


def test_sharded_options_validation():
    with pytest.raises(ValueError, match="mesh_axes"):
        StitchOptions(mesh_axes=(("model", 0),)).validate()
    with pytest.raises(ValueError, match="mesh_axes"):
        StitchOptions(mesh_axes=((1, 8),)).validate()


def test_codegen_refuses_collective_members():
    from types import SimpleNamespace

    from repro.core.codegen import emit_fusion
    from repro.core.fusion import FusedComputation

    b = GraphBuilder("cg")
    x = b.parameter("x", (8,))
    r = b.all_reduce(x, "model")
    f = FusedComputation(members=[r.instr])
    sol = SimpleNamespace(assignment={}, blocks=1)
    with pytest.raises(ValueError, match="collective"):
        emit_fusion(f, sol, plan=None)
