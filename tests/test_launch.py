"""Launch-layer tests on a 1-device mesh: input specs, cell lowering,
jaxpr cost model, HLO collective census (no 512-device requirement)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.costmodel import fn_cost
from repro.launch.dryrun import cell_is_skipped, input_specs
from repro.launch.hlostats import collective_bytes
from repro.configs import ARCHITECTURES, SHAPES


def test_input_specs_cover_every_cell():
    for arch in ARCHITECTURES:
        for shape in SHAPES:
            specs = input_specs(arch, shape)
            leaves = jax.tree.leaves(specs)
            assert leaves and all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
            if SHAPES[shape]["kind"] == "decode":
                assert specs["tokens"].shape == (SHAPES[shape]["global_batch"],)


def test_long_context_skips_match_design():
    skipped = {
        a for a in ARCHITECTURES if cell_is_skipped(a, "long_500k") is not None
    }
    assert skipped == {
        "llama4-scout-17b-a16e", "granite-moe-3b-a800m", "qwen1.5-0.5b",
        "mistral-large-123b", "granite-20b", "qwen2.5-14b", "qwen2-vl-2b",
        "whisper-base",
    }
    assert cell_is_skipped("mamba2-1.3b", "long_500k") is None
    assert cell_is_skipped("hymba-1.5b", "long_500k") is None


def test_jaxpr_cost_counts_scan_bodies():
    """The raison d'être of the walker: scan body costs multiply by length
    (XLA's cost_analysis counts while bodies once)."""

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    cost = fn_cost(f, x, w)
    dot_flops = 2 * 8 * 16 * 16
    assert cost["dot_flops"] == pytest.approx(7 * dot_flops)


def test_jaxpr_cost_dot_general_exact():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)

    a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    cost = fn_cost(f, a, b)
    assert cost["dot_flops"] == 2 * 4 * 8 * 32 * 16


def test_jaxpr_cost_counts_remat_recompute():
    def g(x):
        return jnp.sum(jnp.tanh(x) ** 2)

    def with_remat(x):
        return jax.grad(lambda y: jax.checkpoint(g)(y))(x)

    def without(x):
        return jax.grad(g)(x)

    x = jax.ShapeDtypeStruct((64,), jnp.float32)
    assert fn_cost(with_remat, x)["flops"] >= fn_cost(without, x)["flops"]


HLO_SAMPLE = """
HloModule test

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = f32[8]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %ag = f32[16]{0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""


def test_collective_census_scales_by_trip_count():
    out = collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 16 * 4                 # once, entry
    assert out["all-reduce"] == 5 * 8 * 4              # 5 loop trips


def test_one_device_cell_lowers_and_compiles():
    """End-to-end build_cell on a 1x1 mesh with a reduced arch — keeps the
    dry-run path under pytest without 512 host devices."""

    from repro.launch import dryrun as dr
    from repro.configs import get_config, reduced_config

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # monkeypatch a tiny cell: reduced config + tiny shape
    cfg = reduced_config(get_config("qwen1.5-0.5b"))
    orig_get, orig_shapes = dr.get_config, dict(dr.SHAPES)
    try:
        dr.get_config = lambda name: cfg  # noqa: E731
        dr.SHAPES["tiny"] = dict(seq_len=16, global_batch=2, kind="train")
        with mesh:
            fn, args, raw = dr.build_cell("qwen1.5-0.5b", "tiny", mesh, 1)
            compiled = fn.lower(*args).compile()
        assert compiled.cost_analysis() is not None
        cost = dr_cost = fn_cost(raw, *args)
        assert cost["flops"] > 0
    finally:
        dr.get_config = orig_get
        dr.SHAPES.clear()
        dr.SHAPES.update(orig_shapes)


@pytest.fixture
def cache_dir_restored():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    from repro.launch.runtime import CHECKOUT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (CHECKOUT / "chip_smoke.py").exists()   # CHECKOUT is the repo root
    assert enable_compile_cache() == path          # fixed: same path every call


def test_compile_cache_follows_env(monkeypatch, cache_dir_restored, tmp_path):
    from repro.launch.runtime import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_device_info_reports_jax_devices():
    from repro.launch.runtime import device_info

    info = device_info()
    assert info == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }


class _Stop(Exception):
    pass


@pytest.mark.parametrize("reduced,d_model", [(False, 1024), (True, 64)])
def test_serve_launcher_reduces_only_when_asked(
    reduced, d_model, capsys, monkeypatch, cache_dir_restored
):
    from repro.launch import serve

    def init_params(cfg, seed=0):
        raise _Stop(cfg)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(serve, "init_params", init_params)
    with pytest.raises(_Stop) as stop:
        serve.main(["--arch", "qwen1.5-0.5b"] + (["--reduced"] if reduced else []))
    assert stop.value.args[0].d_model == d_model
    assert capsys.readouterr().out.splitlines()[0].startswith("[serve] devices: cpu")


def test_serve_launcher_serves_reduced(capsys, monkeypatch, cache_dir_restored):
    from repro.launch import serve

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    serve.main(["--arch", "qwen1.5-0.5b", "--reduced", "--requests", "2",
                "--max-new", "2"])
    assert "[serve] 2/2 done" in capsys.readouterr().out


@pytest.mark.parametrize("reduced,d_model", [(False, 1024), (True, 64)])
def test_train_launcher_reduces_only_when_asked(
    reduced, d_model, capsys, monkeypatch, cache_dir_restored
):
    from repro.launch import train

    def init_params(cfg, seed=0):
        raise _Stop(cfg)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(train, "init_params", init_params)
    with pytest.raises(_Stop) as stop:
        train.main(["--arch", "qwen1.5-0.5b"] + (["--reduced"] if reduced else []))
    assert stop.value.args[0].d_model == d_model
    assert capsys.readouterr().out.splitlines()[0].startswith("[train] devices: cpu")
