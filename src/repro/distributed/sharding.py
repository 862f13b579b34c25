"""Sharding rules: FSDP(+pod) x TP over the production mesh.

Mesh axes: (``pod``,) ``data``, ``model``.
  * params/optimizer state: the largest shardable dim goes to the fsdp axes
    (pod+data, ZeRO-3 style), a second dim to ``model`` (TP) — divisibility
    checked per-dim with graceful fallback to replication;
  * MoE expert stacks shard the expert dim over ``model`` when divisible
    (expert parallelism), else the ffn dim;
  * activations/batch shard over (pod, data) when the batch divides, else
    over ``data`` alone, else replicate (the long_500k gb=1 cells);
  * vocab-parallel logits: last dim of logits on ``model``.

Everything returns NamedSharding against the passed mesh so the same rules
serve the 16x16 single-pod and 2x16x16 multi-pod dry runs.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _divisible(dim: int, mesh: Mesh, axes) -> bool:
    return dim % axis_size(mesh, axes) == 0


def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               stacked: bool = False) -> P:
    """Sharding spec for one parameter.  ``stacked`` marks a leading
    layer-stack dim (from scan-over-layers) that stays unsharded."""
    fsdp = fsdp_axes(mesh)
    dims: list = [None] * len(shape)
    body = list(range(1, len(shape))) if stacked else list(range(len(shape)))
    if not body:
        return P(*dims)
    # vocab-parallel embedding/unembed: the vocab dim goes to 'model' so the
    # logits come out vocab-sharded (Megatron-style); d to fsdp.
    if ("embed/tok" in path or "embed/unembed" in path) and len(body) == 2:
        a, b = body
        vdim, ddim = (a, b) if shape[a] >= shape[b] else (b, a)
        if _divisible(shape[vdim], mesh, "model"):
            dims[vdim] = "model"
        if _divisible(shape[ddim], mesh, fsdp):
            dims[ddim] = fsdp
        return P(*dims)
    # MoE expert stacks: (L?, E, d, f) — expert dim to model if divisible
    is_expert = "wi" in path or "wg" in path or "wo" in path
    if len(body) == 3 and is_expert:
        e, d, f = body
        if _divisible(shape[e], mesh, "model"):
            dims[e] = "model"
            if _divisible(shape[d], mesh, fsdp):
                dims[d] = fsdp
        else:
            if _divisible(shape[f], mesh, "model"):
                dims[f] = "model"
            if _divisible(shape[d], mesh, fsdp):
                dims[d] = fsdp
        return P(*dims)
    if len(body) >= 2:
        a, b = body[-2], body[-1]
        # 2-D weight (d_in, d_out): fsdp on the bigger dim, model on the other
        big, small = (a, b) if shape[a] >= shape[b] else (b, a)
        if _divisible(shape[big], mesh, fsdp):
            dims[big] = fsdp
        if _divisible(shape[small], mesh, "model"):
            dims[small] = "model"
        elif dims[big] is None and _divisible(shape[small], mesh, fsdp):
            dims[small] = fsdp
        return P(*dims)
    # 1-D params (norm gains, biases): shard over model when large+divisible
    d = body[0]
    if shape[d] >= 4096 and _divisible(shape[d], mesh, "model"):
        dims[d] = "model"
    return P(*dims)


def param_layout(path: str, shape: Tuple[int, ...], mesh: Mesh,
                 stacked: bool = False):
    """The ``core.shard`` layout tuple for one parameter — the same
    placement ``param_spec`` names, in the form ``compile_module(...,
    param_layouts=)`` and the ShardingPass consume (one entry per dim:
    ``None`` or a tuple of mesh axis names)."""
    from ..core.shard import spec_to_layout

    return spec_to_layout(
        param_spec(path, shape, mesh, stacked=stacked), len(shape)
    )


def params_shardings(param_tree, mesh: Mesh, stacked_keys=("layers", "enc_layers")):
    """NamedSharding pytree matching ``param_tree`` (arrays or SDS)."""

    def walk(path, node, stacked):
        if isinstance(node, dict):
            return {
                k: walk(f"{path}/{k}", v, stacked or k in stacked_keys)
                for k, v in node.items()
            }
        if isinstance(node, (tuple, list)):
            vals = [walk(f"{path}/{i}", v, stacked) for i, v in enumerate(node)]
            return type(node)(vals) if not hasattr(node, "_fields") else type(node)(*vals)
        spec = param_spec(path, tuple(node.shape), mesh, stacked=stacked)
        return NamedSharding(mesh, spec)

    return walk("", param_tree, False)


def batch_axes(mesh: Mesh, global_batch: int):
    """Largest prefix of (pod, data) that divides the batch."""
    cands = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen: list = []
    for a in cands:
        if global_batch % axis_size(mesh, tuple(chosen + [a])) == 0:
            chosen.append(a)
    return tuple(chosen)


def batch_spec(mesh: Mesh, global_batch: int, rank: int) -> P:
    axes = batch_axes(mesh, global_batch)
    dims: list = [None] * rank
    if axes:
        dims[0] = axes if len(axes) > 1 else axes[0]
    return P(*dims)


def batch_shardings(batch_tree, mesh: Mesh, global_batch: int):
    def one(x):
        return NamedSharding(mesh, batch_spec(mesh, global_batch, len(x.shape)))

    return jax.tree.map(one, batch_tree)


def cache_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               global_batch: int) -> P:
    """KV/SSM cache sharding: (L, B, S|state...) — batch over (pod,data)
    when divisible; KV heads over 'model' when they divide it, else the
    HEAD DIM (always a multiple of 16) — the sequence dim stays unsharded
    so the one-token dynamic_update_slice write never reshards (GSPMD's
    "involuntary full rematerialization" of seq-sharded cache updates would
    replicate the whole cache).  SSM state heads over 'model'."""
    dims: list = [None] * len(shape)
    baxes = batch_axes(mesh, global_batch)
    if len(shape) >= 2 and baxes:
        dims[1] = baxes if len(baxes) > 1 else baxes[0]
    leaf = path.split("/")[-1]
    if leaf in ("k_scale", "v_scale") and len(shape) == 4:
        # (L, B, W, Hkv) int8-cache scale planes: batch + heads when divisible
        if _divisible(shape[3], mesh, "model") and shape[3] >= axis_size(mesh, "model"):
            dims[3] = "model"
        return P(*dims)
    if leaf in ("k", "v", "xk", "xv") and len(shape) == 5:
        # (L, B, S, Hkv, hd)
        if _divisible(shape[3], mesh, "model") and shape[3] >= axis_size(mesh, "model"):
            dims[3] = "model"
        elif _divisible(shape[4], mesh, "model"):
            dims[4] = "model"
    if leaf == "ssm" and len(shape) == 5:
        # (L, B, H, P, N): heads over model
        if _divisible(shape[2], mesh, "model"):
            dims[2] = "model"
    return P(*dims)


def current_mesh() -> Optional[Mesh]:
    """The mesh installed by a ``with mesh:`` context, if any."""
    try:
        from jax._src.mesh import thread_resources

        m = thread_resources.env.physical_mesh
        return m if m.axis_names else None
    except Exception:  # noqa: BLE001
        return None


def constrain_sp(x):
    """Sequence-parallel constraint on a (B, S, d) residual-stream tensor:
    batch over (pod, data) when divisible, SEQUENCE over 'model'.  Shards
    the scan-over-layers remat stash 'model'-ways (Megatron-SP); GSPMD
    inserts the gather/scatter pairs around attention/MLP automatically.
    No-op outside a mesh context or when dims don't divide."""
    mesh = current_mesh()
    if mesh is None or x.ndim < 3 or "model" not in mesh.axis_names:
        return x
    baxes = batch_axes(mesh, x.shape[0])
    seq_ax = "model" if x.shape[1] % mesh.shape["model"] == 0 else None
    spec = [baxes if len(baxes) > 1 else (baxes[0] if baxes else None), seq_ax]
    spec += [None] * (x.ndim - 2)
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except (ValueError, RuntimeError):
        return x


def opt_state_shardings(opt_specs, params_shard, mesh: Mesh):
    """AdamW m/v mirror the param shardings; step is replicated."""
    from ..train.optimizer import AdamWState

    return AdamWState(
        step=NamedSharding(mesh, P()),
        m=jax.tree.map(lambda s: s, params_shard),
        v=jax.tree.map(lambda s: s, params_shard),
    )
