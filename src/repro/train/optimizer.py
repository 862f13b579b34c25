"""AdamW + LR schedules from scratch (no optax in this environment).

The optimizer state is a pytree mirroring the params (m, v in f32 —
sharded identically to the params by the same sharding rules, giving
ZeRO-style partitioned optimizer state under the fsdp axes).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array          # () int32
    m: Any                   # f32 pytree like params
    v: Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    schedule: str = "cosine"          # cosine | linear | constant
    min_lr_ratio: float = 0.1


def lr_at(cfg: AdamWConfig, step) -> jax.Array:
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    frac = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    if cfg.schedule == "cosine":
        decay = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
            1 + jnp.cos(jnp.pi * frac)
        )
    elif cfg.schedule == "linear":
        decay = 1.0 - (1 - cfg.min_lr_ratio) * frac
    else:
        decay = jnp.asarray(1.0)
    return cfg.lr * warm * decay


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(leaf.astype(jnp.float32))) for leaf in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), tree), norm


def _decay_mask(path: Tuple, leaf) -> bool:
    """No weight decay on norms/biases/scalars (1-D and smaller)."""
    return getattr(leaf, "ndim", 0) >= 2


def adamw_init(params) -> AdamWState:
    def zeros(p):
        return jnp.zeros(p.shape, jnp.float32)

    return AdamWState(
        step=jnp.zeros((), jnp.int32),
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
    )


def adamw_update(
    cfg: AdamWConfig, params, grads, state: AdamWState
) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip_norm)
    step = state.step + 1
    lr = lr_at(cfg, state.step)
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if _decay_mask((), p):
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        return new_p, m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.m)
    flat_v = jax.tree.leaves(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v, strict=False)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, AdamWState(step, new_m, new_v), metrics
