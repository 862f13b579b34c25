"""Plain reference of a GPTBigCode decoder layer stack (granite-20b-code),
in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``: no kernels, no paged cache, no
sharding.

A layer (HF ``GPTBigCodeBlock`` with ``multi_query=True``):

    h  = LayerNorm(x; ln1_g, ln1_b, eps)
    q  = h @ wq + bq                    (n_head heads of head_dim)
    k  = h @ wk + bk ; v = h @ wv + bv  (ONE key/value head of head_dim)
    o  = softmax(q k^T / sqrt(head_dim), causal) v
    x  = x + o @ wo + bo
    h2 = LayerNorm(x; ln2_g, ln2_b, eps)
    x  = x + gelu_tanh(h2 @ wfc + bfc) @ wproj + bproj

``wq``, ``wk`` and ``wv`` are the column blocks of the published fused
``c_attn`` (query heads first, then the key head, then the value head);
``wo`` is ``attn.c_proj``, ``wfc`` ``mlp.c_fc`` and ``wproj``
``mlp.c_proj``, stored (in, out).

Departures from the published model, all outside the layers: the token
and learned absolute position embeddings (``wte``, ``wpe``), the final
LayerNorm and the tied LM head are left out, so ``forward`` takes and
returns hidden states; dropout is off, as at inference.

``decode_step`` is one new token per row against that row's cached K/V:
the cache holds ``T`` positions of which the additive ``mask`` (0 kept,
large negative hidden) keeps each row's own, and the new token's key and
value join the softmax as one extra column, so no cache write is needed.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

Layer = Dict[str, jnp.ndarray]

#: additive mask value of a hidden position
MASK_NEG = -1e9


def layernorm(x, g, b, eps: float):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(u):
    """``gelu_pytorch_tanh``."""
    return 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (u + 0.044715 * u ** 3)))


def _qkv(h, lp: Layer):
    hd = lp["wk"].shape[1]
    q = (h @ lp["wq"] + lp["bq"]).reshape(h.shape[:-1] + (-1, hd))
    return q, h @ lp["wk"] + lp["bk"], h @ lp["wv"] + lp["bv"]


def _mlp_out(x, o, lp: Layer, eps: float):
    x = x + o @ lp["wo"] + lp["bo"]
    h2 = layernorm(x, lp["ln2_g"], lp["ln2_b"], eps)
    return x + gelu_tanh(h2 @ lp["wfc"] + lp["bfc"]) @ lp["wproj"] + lp["bproj"]


def forward(h, params: Sequence[Layer], eps: float = 1e-5):
    """Causal forward of hidden states ``h`` ``(B, S, D)`` through every
    layer; returns the hidden states and each layer's ``(k, v)``, both
    ``(B, S, head_dim)``."""
    with jax.default_matmul_precision("highest"):
        S = h.shape[1]
        causal = jnp.tril(jnp.ones((S, S), bool))
        kvs: List[Tuple] = []
        for lp in params:
            q, k, v = _qkv(layernorm(h, lp["ln1_g"], lp["ln1_b"], eps), lp)
            s = jnp.einsum("bshd,btd->bhst", q, k) / math.sqrt(k.shape[-1])
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("bhst,btd->bshd", p, v)
            h = _mlp_out(h, o.reshape(h.shape), lp, eps)
            kvs.append((k, v))
        return h, kvs


def decode_step(x, mask, k_cache, v_cache, params: Sequence[Layer],
                eps: float = 1e-5):
    """One new token per row: ``x`` ``(B, D)``, ``mask`` ``(B, T)``
    additive, ``k_cache``/``v_cache`` per layer ``(B, T, head_dim)``.
    Returns the hidden states and each layer's new ``k`` and ``v``
    ``(B, head_dim)``."""
    with jax.default_matmul_precision("highest"):
        ks, vs = [], []
        for lp, kc, vc in zip(params, k_cache, v_cache, strict=True):
            q, k, v = _qkv(layernorm(x, lp["ln1_g"], lp["ln1_b"], eps), lp)
            keys = jnp.concatenate([kc, k[:, None, :]], axis=1)
            vals = jnp.concatenate([vc, v[:, None, :]], axis=1)
            bias = jnp.concatenate([mask, jnp.zeros_like(mask[:, :1])], axis=1)
            s = jnp.einsum("bhd,btd->bht", q, keys) / math.sqrt(k.shape[-1])
            p = jax.nn.softmax(s + bias[:, None, :], axis=-1)
            o = jnp.einsum("bht,btd->bhd", p, vals)
            x = _mlp_out(x, o.reshape(x.shape), lp, eps)
            ks.append(k)
            vs.append(v)
        return x, ks, vs


def init_params(key, n_layer: int, d: int, n_head: int, d_ff: int) -> List[Layer]:
    """Random layers: projections N(0, 1/fan_in), biases N(0, 0.1),
    LayerNorm gains 1 + N(0, 0.1)."""
    hd = d // n_head
    shapes = {
        "ln1_g": (d,), "ln1_b": (d,), "wq": (d, d), "bq": (d,),
        "wk": (d, hd), "bk": (hd,), "wv": (d, hd), "bv": (hd,),
        "wo": (d, d), "bo": (d,), "ln2_g": (d,), "ln2_b": (d,),
        "wfc": (d, d_ff), "bfc": (d_ff,), "wproj": (d_ff, d), "bproj": (d,),
    }
    layers = []
    for lk in jax.random.split(key, n_layer):
        lp = {}
        for pk, (name, shape) in zip(jax.random.split(lk, len(shapes)),
                                     shapes.items(), strict=True):
            z = jax.random.normal(pk, shape, jnp.float32)
            if name.endswith("_g"):
                lp[name] = 1.0 + 0.1 * z
            elif len(shape) == 1:
                lp[name] = 0.1 * z
            else:
                lp[name] = z * shape[0] ** -0.5
        layers.append(lp)
    return layers
