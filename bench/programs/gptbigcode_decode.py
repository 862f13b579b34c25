"""One decode step of a GPTBigCode decoder stack (multi-query attention) with
Megatron tensor parallelism, in plain ``jax.numpy`` at float32: the program
that the stitch tensor-parallel cells run.

Each of ``rows`` rows brings one new token's hidden state and attends over
its own K/V context of ``context`` positions, given as inputs.  The single
key/value head has no head axis: the caches are ``(rows, context, head)``.
An additive ``(rows, 1, context)`` mask hides the positions at and past each
row's length; the new token's own key and value join the softmax as one
extra column.  The cache write, the embedding (tokens and learned
positions) and the LM head stay outside.

Per layer: LayerNorm, the fused ``c_attn`` projection (query heads and the
one K/V head, with bias), masked softmax attention with scale 1/sqrt(head),
``c_proj`` with bias, residual, LayerNorm, ``c_fc`` with bias, tanh GELU,
``c_proj`` with bias, residual.

``program`` is what one chip of a ``("model",)`` mesh runs under
``shard_map``: its query heads, the columns of ``c_fc`` and the rows of
both output projections, each of those followed by a ``lax.psum`` over
``"model"`` and only then by its bias; the K/V head's projection and the
caches are replicated.  ``reference`` is the same math on whole matrices
on one logical device, with no collective.  ``specs`` gives the in/out
``PartitionSpec``s that place the global arguments.

``dot`` and ``einsum`` are the contractions the program uses; the precision
control passes lower-precision ones.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

#: added to the scores of hidden positions
MASK_NEG = -1e9
#: the mesh axis the program's collectives reduce over
AXIS = "model"


def dims(cfg: dict, traffic: dict) -> dict:
    d, h = cfg["n_embd"], cfg["n_head"]
    return dict(
        layers=cfg["n_layer"], d=d, h=h, hd=d // h, f=cfg["n_inner"],
        eps=cfg["layer_norm_epsilon"], tp=cfg["tensor_parallel"],
        rows=traffic["rows"], t=traffic["context"],
    )


def _layer(dot, einsum, eps, all_reduce):
    """One layer ``(x, mask, lp) -> (x, k, v)`` on whatever share of the
    query heads and MLP columns ``lp`` holds; ``all_reduce`` sums the
    row-parallel projections' partial products."""

    def layernorm(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mu
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return xc * jax.lax.rsqrt(var + eps) * g + b

    def gelu_tanh(u):
        return 0.5 * u * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (u + 0.044715 * u * u * u)))

    def layer(x, mask, lp):
        B = x.shape[0]
        hd = lp["wk"].shape[1]
        H = lp["wq"].shape[1] // hd
        scale = 1.0 / math.sqrt(hd)
        h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
        q = (dot(h, lp["wq"]) + lp["bq"]).reshape(B, H, hd)
        k = dot(h, lp["wk"]) + lp["bk"]                 # (B, hd): the one head
        v = dot(h, lp["wv"]) + lp["bv"]
        s_ctx = einsum("bhd,btd->bht", q, lp["k"]) * scale + mask
        s_new = jnp.sum(q * k[:, None, :], axis=-1, keepdims=True) * scale
        top = jnp.maximum(jnp.max(s_ctx, axis=-1, keepdims=True), s_new)
        e_ctx = jnp.exp(s_ctx - top)
        e_new = jnp.exp(s_new - top)
        den = jnp.sum(e_ctx, axis=-1, keepdims=True) + e_new
        o = (einsum("bht,btd->bhd", e_ctx, lp["v"]) + e_new * v[:, None, :]) / den
        x = x + all_reduce(dot(o.reshape(B, H * hd), lp["wo"])) + lp["bo"]
        h2 = layernorm(x, lp["ln2_g"], lp["ln2_b"])
        m = gelu_tanh(dot(h2, lp["wfc"]) + lp["bfc"])
        x = x + all_reduce(dot(m, lp["wproj"])) + lp["bproj"]
        return x, k, v

    return layer


def _stack(layer):
    def fn(x, mask, layers):
        ks, vs = [], []
        for lp in layers:
            x, k, v = layer(x, mask, lp)
            ks.append(k)
            vs.append(v)
        return x, ks, vs

    return fn


def program(cfg: dict, traffic: dict, dot=jnp.matmul, einsum=jnp.einsum):
    """The per-chip step ``fn(x, mask, layers) -> (x, ks, vs)`` for
    ``shard_map`` over ``specs``."""
    m = dims(cfg, traffic)
    return _stack(_layer(dot, einsum, m["eps"],
                         lambda y: jax.lax.psum(y, AXIS)))


def reference(cfg: dict, traffic: dict, dot=jnp.matmul, einsum=jnp.einsum):
    """The plain reference: the whole step on global arrays, for
    ``jax.jit``."""
    m = dims(cfg, traffic)
    return _stack(_layer(dot, einsum, m["eps"], lambda y: y))


#: how each layer parameter is split over the mesh axis (absent: replicated)
SPLIT = {
    "wq": P(None, AXIS), "bq": P(AXIS), "wo": P(AXIS, None),
    "wfc": P(None, AXIS), "bfc": P(AXIS), "wproj": P(AXIS, None),
}


def _layer_shapes(m: dict) -> dict:
    B, D, hd, F, T = m["rows"], m["d"], m["hd"], m["f"], m["t"]
    return {
        "ln1_g": (D,), "ln1_b": (D,),
        "wq": (D, D), "bq": (D,), "wk": (D, hd), "bk": (hd,),
        "wv": (D, hd), "bv": (hd,), "wo": (D, D), "bo": (D,),
        "ln2_g": (D,), "ln2_b": (D,),
        "wfc": (D, F), "bfc": (F,), "wproj": (F, D), "bproj": (D,),
        "k": (B, T, hd), "v": (B, T, hd),
    }


def specs(cfg: dict, traffic: dict):
    """``(in_specs, out_specs)`` of ``program`` under ``shard_map``."""
    m = dims(cfg, traffic)
    layer = {n: SPLIT.get(n, P()) for n in _layer_shapes(m)}
    ins = (P(), P(), [dict(layer) for _ in range(m["layers"])])
    outs = (P(), [P()] * m["layers"], [P()] * m["layers"])
    return ins, outs


def arg_shapes(cfg: dict, traffic: dict):
    """The call's global arguments as ``ShapeDtypeStruct``s (all float32)."""
    m = dims(cfg, traffic)
    B, D, T = m["rows"], m["d"], m["t"]

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    layer = {k: s(v) for k, v in _layer_shapes(m).items()}
    return (s((B, D)), s((B, 1, T)), [dict(layer) for _ in range(m["layers"])])


def lengths(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """Each row's context length, drawn from ``seed``: lognormal around
    ``context_median``, clipped to ``[context_min, context]``."""
    rng = np.random.default_rng(seed)
    ln = rng.lognormal(math.log(traffic["context_median"]),
                       traffic["context_sigma"], traffic["rows"])
    return np.clip(np.rint(ln), traffic["context_min"],
                   traffic["context"]).astype(np.int64)


def make_args(cfg: dict, traffic: dict, seed: int, variants: int, mesh):
    """``variants`` argument tuples that differ in the new tokens' hidden
    states and share everything else, made in one jitted call from ``seed``
    with each array already placed on ``mesh`` by ``specs``: the split
    weights never exist whole on one chip."""
    m = dims(cfg, traffic)
    B, D, T = m["rows"], m["d"], m["t"]
    shapes = _layer_shapes(m)
    lens = jnp.asarray(lengths(cfg, traffic, seed), jnp.int32)

    def make(key):
        keys = jax.random.split(key, m["layers"] + 1)
        layers = []
        for lk in keys[1:]:
            parts = jax.random.split(lk, len(shapes))
            lp = {}
            for pk, (name, shape) in zip(parts, shapes.items(), strict=True):
                z = jax.random.normal(pk, shape, jnp.float32)
                if name.endswith("_g"):
                    lp[name] = 1.0 + 0.1 * z
                elif name.startswith("b") or name.endswith("_b"):
                    lp[name] = 0.1 * z
                elif name in ("k", "v"):
                    lp[name] = z
                else:
                    lp[name] = z * shape[0] ** -0.5
            layers.append(lp)
        xs = jax.random.normal(keys[0], (variants, B, D), jnp.float32)
        mask = jnp.where(jnp.arange(T)[None, None, :] < lens[:, None, None],
                         0.0, MASK_NEG)
        return xs, mask.astype(jnp.float32), layers

    ins, _ = specs(cfg, traffic)
    place = jax.tree.map(lambda s: NamedSharding(mesh, s), ins,
                         is_leaf=lambda s: isinstance(s, P))
    make = jax.jit(make, out_shardings=(NamedSharding(mesh, P()),) + place[1:])
    xs, mask, layers = make(jax.random.PRNGKey(seed))
    return [(xs[i], mask, layers) for i in range(variants)]


def cost(cfg: dict, traffic: dict):
    """(FLOPs, least HBM bytes) of one call on one chip: every weight of the
    chip's share and every cached K/V value read once, the inputs read and
    the outputs written once; the partial sums each chip hands to its
    all-reduces are not counted."""
    m = dims(cfg, traffic)
    B, D, hd, F, T, L, tp = (m["rows"], m["d"], m["hd"], m["f"], m["t"],
                             m["layers"], m["tp"])
    Dq = D // tp                                # the chip's query width
    proj = 2 * B * (D * Dq + 2 * D * hd + Dq * D + 2 * D * F // tp)
    attn = 2 * 2 * B * Dq * T                   # scores and weighted sum
    flops = L * (proj + attn)
    weights = L * (D * Dq + Dq + 2 * (D * hd + hd) + Dq * D + D + 4 * D
                   + 2 * D * F // tp + F // tp + D)
    io = B * D + B * T + B * D + L * 2 * B * hd
    return flops, 4 * (weights + L * kv_elems(m) + io)


def kv_elems(m: dict) -> int:
    """Cached K and V values of one layer."""
    return 2 * m["rows"] * m["t"] * m["hd"]


def attention_bytes(cfg: dict, traffic: dict) -> int:
    """Least HBM bytes of the kernels that read the K/V caches on one chip:
    the caches themselves, over every layer."""
    m = dims(cfg, traffic)
    return 4 * m["layers"] * kv_elems(m)
