"""One decode step of a qwen1.5 (Qwen2-architecture) decoder stack, in plain
``jax.numpy`` at float32: the program that the stitch decode cells run.

Each of ``rows`` rows brings one new token's hidden state and attends over
its own K/V context of ``context`` positions, given as inputs.  An additive
``(rows, 1, context)`` mask hides the positions at and past each row's length; the new token's own
key and value join the softmax as one extra column, so no slice or scatter
is needed.  The cache write, the embedding and the LM head stay outside.

Per layer: RMSNorm, Q/K/V projections with bias, rotary embedding
(rotate-half, written as a matmul by a signed permutation), masked softmax
attention, output projection, residual, RMSNorm, SwiGLU MLP, residual.

``dot`` and ``einsum`` are the contractions the program uses; the precision
control passes lower-precision ones.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: added to the scores of hidden positions
MASK_NEG = -1e9


def dims(cfg: dict, traffic: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(
        layers=cfg["num_hidden_layers"], d=d, h=h, hd=d // h,
        f=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        theta=cfg["rope_theta"], rows=traffic["rows"], t=traffic["context"],
    )


def _rotate_half_matrix(hd: int) -> np.ndarray:
    """``x @ R == concatenate([-x[hd/2:], x[:hd/2]])``."""
    half = hd // 2
    r = np.zeros((hd, hd), np.float32)
    r[half:, :half] = -np.eye(half, dtype=np.float32)
    r[:half, half:] = np.eye(half, dtype=np.float32)
    return r


def program(cfg: dict, traffic: dict, dot=jnp.matmul, einsum=jnp.einsum):
    """The decode step ``fn(x, mask, cos, sin, layers) -> (x, ks, vs)``."""
    m = dims(cfg, traffic)
    B, D, H, hd, eps = m["rows"], m["d"], m["h"], m["hd"], m["eps"]
    rot = _rotate_half_matrix(hd)
    scale = 1.0 / math.sqrt(hd)

    def rmsnorm(x, g):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps) * g

    def rope(t, cos, sin):                      # t (B, H, hd); cos (B, 1, hd)
        return t * cos + dot(t, rot) * sin

    def fn(x, mask, cos, sin, layers):
        ks, vs = [], []
        for lp in layers:
            h = rmsnorm(x, lp["ln1"])
            q = (dot(h, lp["wq"]) + lp["bq"]).reshape(B, H, hd)
            k = (dot(h, lp["wk"]) + lp["bk"]).reshape(B, H, hd)
            v = (dot(h, lp["wv"]) + lp["bv"]).reshape(B, H, hd)
            q, k = rope(q, cos, sin), rope(k, cos, sin)
            s_ctx = einsum("bhd,bhtd->bht", q, lp["k"]) * scale + mask
            s_new = jnp.sum(q * k, axis=-1, keepdims=True) * scale
            top = jnp.maximum(jnp.max(s_ctx, axis=-1, keepdims=True), s_new)
            e_ctx = jnp.exp(s_ctx - top)
            e_new = jnp.exp(s_new - top)
            den = jnp.sum(e_ctx, axis=-1, keepdims=True) + e_new
            o = (einsum("bht,bhtd->bhd", e_ctx, lp["v"]) + e_new * v) / den
            x = x + dot(o.reshape(B, D), lp["wo"])
            h2 = rmsnorm(x, lp["ln2"])
            x = x + dot(jax.nn.silu(dot(h2, lp["wg"])) * dot(h2, lp["wi"]),
                        lp["wd"])
            ks.append(k)
            vs.append(v)
        return x, ks, vs

    return fn


def reference(cfg: dict, traffic: dict, dot=jnp.matmul, einsum=jnp.einsum):
    """The plain reference: the same function, for ``jax.jit``."""
    return program(cfg, traffic, dot=dot, einsum=einsum)


def _layer_shapes(m: dict) -> dict:
    B, D, H, hd, F, T = m["rows"], m["d"], m["h"], m["hd"], m["f"], m["t"]
    return {
        "ln1": (D,), "wq": (D, D), "bq": (D,), "wk": (D, D), "bk": (D,),
        "wv": (D, D), "bv": (D,), "wo": (D, D), "ln2": (D,),
        "wg": (D, F), "wi": (D, F), "wd": (F, D),
        "k": (B, H, T, hd), "v": (B, H, T, hd),
    }


def arg_shapes(cfg: dict, traffic: dict):
    """The call's arguments as ``ShapeDtypeStruct``s (all float32)."""
    m = dims(cfg, traffic)
    B, D, hd, T = m["rows"], m["d"], m["hd"], m["t"]

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    layer = {k: s(v) for k, v in _layer_shapes(m).items()}
    return (s((B, D)), s((B, 1, T)), s((B, 1, hd)), s((B, 1, hd)),
            [dict(layer) for _ in range(m["layers"])])


def lengths(cfg: dict, traffic: dict, seed: int) -> np.ndarray:
    """Each row's context length, drawn from ``seed``."""
    lo, hi = traffic["context_min"], traffic["context"]
    return np.random.default_rng(seed).integers(lo, hi + 1, traffic["rows"])


def make_args(cfg: dict, traffic: dict, seed: int, variants: int):
    """``variants`` argument tuples that differ in the new tokens' hidden
    states and share everything else, made on the device in one jitted
    call from ``seed``."""
    m = dims(cfg, traffic)
    B, D, hd, T = m["rows"], m["d"], m["hd"], m["t"]
    shapes = _layer_shapes(m)
    lens = jnp.asarray(lengths(cfg, traffic, seed), jnp.int32)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, m["layers"] + 1)
        layers = []
        for lk in keys[1:]:
            parts = jax.random.split(lk, len(shapes))
            lp = {}
            for pk, (name, shape) in zip(parts, shapes.items(), strict=True):
                z = jax.random.normal(pk, shape, jnp.float32)
                if name in ("ln1", "ln2"):
                    lp[name] = 1.0 + 0.1 * z
                elif name.startswith("b"):
                    lp[name] = 0.1 * z
                elif name in ("k", "v"):
                    lp[name] = z
                else:
                    lp[name] = z * shape[0] ** -0.5
            layers.append(lp)
        xs = jax.random.normal(keys[0], (variants, B, D), jnp.float32)
        pos = lens.astype(jnp.float32)[:, None]
        inv = m["theta"] ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
        ang = jnp.concatenate([pos * inv, pos * inv], axis=-1)[:, None, :]
        mask = jnp.where(jnp.arange(T)[None, None, :] < lens[:, None, None],
                         0.0, MASK_NEG)
        return xs, mask.astype(jnp.float32), jnp.cos(ang), jnp.sin(ang), layers

    xs, mask, cos, sin, layers = make(jax.random.PRNGKey(seed))
    return [(xs[i], mask, cos, sin, layers) for i in range(variants)]


def cost(cfg: dict, traffic: dict):
    """(FLOPs, least HBM bytes) of one call: every weight and every cached
    K/V value read once, the inputs read and the outputs written once."""
    m = dims(cfg, traffic)
    B, D, H, hd, F, T, L = (m["rows"], m["d"], m["h"], m["hd"], m["f"],
                            m["t"], m["layers"])
    proj = 2 * B * D * (4 * D + 3 * F)          # q, k, v, o, gate, up, down
    attn = 2 * 2 * B * H * T * hd                # scores and weighted sum
    flops = L * (proj + attn)       # the rotation's matmul is not counted
    weights = L * (4 * D * D + 3 * D + 3 * D * F + 2 * D)
    kv = L * 2 * B * H * T * hd
    io = B * D + B * T + 2 * B * hd + B * D + L * 2 * B * D
    return flops, 4 * (weights + kv + io)
