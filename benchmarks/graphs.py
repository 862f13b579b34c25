"""The paper's six benchmark workloads (Table 2) re-created in StitchIR.

LR / W2V / RNN / BiRNN are the public tensorflow-examples models the paper
uses; Speech and NMT are modeled on the paper's description of its in-house
workloads (Speech: "complex interaction patterns among reduce, transpose,
concat, and elementwise ops"; NMT: the Figure-3 attention softmax×BatchDot
subgraph on marginal batched shapes, fused per the user decision).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import GraphBuilder, Module


def random_feeds(module: Module, rng) -> dict:
    """Random feeds for every module parameter (int32 params get small
    first-dim-bounded indices, floats get uniform(-1, 1)) — the ONE feed
    builder shared by the benchmark harness and the test suites
    (``tests/conftest.make_feeds`` delegates here)."""
    out = {}
    for p in module.parameters:
        if np.dtype(p.dtype) == np.int32:
            out[p.name] = rng.randint(
                0, max(2, p.shape[0] if p.shape else 2), size=p.shape
            ).astype(np.int32)
        else:
            out[p.name] = rng.uniform(-1, 1, size=p.shape).astype(
                np.dtype(p.dtype)
            )
    return out


LR_DIM = (64, 16)          # batch, features
W2V_DIM = (64, 32, 512)    # batch, embed dim, vocab
RNN_STEPS = 6
SPEECH_DIM = (8, 50, 40)   # batch, frames, filters
NMT_DIM = (4, 8, 32, 16)   # batch, heads, seq, head_dim


def lr_graph() -> Module:
    """Logistic-regression training step: fwd + grads + SGD updates."""
    b = GraphBuilder("LR")
    B, D = LR_DIM
    x = b.parameter("x", (B, D), jnp.float32)
    y = b.parameter("y", (B, 1), jnp.float32)
    W = b.parameter("W", (D, 1), jnp.float32)
    bias = b.parameter("b", (1,), jnp.float32)
    z = b.dot(x, W)                                    # LC
    p = b.sigmoid(z + b.broadcast(bias, (B, 1), (1,)))
    e = p - y
    xt = b.transpose(x, (1, 0))
    dW = b.dot(xt, e)                                  # LC
    _W2 = W - dW * 0.1                                 # update kernel
    db = b.reduce(e, (0, 1), "mean")
    _b2 = bias - b.broadcast(db, (1,), ()) * 0.1
    # loss for logging: -(y log p + (1-y) log(1-p))
    lp = b.log(b.maximum(p, 1e-6))
    ln = b.log(b.maximum(1.0 - p, 1e-6))
    _loss = b.reduce(0.0 - (y * lp + (1.0 - y) * ln), (0, 1), "mean")
    return b.module


def w2v_graph() -> Module:
    """Word2vec negative-sampling step: gathers + elementwise grads."""
    b = GraphBuilder("W2V")
    B, D, V = W2V_DIM
    t_in = b.parameter("emb_in", (V, D), jnp.float32)
    t_out = b.parameter("emb_out", (V, D), jnp.float32)
    idx = b.parameter("center", (B,), jnp.int32)
    ctx = b.parameter("context", (B,), jnp.int32)
    lbl = b.parameter("label", (B,), jnp.float32)
    ein = b.gather(t_in, idx)                          # (B, D)
    eout = b.gather(t_out, ctx)
    score = b.reduce(ein * eout, (1,), "sum")          # (B,)
    p = b.sigmoid(score)
    g = p - lbl
    gb = b.broadcast(g, (B, D), (0,))
    _d_in = ein - gb * eout * 0.05                     # updated rows
    _d_out = eout - gb * ein * 0.05
    return b.module


def _rnn_cell(b, x_t, h, Wx, Wh, bias, tag):
    a = b.dot(x_t, Wx)                                 # LC
    c = b.dot(h, Wh)                                   # LC
    s = a + c + b.broadcast(bias, a.shape, (1,))
    return b.tanh(s)


def rnn_graph(steps: int = RNN_STEPS, name="RNN") -> Module:
    b = GraphBuilder(name)
    B, D, H = 16, 24, 32
    Wx = b.parameter("Wx", (D, H), jnp.float32)
    Wh = b.parameter("Wh", (H, H), jnp.float32)
    bias = b.parameter("b", (H,), jnp.float32)
    h = b.parameter("h0", (B, H), jnp.float32)
    for t in range(steps):
        x_t = b.parameter(f"x{t}", (B, D), jnp.float32)
        h = _rnn_cell(b, x_t, h, Wx, Wh, bias, t)
    Wo = b.parameter("Wo", (H, 8), jnp.float32)
    logits = b.dot(h, Wo)                              # LC
    _probs = b.softmax(logits, dim=-1)
    return b.module


def birnn_graph(steps: int = RNN_STEPS) -> Module:
    b = GraphBuilder("BiRNN")
    B, D, H = 16, 24, 32
    xs = [b.parameter(f"x{t}", (B, D), jnp.float32) for t in range(steps)]
    hf = b.parameter("hf0", (B, H), jnp.float32)
    hb = b.parameter("hb0", (B, H), jnp.float32)
    Wxf = b.parameter("Wxf", (D, H), jnp.float32)
    Whf = b.parameter("Whf", (H, H), jnp.float32)
    bf = b.parameter("bf", (H,), jnp.float32)
    Wxb = b.parameter("Wxb", (D, H), jnp.float32)
    Whb = b.parameter("Whb", (H, H), jnp.float32)
    bb = b.parameter("bb", (H,), jnp.float32)
    for t in range(steps):
        hf = _rnn_cell(b, xs[t], hf, Wxf, Whf, bf, f"f{t}")
    for t in reversed(range(steps)):
        hb = _rnn_cell(b, xs[t], hb, Wxb, Whb, bb, f"b{t}")
    hcat = b.concat([hf, hb], dim=1)                   # (B, 2H)
    Wo = b.parameter("Wo", (2 * H, 8), jnp.float32)
    _out = b.softmax(b.dot(hcat, Wo), dim=-1)
    return b.module


def speech_graph() -> Module:
    """Acoustic frontend head: square/log/reduce/transpose/concat mix."""
    b = GraphBuilder("Speech")
    B, T, F = SPEECH_DIM
    x = b.parameter("frames", (B, T, F), jnp.float32)
    mel_w = b.parameter("mel", (F, F), jnp.float32)
    power = b.square(x)
    flat = b.reshape(power, (B * T, F))
    mel = b.dot(flat, mel_w)                           # LC
    lg = b.log(b.maximum(b.reshape(mel, (B, T, F)), 1e-6))
    # per-utterance mean/var normalization (column reduces over time)
    mu = b.reduce(lg, (1,), "mean")                    # (B, F)
    mub = b.broadcast(mu, (B, T, F), (0, 2))
    cen = lg - mub
    var = b.reduce(b.square(cen), (1,), "mean")
    inv = b.rsqrt(var + 1e-5)
    norm = cen * b.broadcast(inv, (B, T, F), (0, 2))
    # transpose to feature-major and append a scaled copy (delta stand-in)
    tr = b.transpose(norm, (0, 2, 1))                  # (B, F, T)
    delta = tr * 0.5 + 0.1
    feats = b.concat([tr, delta], dim=1)               # (B, 2F, T)
    gate = b.sigmoid(feats)
    _out = b.reduce(gate * feats, (2,), "mean")        # (B, 2F)
    return b.module


def nmt_graph(fuse_dot: bool = True) -> Module:
    """The paper's Figure-3 subgraph: softmax stitched with BatchMatMul."""
    b = GraphBuilder("NMT")
    B, H, S, D = NMT_DIM
    q = b.parameter("q", (B, H, S, D), jnp.float32)
    k = b.parameter("k", (B, H, S, D), jnp.float32)
    v = b.parameter("v", (B, H, S, D), jnp.float32)
    bias = b.parameter("bias", (S, S), jnp.float32)
    kt = b.transpose(k, (0, 1, 3, 2))
    scores = b.dot(q, kt, fusable=fuse_dot)            # marginal batched shape
    scaled = scores * (1.0 / D ** 0.5) + b.broadcast(bias, scores.shape, (2, 3))
    p = b.softmax(scaled, dim=-1)
    ctx = b.dot(p, v, fusable=fuse_dot)                # Dot.1 of Figure 3
    _out = b.tanh(ctx)
    return b.module


def stacked_transformer_graph(num_layers: int = 8) -> Module:
    """N structurally-identical pre-norm transformer-ish blocks separated by
    library MatMuls — the repeated-layer serving workload the kernel cache
    targets: every middle layer's fusion has the same fusion signature."""
    b = GraphBuilder("Stacked")
    B, D = 16, 64
    x = b.parameter("x", (B, D), jnp.float32)
    for layer in range(num_layers):
        g = b.parameter(f"g{layer}", (D,), jnp.float32)
        W = b.parameter(f"W{layer}", (D, D), jnp.float32)
        ms = b.reduce(b.square(x), (1,), "mean")
        inv = b.rsqrt(ms + 1e-6)
        normed = x * b.broadcast(inv, (B, D), (0,)) * b.broadcast(g, (B, D), (1,))
        h = b.dot(normed, W)                           # LC: layer boundary
        x = x + b.silu(h)
    return b.module


def reduce_towers_graph(num_towers: int = 6) -> Module:
    """Adversarial for greedy fusion (reduce-heavy): N independent
    square/scale/reduce towers whose sinks are *reduces*, not elementwise
    ops — so the paper's ElementwiseFusion never groups them and Algorithm 1
    commits one kernel per tower.  The towers are tiny, so launch overhead
    dominates; the cost-guided planner's horizontal-merge pass packs them
    into one multi-root kernel."""
    b = GraphBuilder("ReduceTowers")
    B, D = 32, 64
    for i in range(num_towers):
        x = b.parameter(f"x{i}", (B, D), jnp.float32)
        s = b.parameter(f"s{i}", (B, D), jnp.float32)
        e = b.square(x * 0.5 + s)
        _ = b.reduce(e * e, (0, 1), "sum")
    return b.module


def broadcast_towers_graph(num_towers: int = 5) -> Module:
    """Adversarial for greedy fusion (broadcast/replication-heavy): each
    tower broadcasts a small per-feature gain across a wide activation,
    normalizes by a mid-tower reduce, broadcasts back to the wide shape, and
    ends in a *reshape* sink (invisible to ElementwiseFusion, which only
    groups elementwise sinks).  Greedy commits one maximal kernel per tower,
    each carrying the reduce and two widening broadcasts; the planner
    explores split-at-reduce / split-before-broadcast partitions per tower
    and packs the towers into fewer kernels via horizontal merge."""
    b = GraphBuilder("BcastHeavy")
    B, D = 16, 32
    for i in range(num_towers):
        x = b.parameter(f"x{i}", (B, D), jnp.float32)
        g = b.parameter(f"g{i}", (D,), jnp.float32)
        scaled = x * b.broadcast(g, (B, D), (1,))
        m = b.reduce(scaled, (1,), "mean")             # (B,)
        cen = scaled - b.broadcast(m, (B, D), (0,))
        _ = b.reshape(b.sigmoid(cen), (B * D,))        # flat sink
    return b.module


def stitch_pipeline_graph() -> Module:
    """Adversarial for single-schedule fusion (schedule-break-heavy): a wide
    row-softmax feeds a full 2-D transpose and a tail normalization.  The
    softmax intermediate (512x320 f32, 640KB) exceeds the replicate limit,
    so no single block schedule crosses the transpose — the paper-faithful
    compiler splits here into three kernels.  Multi-phase stitching lowers
    the whole pipeline as ONE kernel: the softmax phase materializes its
    output in a full VMEM staging buffer and the transpose phase re-tiles
    it under its own sub-schedule (arXiv:1911.11576 / 2009.10924)."""
    b = GraphBuilder("StitchPipe")
    B, D = 512, 320
    x = b.parameter("x", (B, D), jnp.float32)
    g = b.parameter("g", (D,), jnp.float32)
    scaled = x * b.broadcast(g, (B, D), (1,))
    mx = b.reduce(scaled, (1,), "max")
    e = b.exp(scaled - b.broadcast(mx, (B, D), (0,)))
    s = b.reduce(e, (1,), "sum")
    p = e / b.broadcast(s, (B, D), (0,))
    t = b.transpose(p, (1, 0))                         # (D, B): the break
    _out = b.tanh(t) * 0.5
    return b.module


# --------------------------------------------------------------------------
# Plain-jnp family (jaxpr-frontend parity): the same computations written as
# ordinary jax.numpy functions — zero GraphBuilder calls — captured through
# ``repro.stitch``.  Each entry pairs the jnp function with the hand-built
# module above so benchmarks and tests can assert the frontend reproduces
# the hand-built plans (same kernel counts, outputs allclose to jax.jit).
# --------------------------------------------------------------------------


def nmt_fn(q, k, v, bias):
    """Figure-3 attention (softmax stitched with BatchMatMul) in plain jnp —
    mirrors ``nmt_graph``."""
    d = q.shape[-1]
    kt = jnp.swapaxes(k, -1, -2)
    scores = jnp.matmul(q, kt)
    scaled = scores * (1.0 / d ** 0.5) + bias
    mx = jnp.max(scaled, axis=-1, keepdims=True)
    e = jnp.exp(scaled - mx)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.tanh(jnp.matmul(p, v))


def nmt_args(rng):
    B, H, S, D = NMT_DIM
    return (
        rng.randn(B, H, S, D).astype("f4"),
        rng.randn(B, H, S, D).astype("f4"),
        rng.randn(B, H, S, D).astype("f4"),
        rng.randn(S, S).astype("f4"),
    )


def stacked_fn(x, gains, weights):
    """Pre-norm transformer-ish blocks in plain jnp — mirrors
    ``stacked_transformer_graph`` (dots stay library calls: compile with
    ``fuse_dot=False``)."""
    for g, W in zip(gains, weights, strict=False):
        ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
        inv = jax.lax.rsqrt(ms + 1e-6)
        normed = x * inv * g[None, :]
        x = x + jax.nn.silu(jnp.matmul(normed, W))
    return x


def stacked_args(rng, num_layers: int = 8):
    B, D = 16, 64
    return (
        rng.randn(B, D).astype("f4"),
        [rng.randn(D).astype("f4") for _ in range(num_layers)],
        [rng.randn(D, D).astype("f4") for _ in range(num_layers)],
    )


def swiglu_fn(x, gains, w_gate, w_up, w_down):
    """Pre-norm SwiGLU MLP blocks (the qwen/llama FFN) in plain jnp: RMSNorm,
    ``silu(x Wg) * (x Wu)``, down projection, residual add."""
    for g, wg, wu, wd in zip(gains, w_gate, w_up, w_down, strict=False):
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        normed = x * jax.lax.rsqrt(ms + 1e-6) * g
        h = jax.nn.silu(jnp.matmul(normed, wg)) * jnp.matmul(normed, wu)
        x = x + jnp.matmul(h, wd)
    return x


def swiglu_args(rng, tokens: int, d_model: int, d_ff: int, num_layers: int = 2):
    s_in, s_ff = d_model ** -0.5, d_ff ** -0.5
    return (
        rng.randn(tokens, d_model).astype("f4"),
        [(1.0 + 0.1 * rng.randn(d_model)).astype("f4") for _ in range(num_layers)],
        [(s_in * rng.randn(d_model, d_ff)).astype("f4") for _ in range(num_layers)],
        [(s_in * rng.randn(d_model, d_ff)).astype("f4") for _ in range(num_layers)],
        [(s_ff * rng.randn(d_ff, d_model)).astype("f4") for _ in range(num_layers)],
    )


def softmax_transpose_fn(x, g):
    """``stitch_pipeline_graph`` in plain jnp: a gained row-softmax feeding
    a full 2-D transpose and a tail op — one multi-phase stitched kernel."""
    scaled = x * g
    e = jnp.exp(scaled - jnp.max(scaled, axis=1, keepdims=True))
    p = e / jnp.sum(e, axis=1, keepdims=True)
    return jnp.tanh(p.T) * 0.5


def reduce_towers_fn(xs, ss):
    """Independent square/scale/reduce towers in plain jnp — mirrors
    ``reduce_towers_graph`` (the horizontal-merge adversary)."""
    outs = []
    for x, s in zip(xs, ss, strict=False):
        e = jnp.square(x * 0.5 + s)
        outs.append(jnp.sum(e * e))
    return tuple(outs)


def reduce_towers_args(rng, num_towers: int = 6):
    B, D = 32, 64
    return (
        [rng.randn(B, D).astype("f4") for _ in range(num_towers)],
        [rng.randn(B, D).astype("f4") for _ in range(num_towers)],
    )


# --------------------------------------------------------------------------
# Tensor-parallel family (shard-aware compilation): the same workloads with
# Megatron-style TP placements.  Each function takes ``axis``: None gives the
# single-device reference plan (the per-device-kernel ceiling in compare.py),
# an axis name gives the shard_map body with the ``lax.psum`` all-reduce.
# The collective always lands immediately after a library dot, so it breaks
# no fusion group: per-device kernel counts match the single-device plan,
# and the stitched kernels on both sides of the psum span the break.
# --------------------------------------------------------------------------


def nmt_tp_fn(q, k, v, bias, wo, axis=None):
    """Head-parallel attention + row-parallel output projection.  ``q/k/v``
    shard the head dim, ``wo`` the flattened head*dim rows; the psum after
    the projection dot merges the per-head partial outputs."""
    B, H, S, D = q.shape
    kt = jnp.swapaxes(k, -1, -2)
    scores = jnp.matmul(q, kt) * (1.0 / D ** 0.5) + bias
    mx = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - mx)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    ctx = jnp.tanh(jnp.matmul(p, v))
    # flatten to an explicit 2-D projection: a 3-D matmul would make jax
    # insert a reshape between the dot and the psum, stranding it as its own
    # kernel on the sharded side (single-device fuses it into the tail)
    flat = jnp.reshape(jnp.transpose(ctx, (0, 2, 1, 3)), (B * S, H * D))
    y = jnp.matmul(flat, wo)
    if axis is not None:
        y = jax.lax.psum(y, axis)
    return y * jax.nn.sigmoid(y)


#: the TP variant doubles the head count so each of the 8 shards keeps a
#: real head dim (H=1 per shard would make jax squeeze the batched dots
#: into a different graph than the single-device reference plan)
NMT_TP_DIM = (4, 16, 32, 16)


def nmt_tp_args(rng):
    B, H, S, D = NMT_TP_DIM
    return (
        rng.randn(B, H, S, D).astype("f4"),
        rng.randn(B, H, S, D).astype("f4"),
        rng.randn(B, H, S, D).astype("f4"),
        rng.randn(S, S).astype("f4"),
        rng.randn(H * D, D).astype("f4"),
    )


def nmt_tp_specs():
    from jax.sharding import PartitionSpec as P

    return dict(
        in_specs=(
            P(None, "model"), P(None, "model"), P(None, "model"),
            P(), P("model", None),
        ),
        out_specs=P(),
    )


def stacked_tp_fn(x, gains, w1s, w2s, axis=None):
    """Megatron MLP blocks: W1 column-parallel, W2 row-parallel, one psum
    per layer merging the partial block outputs into the residual stream."""
    for g, W1, W2 in zip(gains, w1s, w2s, strict=False):
        ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
        inv = jax.lax.rsqrt(ms + 1e-6)
        normed = x * inv * g[None, :]
        y = jnp.matmul(jax.nn.silu(jnp.matmul(normed, W1)), W2)
        if axis is not None:
            y = jax.lax.psum(y, axis)
        x = x + y
    return x


def stacked_tp_args(rng, num_layers: int = 8):
    B, D, F = 16, 64, 128
    return (
        rng.randn(B, D).astype("f4"),
        [rng.randn(D).astype("f4") for _ in range(num_layers)],
        [rng.randn(D, F).astype("f4") for _ in range(num_layers)],
        [rng.randn(F, D).astype("f4") for _ in range(num_layers)],
    )


def stacked_tp_specs(num_layers: int = 8):
    from jax.sharding import PartitionSpec as P

    return dict(
        in_specs=(
            P(),
            [P()] * num_layers,
            [P(None, "model")] * num_layers,
            [P("model", None)] * num_layers,
        ),
        out_specs=P(),
    )


#: tensor-parallel families: fn(..., axis=) + args + the shard_map specs +
#: the StitchOptions overrides both the sharded and the single-device
#: reference compile use (library dots keep the collective off any fusion
#: group's interior).
TP_FAMILIES = {
    "NMT_TP": {
        "fn": nmt_tp_fn, "args": nmt_tp_args, "specs": nmt_tp_specs,
        "options": {"fuse_dot": False},
    },
    "Stacked_TP": {
        "fn": stacked_tp_fn, "args": stacked_tp_args,
        "specs": stacked_tp_specs, "options": {"fuse_dot": False},
    },
}


#: frontend-parity families: jnp fn + example args + the hand-built module
#: it must reproduce + the StitchOptions overrides the frontend compiles
#: under (e.g. Stacked keeps its dots as library calls via fuse_dot=False,
#: matching the hand-built graph's ``fusable=False`` dots).
JNP_FAMILIES = {
    "NMT": {
        "fn": nmt_fn, "args": nmt_args, "module": nmt_graph, "options": {},
    },
    "Stacked": {
        "fn": stacked_fn, "args": stacked_args,
        "module": stacked_transformer_graph, "options": {"fuse_dot": False},
    },
    "ReduceTowers": {
        "fn": reduce_towers_fn, "args": reduce_towers_args,
        "module": reduce_towers_graph, "options": {},
    },
}


ALL_GRAPHS = {
    "LR": lr_graph,
    "W2V": w2v_graph,
    "RNN": rnn_graph,
    "BiRNN": birnn_graph,
    "Speech": speech_graph,
    "NMT": nmt_graph,
    "Stacked": stacked_transformer_graph,
    "ReduceTowers": reduce_towers_graph,
    "BcastHeavy": broadcast_towers_graph,
    "StitchPipe": stitch_pipeline_graph,
}
