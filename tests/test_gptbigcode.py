"""GPTBigCode (granite-20b-code) at small widths with multi-query attention:
the plain reference against itself, and the tensor-parallel decode step
that the benchmark runs, compiled by ``stitch(mesh=...)`` on 4 of the 8
virtual CPU devices, against the reference.

Small widths: d 256, 8 query heads of 32, one K/V head, d_ff 1024, 4 rows
over a K/V context of 64, 2 layers.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import stitch
from repro.models import gptbigcode_ref as ref

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.programs import gptbigcode_decode as PROG  # noqa: E402

D, H, F, L, B, T = 256, 8, 1024, 2, 4, 64
HD = D // H
CFG = dict(n_embd=D, n_head=H, n_inner=F, n_layer=L, layer_norm_epsilon=1e-5,
           tensor_parallel=4)
TRAFFIC = dict(rows=B, context=T)
LENS = np.array([64, 5, 40, 17])


def _rel(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _inputs(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = ref.init_params(k[0], L, D, H, F)
    x = jax.random.normal(k[1], (B, D), jnp.float32)
    kc = list(jax.random.normal(k[2], (L, B, T, HD), jnp.float32))
    vc = list(jax.random.normal(k[3], (L, B, T, HD), jnp.float32))
    mask = jnp.where(jnp.arange(T)[None, :] < LENS[:, None], 0.0, ref.MASK_NEG)
    return x, mask.astype(jnp.float32), kc, vc, params


def _program_args(x, mask, kc, vc, params):
    layers = [dict(lp, k=k, v=v) for lp, k, v in zip(params, kc, vc, strict=True)]
    return x, mask[:, None, :], layers


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:4]), ("model",))


def _compare(mesh, program, in_specs, out_specs, seed=0):
    x, mask, kc, vc, params = _inputs(seed)
    want = ref.decode_step(x, mask, kc, vc, params)
    st = stitch(program, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    with jax.default_matmul_precision("highest"):
        got = st(*_program_args(x, mask, kc, vc, params))
    return max(_rel(g, w) for g, w in zip(jax.tree.leaves(got),
                                          jax.tree.leaves(want), strict=True)), st


def test_stitch_mesh_decode_matches_reference(mesh):
    in_specs, out_specs = PROG.specs(CFG, TRAFFIC)
    err, st = _compare(mesh, PROG.program(CFG, TRAFFIC), in_specs, out_specs)
    assert err < 2e-5
    s = st.stats
    assert st.num_fallbacks == 0
    assert s.collective_calls == 2 * L
    assert s.collective_bytes == 2 * L * B * D * 4


def test_reference_decode_after_prefix_equals_forward():
    """``forward`` over S+1 positions gives, at the last one, what
    ``decode_step`` gives for that token against the first S positions'
    K/V, held in a longer cache whose extra positions are masked."""
    S = 40
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    params = ref.init_params(k[0], L, D, H, F)
    h = jax.random.normal(k[1], (B, S + 1, D), jnp.float32)
    full, _ = ref.forward(h, params)
    _, kvs = ref.forward(h[:, :S], params)
    junk = jax.random.normal(k[2], (B, T - S, HD), jnp.float32)
    kc = [jnp.concatenate([kk, junk], axis=1) for kk, _ in kvs]
    vc = [jnp.concatenate([vv, junk], axis=1) for _, vv in kvs]
    mask = jnp.where(jnp.arange(T)[None, :] < S, 0.0, ref.MASK_NEG)
    mask = jnp.broadcast_to(mask, (B, T)).astype(jnp.float32)
    x, ks, vs = ref.decode_step(h[:, S], mask, kc, vc, params)
    assert _rel(x, full[:, S]) < 2e-5
    _, kvs_all = ref.forward(h, params)
    for (kk, vv), k_new, v_new in zip(kvs_all, ks, vs, strict=True):
        assert _rel(k_new, kk[:, S]) < 2e-5 and _rel(v_new, vv[:, S]) < 2e-5


def test_sharded_kv_head_fails_the_comparison(mesh):
    """The one K/V head split over the chips like the query heads: each chip
    then attends with a quarter of its width, and the step is wrong."""
    in_specs, out_specs = PROG.specs(CFG, TRAFFIC)
    split = {"wk": P(None, "model"), "bk": P("model"), "wv": P(None, "model"),
             "bv": P("model"), "k": P(None, None, "model"),
             "v": P(None, None, "model")}
    layers = [{n: split.get(n, s) for n, s in lp.items()} for lp in in_specs[2]]
    outs = (P(), [P(None, "model")] * L, [P(None, "model")] * L)
    err, _ = _compare(mesh, PROG.program(CFG, TRAFFIC),
                      in_specs[:2] + (layers,), outs)
    assert err > 1e-2
