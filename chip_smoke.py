#!/usr/bin/env python3
"""Smoke run of the system on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: the paged server + stitch programs
    python chip_smoke.py --chips 4   # four chips: the sharded stitch path only

Phases (one chip):
  * serve  — qwen1.5-0.5b at its published width (bf16, random weights from
    seed 0) in the paged engine of ``repro.launch.serve``: 8 requests with
    prompts of 64..512 tokens, 32 new tokens each;
  * stitch — three plain-jnp programs through ``repro.stitch`` with default
    options (f32): a 2-layer pre-norm SwiGLU block at qwen1.5-0.5b widths,
    Fig-3 attention at (1, 16, 512, 64), and a row-softmax -> transpose
    that compiles to one multi-phase stitched kernel.  Each is compared with
    ``jax.jit`` of the same function.

With ``--chips 4`` it runs only a 2-layer Megatron MLP (D=1024, F=2816)
through ``stitch(mesh=...)`` on a 4-device ("model",) mesh, compared with
``jax.jit(shard_map(fn))`` on the same mesh.

Each phase prints one result line; the last line of stdout is a JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU, or
without the rest of the repository next to it, the script exits 1 before any
phase and prints no result.  It runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

#: serve phase settings at full size
SERVE = dict(
    pool=8, max_len=1024, block_size=16,
    prompt_lens=(64, 128, 192, 256, 320, 384, 448, 512), max_new=32,
)

#: stitched output vs jax.jit, both at HIGHEST matmul precision: largest
#: |difference| over the largest |reference|.  f32 summation-order noise over
#: these contractions is ~1e-6; a bf16-rounded contraction would be ~4e-3.
STITCH_TOL = 5e-4


class SmokeFailure(Exception):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


def serve_phase(cfg, *, pool, max_len, block_size, prompt_lens, max_new, seed=0):
    """Serve ``prompt_lens`` requests in the paged engine; check every
    request finished with in-vocabulary tokens and that the model's logits
    over the longest request are finite."""
    import jax
    import numpy as np

    from repro.launch.serve import build_engine, make_requests, serve
    from repro.models import forward, init_params

    params = init_params(cfg, seed=seed)
    engine = build_engine(cfg, params, engine="paged", pool=pool,
                          max_len=max_len, block_size=block_size)
    reqs = make_requests(cfg.vocab_size, prompt_lens, max_new, seed=seed)
    result = serve(engine, reqs, strict=True)
    check(result["done"] == len(reqs),
          f"serve: {result['done']}/{len(reqs)} requests done")
    for r in reqs:
        toks = np.asarray(r.out_tokens)
        check(len(toks) == max_new,
              f"serve: request {r.rid} has {len(toks)}/{max_new} tokens")
        check(bool(np.all((toks >= 0) & (toks < cfg.vocab_size))),
              f"serve: request {r.rid} has tokens outside the vocabulary")

    # teacher-forced forward over the longest request: its logits must be
    # finite, and show how often the engine's greedy token is their argmax
    r = max(reqs, key=lambda q: len(q.prompt))
    seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
    logits = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg))(params, seq[None])
    logits = np.asarray(logits[0, len(r.prompt) - 1:, : cfg.vocab_size])
    check(bool(np.isfinite(logits).all()), "serve: non-finite logits")
    agree = int((logits.argmax(-1) == np.asarray(r.out_tokens)).sum())
    return dict(result, requests=len(reqs), agree=agree, agree_of=len(r.out_tokens))


# --------------------------------------------------------------------------
# stitch
# --------------------------------------------------------------------------


def stitch_programs(full: bool = True):
    """name -> (plain-jnp fn, args): the smoke's stitch programs at full
    widths, or at small widths for a CPU run."""
    import numpy as np

    from graphs import nmt_fn, softmax_transpose_fn, swiglu_args, swiglu_fn

    rng = np.random.RandomState(0)
    tokens, d, f = (512, 1024, 2816) if full else (16, 128, 256)
    heads, seq, hd = (16, 512, 64) if full else (2, 16, 8)
    rows, cols = (512, 1024) if full else (16, 128)
    return {
        "swiglu": (swiglu_fn, swiglu_args(rng, tokens, d, f, num_layers=2)),
        "attention": (nmt_fn, tuple(
            rng.randn(1, heads, seq, hd).astype("f4") for _ in range(3)
        ) + (rng.randn(seq, seq).astype("f4"),)),
        "softmax_transpose": (softmax_transpose_fn, (
            rng.randn(rows, cols).astype("f4"), rng.randn(cols).astype("f4"),
        )),
    }


def _max_rel_err(name: str, out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    check(out.shape == ref.shape,
          f"{name}: shape {out.shape} != reference {ref.shape}")
    check(bool(np.isfinite(out).all()), f"{name}: non-finite output")
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def _warm_ms(fn, *args) -> float:
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return 1e3 * (time.perf_counter() - t0)


def compare_with_jit(name: str, st, ref_fn, args, tol: float = STITCH_TOL):
    """Run the stitched function and ``ref_fn`` at HIGHEST matmul precision;
    check no fallback and the error against ``ref_fn``."""
    import jax

    with jax.default_matmul_precision("highest"):
        out = jax.block_until_ready(st(*args))
        ref = jax.block_until_ready(ref_fn(*args))
        stitched_ms, jit_ms = _warm_ms(st, *args), _warm_ms(ref_fn, *args)
    check(st.num_fallbacks == 0,
          f"{name}: {st.num_fallbacks} fallback(s) to jax.jit")
    err = _max_rel_err(name, out, ref)
    check(err <= tol, f"{name}: max error {err:.3e} of max|ref| > {tol:.0e}")
    s = st.stats
    return dict(
        out=out, err=err, stitched=s.stitched_kernels,
        standalone=s.standalone_kernels, library=s.library_calls,
        interpret=s.interpret, fallbacks=st.num_fallbacks,
        stitched_ms=stitched_ms, jit_ms=jit_ms,
    )


def stitch_phase(name: str, fn, args, tol: float = STITCH_TOL):
    """``repro.stitch(fn)`` with default options vs ``jax.jit(fn)``."""
    import jax

    from repro import stitch

    return compare_with_jit(name, stitch(fn), jax.jit(fn), args, tol)


# --------------------------------------------------------------------------
# sharded (four chips)
# --------------------------------------------------------------------------


def sharded_phase(devices, *, tokens=512, d_model=1024, d_ff=2816, layers=2,
                  tol: float = STITCH_TOL):
    """Megatron MLP blocks (W1 column-, W2 row-parallel, one psum a layer)
    through ``stitch(mesh=...)`` vs ``jax.jit(shard_map(fn))``."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from graphs import TP_FAMILIES, stacked_tp_fn, stacked_tp_specs
    from repro import StitchOptions, stitch
    from repro.core.shard import wrap_shard_map

    mesh = Mesh(np.array(devices), ("model",))
    fn = functools.partial(stacked_tp_fn, axis="model")
    specs = stacked_tp_specs(layers)
    rng = np.random.RandomState(0)
    args = (
        rng.randn(tokens, d_model).astype("f4"),
        [(1.0 + 0.1 * rng.randn(d_model)).astype("f4") for _ in range(layers)],
        [(d_model ** -0.5 * rng.randn(d_model, d_ff)).astype("f4") for _ in range(layers)],
        [(d_ff ** -0.5 * rng.randn(d_ff, d_model)).astype("f4") for _ in range(layers)],
    )
    st = stitch(fn, options=StitchOptions(**TP_FAMILIES["Stacked_TP"]["options"]),
                mesh=mesh, **specs)
    ref_fn = jax.jit(wrap_shard_map(fn, mesh, specs["in_specs"], specs["out_specs"]))
    res = compare_with_jit("megatron_mlp", st, ref_fn, args, tol)
    spans = len(res["out"].sharding.device_set)
    check(spans == len(devices), f"output spans {spans}/{len(devices)} devices")
    check(st.stats.collective_calls == layers,
          f"{st.stats.collective_calls} collectives, expected {layers}")
    return dict(res, devices=spans, collectives=st.stats.collective_calls)


# --------------------------------------------------------------------------


def _stitch_line(name: str, r) -> str:
    return (f"[stitch] {name}: {r['stitched']} stitched + {r['standalone']} "
            f"standalone + {r['library']} library kernels, "
            f"{r['fallbacks']} fallbacks, interpret={r['interpret']}, "
            f"max error {r['err']:.3e} of max|ref| (tol {STITCH_TOL:.0e})")


def _first_look(name: str, r) -> str:
    return (f"[first look] {name}: one warm call, host clock: stitched "
            f"{r['stitched_ms']:.3f} ms, jax.jit {r['jit_ms']:.3f} ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded stitch path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    try:
        from repro.configs import get_config
        from repro.launch.runtime import device_info, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository is not next to this script ({e})",
              file=sys.stderr)
        return 1

    print(f"[cache] compilation cache: {enable_compile_cache()}")
    info = device_info()
    print(f"[device] {info['platform']} {info['kind']} x{info['count']}")
    try:
        if args.chips == 4:
            r = sharded_phase(devices[:4])
            print(_first_look("megatron_mlp", r))
            print(_stitch_line("megatron_mlp", r)
                  + f", output on {r['devices']} devices, "
                  f"{r['collectives']} all-reduces")
            check(r["interpret"] is False, "sharded kernels ran interpreted")
        else:
            cfg = get_config("qwen1.5-0.5b")
            r = serve_phase(cfg, **SERVE)
            print(f"[serve] {cfg.name} ({cfg.dtype}, d_model {cfg.d_model}, "
                  f"{cfg.num_layers} layers): {r['done']}/{r['requests']} "
                  f"requests done, {r['tokens']} tokens in {r['seconds']:.3f} s "
                  f"(host clock, compilation included); logits finite; greedy "
                  f"tokens = forward argmax for {r['agree']}/{r['agree_of']}")
            for name, (fn, fargs) in stitch_programs(full=True).items():
                r = stitch_phase(name, fn, fargs)
                print(_first_look(name, r))
                print(_stitch_line(name, r))
                check(r["interpret"] is False, f"{name}: kernels ran interpreted")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
