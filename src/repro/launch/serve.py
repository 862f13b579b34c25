"""Serving launcher: batched decode on a selected architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --requests 8 --max-new 16 [--reduced] [--engine paged|slot] \
        [--block-size 16] [--num-blocks N] [--ttft-slo-ms 50]

The config runs at its published width unless ``--reduced`` is given.
``build_engine``/``make_requests``/``serve`` are the launcher's code as
callables, for scripts that drive the same path.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..configs import get_config, reduced_config
from ..models import count_params, init_params
from ..serve import PagedServeEngine, Request, ServeEngine, SLOConfig
from .runtime import device_info, enable_compile_cache


def build_engine(cfg, params, *, engine: str = "paged", pool: int = 4,
                 max_len: int = 128, prefill_chunk: int = 16,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 slo: Optional[SLOConfig] = None):
    """The launcher's engine: ``paged`` (continuous batching over KV blocks,
    ``pool`` decode rows) or ``slot`` (``pool`` contiguous per-slot rings)."""
    if engine == "paged":
        return PagedServeEngine(
            cfg, params, decode_width=pool, max_len=max_len,
            block_size=block_size, num_blocks=num_blocks,
            prefill_chunk=prefill_chunk, slo=slo,
        )
    if engine == "slot":
        return ServeEngine(cfg, params, pool_size=pool, max_len=max_len,
                           prefill_chunk=prefill_chunk)
    raise ValueError(f"unknown engine {engine!r}; expected 'paged' or 'slot'")


def make_requests(vocab_size: int, prompt_lens: Sequence[int], max_new: int,
                  seed: int = 0) -> List[Request]:
    """One request per prompt length, prompt tokens drawn from ``seed``."""
    rng = np.random.RandomState(seed)
    return [
        Request(rid=i, prompt=rng.randint(1, vocab_size, size=int(n)).astype(np.int32),
                max_new_tokens=max_new)
        for i, n in enumerate(prompt_lens)
    ]


def serve(engine, reqs: Sequence[Request], max_ticks: int = 20_000,
          strict: bool = False) -> Dict[str, object]:
    """Admit ``reqs`` and tick until done.  Returns the unfinished count,
    generated tokens and wall seconds (host clock, compilation included)."""
    t0 = time.perf_counter()
    # admit() parks overflow on the engine's FIFO wait queue; ticks drain it
    for r in reqs:
        engine.admit(r)
    remaining = engine.run_until_done(max_ticks=max_ticks, strict=strict)
    dt = time.perf_counter() - t0
    return {
        "remaining": remaining,
        "done": sum(r.done for r in reqs),
        "tokens": sum(len(r.out_tokens or []) for r in reqs),
        "seconds": dt,
    }


def _report(engine, reqs: Sequence[Request], result: Dict[str, object]) -> None:
    for r in reqs:
        print(f"[req {r.rid:3d}] prompt={len(r.prompt):3d} "
              f"new={len(r.out_tokens or []):3d} "
              f"wait={1e3 * (r.queue_wait_s or 0):7.1f}ms "
              f"ttft={1e3 * (r.ttft_s or 0):7.1f}ms "
              f"latency={1e3 * (r.latency_s or 0):7.1f}ms "
              f"tok/s={r.tokens_per_s or 0:6.1f}")
    st = engine.stats()
    toks, dt = result["tokens"], result["seconds"]
    print(f"[serve] {result['done']}/{len(reqs)} done "
          f"({result['remaining']} unfinished), "
          f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    print(f"[serve] launches: prefill={st['prefill_launches']} "
          f"(per-token would be {st['prefill_tokens']}), "
          f"decode={st['decode_launches']}; "
          f"decode_cache={st['decode_cache']}")
    if "kv_blocks" in st:
        kv = st["kv_blocks"]
        print(f"[serve] kv blocks: peak={kv['peak_in_use']}/{kv['num_blocks']} "
              f"(util {kv['peak_utilization']:.2f}), "
              f"alloc={kv['allocated_total']} freed={kv['freed_total']} "
              f"preemptions={st['preemptions']} "
              f"max_inflight={st['max_inflight']}")


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--engine", choices=("paged", "slot"), default="paged",
                    help="paged = continuous batching over KV blocks "
                    "(default); slot = contiguous per-slot rings")
    ap.add_argument("--pool", type=int, default=4,
                    help="slot engine: batch slots; paged engine: decode "
                    "width (rows per batched launch)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per prefill launch (1 = per-token)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: tokens per KV block")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged engine: total KV blocks (default: "
                    "pool * ceil(ring/block_size), i.e. no memory pressure)")
    ap.add_argument("--ttft-slo-ms", type=float, default=None,
                    help="paged engine: prioritize prefill when a request's "
                    "projected TTFT would overrun this")
    ap.add_argument("--decode-slo-ms", type=float, default=None,
                    help="paged engine: force a decode launch when the gap "
                    "since the last one exceeds this")
    ap.add_argument("--reduced", action="store_true",
                    help="serve a small same-family config instead of the "
                    "published width")
    args = ap.parse_args(argv)

    enable_compile_cache()
    print("[serve] devices: {platform} {kind} x{count}".format(**device_info()))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    params = init_params(cfg, seed=0)
    slo = None
    if args.ttft_slo_ms is not None or args.decode_slo_ms is not None:
        slo = SLOConfig(
            ttft_slo_s=(args.ttft_slo_ms / 1e3
                        if args.ttft_slo_ms is not None else None),
            decode_slo_s=(args.decode_slo_ms / 1e3
                          if args.decode_slo_ms is not None else None),
        )
    engine = build_engine(
        cfg, params, engine=args.engine, pool=args.pool, max_len=args.max_len,
        prefill_chunk=args.prefill_chunk, block_size=args.block_size,
        num_blocks=args.num_blocks, slo=slo,
    )
    if args.engine == "paged":
        kv = (f"blocks={engine.num_blocks}x{engine.block_size}"
              if engine.allocator is not None else "no-kv(ssm)")
        print(f"[serve] {cfg.name}: {count_params(params):,} params, "
              f"paged width={args.pool}, max_len={args.max_len}, {kv}, "
              f"prefill_chunk={args.prefill_chunk}")
    else:
        print(f"[serve] {cfg.name}: {count_params(params):,} params, "
              f"slot pool={args.pool}, max_len={args.max_len}, "
              f"prefill_chunk={args.prefill_chunk}")
    lens = np.random.RandomState(0).randint(4, 12, size=args.requests)
    reqs = make_requests(cfg.vocab_size, lens, args.max_new, seed=0)
    result = serve(engine, reqs)
    _report(engine, reqs, result)


if __name__ == "__main__":
    main()
