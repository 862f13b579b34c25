"""End-to-end serving driver: continuous batching through the paged engine.

The paper's NMT use case — latency-critical online inference with small
batches — mapped onto our serving substrate: a small decoder LM with the
attention pattern the stitched kernels accelerate, continuous batching
over paged KV blocks, greedy decode.  Twenty requests share a KV pool
sized for far fewer worst-case contexts; the block allocator and the
prefill/decode scheduler keep them all moving at once, where the old
slot engine would cap concurrency at its pool size.

    PYTHONPATH=src python examples/serve_nmt.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.models import init_params
from repro.serve import PagedServeEngine, Request


def main():
    # small qwen-family decoder (the NMT-attention pattern)
    cfg = reduced_config(
        get_config("qwen1.5-0.5b"), num_layers=4, d_model=128,
        num_heads=4, head_dim=32, d_ff=256, vocab_size=512,
    )
    params = init_params(cfg, seed=0)
    # 64 blocks x 8 tokens = 512 KV tokens total — the old slot engine's
    # budget for FOUR max_len=128 slots now serves ~20 short requests
    engine = PagedServeEngine(
        cfg, params, decode_width=16, max_len=128, block_size=8,
        num_blocks=64, prefill_chunk=8,
    )

    rng = np.random.RandomState(0)
    requests = [
        Request(rid=i, prompt=rng.randint(1, 500, size=rng.randint(4, 12)),
                max_new_tokens=12)
        for i in range(20)
    ]

    t0 = time.perf_counter()
    done = []
    ticks = 0
    # admit everything up front: placements claim a decode row + KV blocks
    # immediately (prefill itself runs interleaved over the next ticks);
    # overflow parks on the FIFO wait queue and drains as blocks free up
    for r in requests:
        placed = engine.admit(r)
        print(f"[admit] request {r.rid} (prompt {len(r.prompt)} toks) "
              f"{'-> row' if placed else '-> queued'}")
    while engine.busy and ticks < 2000:
        engine.tick()
        ticks += 1
        for r in requests:
            if r.done and r not in done:
                done.append(r)
                print(f"[done ] request {r.rid}: {r.out_tokens} "
                      f"(wait {1e3 * (r.queue_wait_s or 0):.0f}ms, "
                      f"ttft {1e3 * (r.ttft_s or 0):.0f}ms, "
                      f"{r.tokens_per_s or 0:.1f} tok/s)")
    dt = time.perf_counter() - t0
    total_toks = sum(len(r.out_tokens) for r in requests)
    st = engine.stats()
    kv = st["kv_blocks"]
    print(f"\nserved {len(done)}/{len(requests)} requests, "
          f"{total_toks} tokens in {dt:.2f}s "
          f"({total_toks / dt:.1f} tok/s on {jax.devices()[0].device_kind}, "
          f"width=16, {kv['num_blocks']}x{kv['block_size']}-token blocks)")
    print(f"prefill launches: {st['prefill_launches']} for "
          f"{st['prefill_tokens']} prompt tokens; "
          f"decode launches: {st['decode_launches']}; "
          f"max in-flight: {st['max_inflight']} "
          f"(slot engine with this KV budget caps at 4); "
          f"kv peak {kv['peak_in_use']}/{kv['num_blocks']} blocks, "
          f"preemptions {st['preemptions']}")
    assert len(done) == len(requests)
    assert st["max_inflight"] > 4      # the continuous-batching win
    assert kv["in_use"] == 0           # every block returned


if __name__ == "__main__":
    main()
