"""Share of the roofline that a stitched call reaches, in %: the least time
the chip needs for the program's work (the larger of its FLOPs over the
bf16 peak and its least bytes over HBM bandwidth, per chip, from
``bench/programs``) over the call's mean device-busy time in the trace.
The same work whatever plan implements it; moves ``call_ms``."""


def read(run):
    tr = run.get("trace") or {}
    if "flops" not in run or "span_busy_s" not in tr:
        return None
    busy = [b for n, b in tr["span_busy_s"] if n == "stitch_call"]
    if not busy or sum(busy) <= 0:
        return None
    pk = run["peaks"]
    least = max(run["flops"] / pk["bf16_flops_per_s"],
                run["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (sum(busy) / len(busy))
