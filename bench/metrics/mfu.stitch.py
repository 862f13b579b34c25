"""The whole stitched call's share of the chips' bf16 peak, in %: the
program's FLOPs per chip over the mean host-clock time of a traced call
over one chip's peak (v5e publishes no f32 peak); moves ``call_ms``."""


def read(run):
    tr = run.get("trace") or {}
    calls = tr.get("stitch_call_s")
    if "flops" not in run or not calls:
        return None
    t = sum(calls) / len(calls)
    return 100.0 * run["flops"] / t / run["peaks"]["bf16_flops_per_s"]
