"""Device time of a stitched call over that of ``jax.jit`` of the same
program, both from the trace: the mean device-busy seconds inside the
``stitch_call`` spans over those inside the ``jit_call`` spans; moves
``call_ms``."""


def _mean_busy(trace, name):
    v = [b for n, b in trace["span_busy_s"] if n == name]
    return sum(v) / len(v) if v else None


def read(run):
    tr = run.get("trace") or {}
    if "span_busy_s" not in tr:
        return None
    st, jt = _mean_busy(tr, "stitch_call"), _mean_busy(tr, "jit_call")
    if not st or not jt:
        return None
    return st / jt
