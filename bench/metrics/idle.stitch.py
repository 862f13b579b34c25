"""Share of the stitched half of the traced window in which no operation
ran on the chips, in %; moves ``call_ms``."""


def read(run):
    half = run.get("trace")
    if not half or "stitch_call_s" not in half or half["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - half["busy_s"] / half["window_s"])
