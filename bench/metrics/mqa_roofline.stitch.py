"""Share of the roofline that the kernels reading the K/V caches reach in
a stitched call, in %: the least time to read their bytes (the program's
``attention_bytes`` over one chip's HBM bandwidth) over their device time
per call in the stitched half of the trace, averaged over the chips.  The
driver finds the kernels by the names the plan gives them; moves
``call_ms``."""


def read(run):
    mqa = run.get("mqa")
    if not mqa or not mqa["kernels"] or mqa["kernel_s"] <= 0:
        return None
    least = mqa["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / mqa["kernel_s"]
