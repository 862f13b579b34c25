"""Kernels one stitched call launches: stitched + standalone + library
(``CompileStats``); moves ``call_ms``."""


def read(run):
    return run.get("counters", {}).get("launches")
