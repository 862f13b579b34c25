"""Host dispatches of one stitched call (``CompileStats``: jitted replay
segments); moves ``call_ms``."""


def read(run):
    return run.get("counters", {}).get("dispatches")
