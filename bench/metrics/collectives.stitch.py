"""All-reduces and other collectives one stitched call runs on each chip
(``CompileStats.collective_calls``); moves ``call_ms``."""


def read(run):
    return run.get("counters", {}).get("collectives")
