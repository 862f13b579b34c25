"""Seconds the process spent in the first call of each replay segment,
where JAX traces the segment's inlined kernels and XLA and Mosaic compile
it: the program's ``repro.replay_build`` spans, from
``repro.tracing.totals()``; moves ``setup_s``."""


def read(run):
    try:
        from repro.tracing import totals
    except ImportError:
        return None
    found = totals().get("repro.replay_build")
    return found[1] if found else None
