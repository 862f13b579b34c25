"""Seconds the process spent capturing the stitched program: the program's
``repro.trace`` (``jax.make_jaxpr``) and ``repro.lower`` (jaxpr to
StitchIR) spans, from ``repro.tracing.totals()``; moves ``setup_s``."""

SPANS = ("repro.trace", "repro.lower")


def read(run):
    try:
        from repro.tracing import totals
    except ImportError:
        return None
    t = totals()
    found = [t[n][1] for n in SPANS if n in t]
    return sum(found) if found else None
