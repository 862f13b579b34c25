"""Host time of one stitched call inside the program, in ms: the mean
length of the program's ``repro.call`` spans that lie inside the
benchmark's ``stitch_call`` spans of the traced window
(``bench/program_spans.py``); moves ``call_ms``."""
from bench import program_spans


def mean_ms(events):
    """Mean ``repro.call`` length inside ``stitch_call`` spans, or None."""
    calls = program_spans.inside(events["spans"], "stitch_call", "repro.call")
    if not calls:
        return None
    return sum(hi - lo for _, lo, hi in calls) / len(calls) * 1e-6


def read(run):
    if "trace" not in run:
        return None
    events = program_spans.events()
    return None if events is None else mean_ms(events)
