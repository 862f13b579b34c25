"""Exposed collective time of the stitched half of the traced window over
its device-busy time, per chip, in %: the time in which a collective ran
and no other operation did on that chip (the driver's ``collective``,
operations classed by HLO opcode), over the half's busy time, both
averaged over the chips; moves ``call_ms``."""


def read(run):
    half, coll = run.get("trace"), run.get("collective")
    if not half or coll is None or half.get("busy_s", 0) <= 0:
        return None
    return 100.0 * coll["exposed_s"] / half["busy_s"]
