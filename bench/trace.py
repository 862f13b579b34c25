"""Reduction of a profiler trace to device busy time, idle gaps, per-op
device time and collective time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
event list: the device operations of each chip and the host spans that the
benchmark opened with ``jax.profiler.TraceAnnotation``.  ``reduce`` works on
that list alone, so a small recorded list checks it without a chip.
All times are in nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: device operations that move data between chips
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather"
)


def op_name(hlo: str) -> str:
    """A short stable name for a device op from its HLO text:
    ``"%copy.12 = f32[8,128]{1,0} copy(...)"`` -> ``"copy f32[8,128]"``."""
    head, _, rest = hlo.partition(" = ")
    head = re.sub(r"\.\d+$", "", head.lstrip("%"))
    shape = re.match(r"[^{ ]*", rest.lstrip("(")).group(0) if rest else ""
    return f"{head} {shape}".strip()


def load(log_dir: str, span_names: Iterable[str]) -> dict:
    """Events of the newest trace under ``log_dir``:
    ``{"device": {chip: [[op, start, end], ...]}, "spans": [[name, start, end], ...]}``.

    Device operations are the events of each ``/device:TPU:<n>`` plane's
    ``XLA Ops`` line; spans are host events whose name is in ``span_names``.
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    names = set(span_names)
    device: Dict[str, List[list]] = {}
    spans: List[list] = []
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend([op_name(e.name), e.start_ns, e.end_ns]
                               for e in line.events)
            device[m.group(1)] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events if e.name in names)
    spans.sort(key=lambda s: s[1])
    return {"device": device, "spans": spans}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


class Union:
    """Disjoint sorted intervals with prefix sums, for fast overlap queries."""

    def __init__(self, intervals: Iterable[Interval]):
        self.iv = merge(intervals)
        self.starts = [a for a, _ in self.iv]
        self.cum = [0.0]
        for a, b in self.iv:
            self.cum.append(self.cum[-1] + (b - a))

    def _covered_before(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        a, b = self.iv[i - 1]
        return self.cum[i - 1] + min(b, t) - a

    def overlap(self, lo: float, hi: float) -> float:
        """Length of the union inside ``[lo, hi]``."""
        return max(0.0, self._covered_before(hi) - self._covered_before(lo))


def self_times(ops: Sequence[list]) -> List[Tuple[str, float]]:
    """Each op's time less that of the ops wholly nested inside it (a
    ``while`` holds its body's ops on the same trace line); ops that only
    overlap, as asynchronous ones do, keep their whole time."""
    out: List[List] = []
    stack: List[int] = []
    for name, a, b in sorted(ops, key=lambda op: (op[1], -op[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= out[stack[-1]][2]:
            out[stack[-1]][1] -= b - a
        out.append([name, b - a, b])
        stack.append(len(out) - 1)
    return [(name, t) for name, t, _ in out]


def _span_at(spans: Sequence[list], starts: Sequence[float], t: float) -> str:
    """The innermost host span open at ``t`` (spans sorted by start)."""
    i = bisect.bisect_right(starts, t)
    for name, lo, hi in reversed(spans[max(0, i - 8):i]):
        if lo <= t <= hi:
            return name
    return "outside any span"


def reduce(events: dict, window: Optional[Interval] = None,
           top: int = 10) -> Optional[dict]:
    """Busy and idle time of the chips inside ``window`` (default: from the
    first span's start to the last span's end).

    Returns None when no device operation ran in the window.  Otherwise:
    ``busy_s`` and ``window_s`` (busy averaged over the chips), ``ops`` (the
    ``top`` operations by device seconds per chip, each less the ops nested
    in it), ``idle_gaps`` (idle
    seconds per chip by the host span open in the middle of each gap),
    ``collective_s`` and ``exposed_collective_s`` (collective time, and the
    part with no other operation running on that chip) and ``span_busy_s``
    (per span, the device-busy seconds inside it, averaged over chips).
    """
    spans = events["spans"]
    if window is None:
        if not spans:
            return None
        window = (min(s[1] for s in spans), max(s[2] for s in spans))
    lo, hi = window
    chips = {c: [op for op in ops if op[2] > lo and op[1] < hi]
             for c, ops in events["device"].items() if ops}
    if not any(chips.values()) or hi <= lo:
        return None
    n = len(chips)
    busy = 0.0
    coll = exposed = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    span_busy = [0.0] * len(spans)
    starts = [s[1] for s in spans]
    for ops in chips.values():
        clipped = [(name, max(a, lo), min(b, hi)) for name, a, b in ops]
        clipped.sort(key=lambda op: op[1])
        merged = Union((a, b) for _, a, b in clipped)
        busy += merged.cum[-1]
        for name, t in self_times(clipped):
            op_time[name] += t
        is_coll = [bool(COLLECTIVE.search(name)) for name, _, _ in clipped]
        c_ops = Union((a, b) for (_, a, b), c in zip(clipped, is_coll, strict=True) if c)
        other = Union((a, b) for (_, a, b), c in zip(clipped, is_coll, strict=True) if not c)
        coll += c_ops.cum[-1]
        exposed += sum((b - a) - other.overlap(a, b) for a, b in c_ops.iv)
        edges = [lo] + [t for iv in merged.iv for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2], strict=True):
            if b > a:
                gaps[_span_at(spans, starts, (a + b) / 2)] += b - a
        for i, (_, s_lo, s_hi) in enumerate(spans):
            span_busy[i] += merged.overlap(s_lo, s_hi)
    ns = 1e-9 / n
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy * ns,
        "window_s": (hi - lo) * 1e-9,
        "chips": n,
        "ops": [[k, v * ns] for k, v in ranked],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "collective_s": coll * ns,
        "exposed_collective_s": exposed * ns,
        "span_busy_s": [[s[0], b * ns] for s, b in zip(spans, span_busy, strict=True)],
    }
