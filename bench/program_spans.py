"""The program's own spans in the newest traced window, for the per-layer
metrics that read them.

The program opens ``repro.*`` spans (``repro.tracing``) on the profiler's
clock.  ``events`` loads the newest ``.xplane.pb`` under
``bench_out/trace/`` through ``bench/trace.py``'s ``load``, with those names
and the benchmark's ``stitch_call``, once per file: the result is kept by
path and modification time.  A program that opens no spans gives events
without them, and ``inside`` then finds none.
"""
from __future__ import annotations

import glob
import os
from typing import List, Optional

from bench import trace as tr_mod

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(BENCH), "bench_out", "trace")

#: the spans loaded: the benchmark's call span and the program's spans of a call
NAMES = ("stitch_call", "repro.call", "repro.prepare", "repro.bind",
         "repro.dispatch", "repro.replay_build")

_loaded: dict = {}      # (path, mtime) -> events


def events(trace_dir: str = TRACE_DIR) -> Optional[dict]:
    """Events of the newest trace under ``trace_dir``, or None if none."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = tr_mod.load(os.path.dirname(path), NAMES)
    return _loaded[key]


def inside(spans: List[list], outer: str, name: str) -> List[list]:
    """The spans called ``name`` that lie wholly inside a span called
    ``outer``."""
    outs = [(lo, hi) for n, lo, hi in spans if n == outer]
    return [s for s in spans if s[0] == name
            and any(lo <= s[1] and s[2] <= hi for lo, hi in outs)]
