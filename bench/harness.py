"""What every cell shares: finding a cell's files by name, the peaks table,
the per-layer metric readers, percentiles and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``bench/configs/<config>.json`` and its traffic mix
``bench/traffic/<traffic>.json``; the mix names the driver
(``bench/<driver>_cell.py``) that runs it.  A per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding any of these means adding files and
entries, never editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(Exception):
    """The cell cannot run here: a missing file, chip or program."""


@dataclasses.dataclass
class Cell:
    name: str
    root: str                  # the checkout the cell's files come from
    chips: int
    config: dict
    traffic: dict
    per_layer: List[dict]      # the per-layer metrics this cell reports
    end_to_end: List[dict]     # the end-to-end metrics this cell reports


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}") from None


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return Cell(
        name=name, root=root, chips=w["chips"],
        config=_load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_load_json(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json")),
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
    )


def driver(cell: Cell):
    """The module that runs the cell's traffic mix."""
    return module(cell, f"{cell.traffic['driver']}_cell.py")


def module(cell: Cell, *parts: str):
    """The Python file ``bench/<parts>`` of the cell's checkout."""
    return _module(os.path.join(cell.root, "bench", *parts))


def _module(path: str):
    if not os.path.exists(path):
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error."""
    table = _load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def read_per_layer(cell: Cell, run: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell that its reader finds in ``run``."""
    out = {}
    for m in cell.per_layer:
        value = module(cell, "metrics", f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile, linear between order statistics (numpy's
    default)."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    if math.isinf(v[hi]):
        return v[hi] if pos > lo else v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def memory_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def check_lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]
