#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

``NAME`` is an entry of ``workloads`` in ``BENCHMARK.json``.  Set-up makes
every input and weight on the device from ``--seed``, compiles and warms up
the cell's shapes; the window then measures for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Afterwards the outputs of the timed path are compared with a plain
reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``: each number compared with its limit (also the last
lines of stderr).  Without a TPU, with fewer chips than the cell asks for,
or without the program beside the benchmark, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_error(cell: harness.Cell):
    """The first ``cell.chips`` TPU devices, or BenchError."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.BenchError(
            f"no TPU: JAX runs on {devices[0].platform!r}; nothing was run")
    if len(devices) < cell.chips:
        raise harness.BenchError(
            f"the cell needs {cell.chips} chips, JAX sees {len(devices)}")
    return devices[:cell.chips]


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devices, t0: float, hooks=None) -> dict:
    """Run ``cell`` on ``devices``; the result line as a dict.  ``hooks``
    plant faults for the tests (see the drivers)."""
    import jax

    try:
        from repro.launch.runtime import enable_compile_cache
    except ImportError as e:
        raise harness.BenchError(f"the program is not beside the benchmark ({e})") from None
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    res = harness.driver(cell).run(cell, seed=seed, seconds=seconds,
                                   trace=trace, devices=devices, t0=t0,
                                   hooks=hooks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    if trace:
        metrics = harness.read_per_layer(cell, res["run"])
        red = res["run"]["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": red["ops"], "idle_gaps": red["idle_gaps"]}
    line["checks"] = res["checks"]
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = chips_or_error(cell)
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        devices, T0)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for text in harness.check_lines(line["checks"]):
        print(text, file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
