#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from: the program's
number and its control's, seed by seed, in one process.

    python3 bench/calibrate.py --workload NAME --seconds S --seeds 1,2,3

For each seed the cell runs once as ``bench/run.py`` runs it (window of
``--seconds``), then its control is computed on the same inputs: the plain
reference one step of precision below the configuration's (three bfloat16
passes for float32 at HIGHEST).  One JSON line per
seed; the last line has the largest program reading and the smallest
control reading.  The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402
from bench.run import chips_or_error  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        devices = chips_or_error(cell)
        import jax
        from repro.launch.runtime import enable_compile_cache
    except (harness.BenchError, ImportError) as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    prog, ctl = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.driver(cell).run(cell, seed=seed, seconds=args.seconds,
                                       trace=False, devices=devices, t0=t0,
                                       control=True)
        name = next(iter(res["checks"]))
        value = res["checks"][name]["value"]
        prog.append(value)
        ctl.append(res["control"])
        print(json.dumps({"seed": seed, "number": name, "program": value,
                          "control": res["control"], "correct": res["correct"],
                          "end_to_end": res["end_to_end"],
                          "attempted": res["attempted"], "failed": res["failed"]}),
              flush=True)
    print(json.dumps({"program_max": max(prog), "control_min": min(ctl)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
