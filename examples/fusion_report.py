"""Fusion report: run the FusionStitching compiler over all six paper
benchmark graphs and print the per-workload plan (kernels, schedules,
VMEM scratch, sharing) — the compiler's explain-mode.

    PYTHONPATH=src python examples/fusion_report.py
"""
import os
import sys

# the benchmark graph registry, tests/graphs.py
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

from graphs import ALL_GRAPHS
from repro.core import StitchOptions, compile_module


def main():
    for name, build in ALL_GRAPHS.items():
        module = build()
        comp = compile_module(module, StitchOptions(max_blocks=64))
        s = comp.stats
        print(f"=== {name}: {len(module.instructions)} instrs -> "
              f"{s.stitched_kernels} stitched + {s.standalone_kernels} standalone "
              f"(+{s.library_calls} library) | XLA baseline {s.xla_baseline_kernels} "
              f"| ratio {s.fusion_ratio:.3f}")
        print(f"    kernel cache: {s.unique_kernels} unique kernels for "
              f"{s.stitched_kernels} fusions ({s.kernel_cache_hits} hits, "
              f"hit rate {s.cache_hit_rate:.0%}) | compile "
              f"{s.compile_time_s * 1e3:.1f}ms "
              + " ".join(f"{k}={v * 1e3:.1f}ms" for k, v in s.pass_times.items()))
        print(f"    verify[{s.verify_mode}]: {s.verify_boundaries} boundaries, "
              f"{s.verify_warnings} warnings, {s.verify_time_s * 1e3:.1f}ms")
        print(f"    planner[{s.planner_mode}]: {s.plans_explored} plans explored "
              f"({s.plans_rejected} infeasible), {s.planner_splits} splits, "
              f"{s.planner_merges} merges, {s.planner_packs} packs, "
              f"{s.planner_stitches} stitches | modeled "
              f"{s.planner_predicted_s * 1e6:.2f}us vs greedy "
              f"{s.greedy_predicted_s * 1e6:.2f}us | launches saved: "
              f"{s.launches_saved_vs_greedy} vs greedy, "
              f"{s.launches_saved_vs_unfused} vs unfused")
        if s.stitch_lowered_kernels:
            print(f"    stitched lowering: {s.stitch_lowered_kernels} kernels, "
                  f"{s.stitch_phases_total} phases, "
                  f"{s.stitch_interface_bytes}B staged interfaces")
        for r in s.reports:
            shared = f", {r.shared_bytes}B shared" if r.shared_bytes else ""
            shrunk = f", {r.num_shrinks} shrinks" if r.num_shrinks else ""
            cached = "  [cached]" if r.cached else ""
            phases = f"  phases={r.num_phases}" if r.num_phases > 1 else ""
            print(f"    {r.name}: {r.num_ops:3d} ops  blocks={r.blocks:<4d} "
                  f"scratch={r.scratch_bytes}B{shared}{shrunk}{phases}  "
                  f"roots={','.join(r.roots)}{cached}")


if __name__ == "__main__":
    main()
