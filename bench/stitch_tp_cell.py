"""Driver of the stitch tensor-parallel cells: one plain-jnp per-chip
program from ``bench/programs`` compiled by ``repro.stitch(mesh=...)`` over
the cell's chips as a ``("model",)`` mesh, and called back to back.

It runs as ``stitch_cell.py`` does, with its window, sampling, trace
reduction and error helpers.  The arguments are made already placed on the
mesh by the program's ``specs``; ``jax.jit(shard_map(program))`` on the same
mesh is the ``vs_jit`` baseline.  The reference is ``jax.jit`` of the
program's global single-device math at HIGHEST precision on the same global
arrays, left to XLA's partitioner, so no chip ever holds the whole weights.

Besides ``stitch_cell``'s run keys, a traced run records ``mqa``: the names
the plan gives the kernels that read a K/V cache parameter
(``StitchedKernel.name``), their device seconds per stitched call, and the
least bytes they must read (the program's ``attention_bytes``); and
``collective``: the collective and exposed collective device seconds of
the stitched half, found by HLO opcode.
"""
from __future__ import annotations

import gc
import os
import re
import shutil
import time

import numpy as np

from bench import harness
from bench import trace as tr_mod
from bench.stitch_cell import SAMPLE, _program, _reduce, _window, max_rel_err

#: the layer parameters that hold the K/V caches
CACHE_KEYS = ("k", "v")


def cache_params(args) -> set:
    """Names the stitched plan gives the cache leaves of ``args``: its
    parameters are ``arg<i>`` over the flattened arguments."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    return {f"arg{i}" for i, (path, _) in enumerate(leaves)
            if getattr(path[-1], "key", None) in CACHE_KEYS}


def cache_kernels(st, params: set) -> list:
    """Names of the stitched kernels whose inputs include one of ``params``."""
    kernels = st.lower().compile().executable.kernels.values()
    return sorted({k.name for k in kernels
                   if any(i.name in params for i in k.inputs)})


def kernel_seconds(events: dict, names, window) -> float:
    """Device seconds, averaged over the chips, of the operations named in
    ``names`` inside ``window`` (the trace names an op ``<name> <shape>``)."""
    lo, hi = window
    names = set(names)
    per_chip = []
    for ops in events["device"].values():
        if ops:
            per_chip.append(sum(min(b, hi) - max(a, lo) for op, a, b in ops
                                if b > lo and a < hi and op.split(" ")[0] in names))
    return sum(per_chip) / len(per_chip) * 1e-9 if per_chip else 0.0


#: HLO opcodes of the operations that move data between chips
COLLECTIVE_OPS = re.compile(
    r"[\]})] (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start|-done)?\(")


def collective_seconds(log_dir: str, window) -> tuple:
    """(collective, exposed) device seconds inside ``window``, averaged over
    the chips, of the newest trace under ``log_dir``: an operation is a
    collective by its HLO opcode (XLA names an all-reduce after the JAX
    primitive, ``%psum.3 = ... all-reduce(...)``), and its exposed part is
    the time no other operation runs on that chip."""
    import glob

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    lo, hi = window
    coll = exposed = 0.0
    chips = 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        ops = [(bool(COLLECTIVE_OPS.search(e.name)), max(e.start_ns, lo),
                min(e.end_ns, hi))
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events if e.end_ns > lo and e.start_ns < hi]
        if not ops:
            continue
        chips += 1
        c_ops = tr_mod.Union((a, b) for c, a, b in ops if c)
        other = tr_mod.Union((a, b) for c, a, b in ops if not c)
        coll += c_ops.cum[-1]
        exposed += sum((b - a) - other.overlap(a, b) for a, b in c_ops.iv)
    return (coll / chips * 1e-9, exposed / chips * 1e-9) if chips else (0.0, 0.0)


def run(cell, seed, seconds, trace, devices, t0, hooks=None, control=False):
    """Run the cell once on ``devices``; the result as a dict.  ``hooks``
    may hold ``wrap``, which replaces the stitched call, and ``program``,
    which replaces the per-chip program, for the tests."""
    import jax
    from jax.sharding import Mesh

    from repro import StitchOptions, stitch

    hooks = hooks or {}
    prog = _program(cell)
    cfg, tr = cell.config, cell.traffic
    mesh = Mesh(np.array(devices), ("model",))
    in_specs, out_specs = prog.specs(cfg, tr)
    rng = np.random.default_rng(seed)
    with jax.default_matmul_precision("highest"):
        args = prog.make_args(cfg, tr, seed, tr["variants"], mesh=mesh)
        jax.block_until_ready(args)
        fn = hooks.get("program", prog.program)(cfg, tr)
        st = stitch(fn, options=StitchOptions(**tr.get("options", {})),
                    mesh=mesh, in_specs=in_specs, out_specs=out_specs)
        jit_fn = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                       out_specs=out_specs, check_vma=False))
        jax.block_until_ready(st(*args[0]))
        system = hooks.get("wrap", lambda f: f)(st)
        for a in args[:2]:
            jax.block_until_ready(system(*a))
        if trace:
            jax.block_until_ready(jit_fn(*args[0]))
        setup_s = time.perf_counter() - t0

        keep = (SAMPLE, [])
        run_info = None
        if not trace:
            calls, elapsed = _window(system, args, seconds, keep, rng)
        else:
            log_dir = os.path.join(harness.ROOT, "bench_out", "trace", cell.name)
            shutil.rmtree(log_dir, ignore_errors=True)
            opts_p = jax.profiler.ProfileOptions()
            opts_p.python_tracer_level = 0
            half = min(seconds, tr["trace_seconds"]) / 2
            jax.profiler.start_trace(log_dir, profiler_options=opts_p)
            calls, elapsed = _window(system, args, half, keep, rng, "stitch_call")
            jit_calls, _ = _window(jit_fn, args, half, None, rng, "jit_call")
            jax.profiler.stop_trace()
            events = tr_mod.load(log_dir, ("stitch_call", "jit_call"))
            run_info = _reduce(events)
        memory_peak = harness.memory_peak(devices)

        stats = st.stats
        counters = {
            "compile_time_s": stats.compile_time_s,
            "launches": stats.stitched_kernels + stats.standalone_kernels
            + stats.library_calls,
            "dispatches": stats.traced_dispatches_per_call,
            "fallbacks": st.num_fallbacks,
            "interpret": stats.interpret,
            "collectives": stats.collective_calls,
            "collective_bytes": getattr(stats, "collective_bytes", None),
        }
        mqa_names = cache_kernels(st, cache_params(args[0])) if trace else []
        del st, system, jit_fn
        gc.collect()

        ref_fn = jax.jit(prog.reference(cfg, tr))
        refs = {v: ref_fn(*args[v]) for v in sorted({v for v, _ in keep[1]})}
        err = max(max_rel_err(out, refs[v]) for v, out in keep[1])
        result_control = None
        if control:
            from bench.precision import dot_bf16x3, einsum_bf16x3

            ctl_fn = jax.jit(prog.reference(cfg, tr, dot=dot_bf16x3,
                                            einsum=einsum_bf16x3))
            result_control = max(max_rel_err(ctl_fn(*args[v]), r)
                                 for v, r in refs.items())

    limit = tr["limits"]["out_err"]
    checks = {
        "out_err": {"value": err, "limit": limit},
        "fallbacks": {"value": counters["fallbacks"], "limit": 0},
    }
    correct = err <= limit and counters["fallbacks"] == 0
    flops, nbytes = prog.cost(cfg, tr)
    res = {
        "correct": bool(correct), "attempted": calls, "failed": 0,
        "memory_peak_bytes": memory_peak, "checks": checks,
        "end_to_end": {"call_ms": elapsed * 1e3 / calls, "setup_s": setup_s},
        "control": result_control,
    }
    if trace:
        if run_info is None:
            raise harness.BenchError("no device operation ran in the traced window")
        half = run_info["trace"]
        st_spans = [s for s in events["spans"] if s[0] == "stitch_call"]
        window = (st_spans[0][1], st_spans[-1][2])
        run_info.update(
            counters=counters, flops=flops, bytes=nbytes, chips=cell.chips,
            peaks=harness.peaks(devices[0].device_kind), jit_calls=jit_calls,
            mqa={"kernels": mqa_names, "bytes": prog.attention_bytes(cfg, tr),
                 "kernel_s": kernel_seconds(events, mqa_names, window)
                 / len(half["stitch_call_s"])},
            collective=dict(zip(("collective_s", "exposed_s"),
                                collective_seconds(log_dir, window), strict=True)),
        )
        res["run"] = run_info
    return res
