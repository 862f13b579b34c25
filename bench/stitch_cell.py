"""Driver of the stitch cells: one plain-jnp program from ``bench/programs``
compiled by ``repro.stitch`` and called back to back.

Set-up makes ``variants`` argument sets on the device (they differ in the
activations), compiles the program and calls it three times.  The window calls it
on the sets in turn, each call ended by ``block_until_ready``; ``call_ms``
is the window over the calls.  A traced run spends the first half of the
window on stitched calls and the second on ``jax.jit`` of the same program,
each call inside a ``TraceAnnotation``; it traces ``trace_seconds`` of the
mix at most, since a trace grows by about 100,000 device operations a
second.  After the window, a sample of the
timed calls' outputs, drawn from the seed, is compared with the plain
reference at HIGHEST precision.
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from bench import harness
from bench import trace as tr_mod

#: outputs of timed calls kept for the comparison
SAMPLE = 8


def _program(cell):
    return harness.module(cell, "programs", f"{cell.traffic['program']}.py")


def max_rel_err(out, ref) -> float:
    """Largest ``max|out - ref| / max|ref|`` over the output leaves."""
    import jax
    import jax.numpy as jnp

    errs = []
    for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(ref), strict=True):
        if o.shape != r.shape:
            return float("inf")
        d = jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32)))
        errs.append(float(d / jnp.maximum(jnp.max(jnp.abs(r)), 1e-30)))
    return max(errs)


def _window(call, args, seconds, keep, rng, name=None):
    """Call back to back for ``seconds``; reservoir-sample ``keep`` outputs
    (by seeded ``rng``) as (variant, output).  Returns (calls, elapsed)."""
    import jax

    calls = 0
    t_start = time.perf_counter()
    while True:
        v = calls % len(args)
        if name is None:
            out = jax.block_until_ready(call(*args[v]))
        else:
            with jax.profiler.TraceAnnotation(name):
                out = jax.block_until_ready(call(*args[v]))
        if keep is not None:
            if len(keep[1]) < keep[0]:
                keep[1].append((v, out))
            else:
                j = int(rng.integers(0, calls + 1))
                if j < keep[0]:
                    keep[1][j] = (v, out)
        calls += 1
        if time.perf_counter() - t_start >= seconds:
            return calls, time.perf_counter() - t_start


def run(cell, seed, seconds, trace, devices, t0, hooks=None, control=False):
    """Run the cell once on ``devices[0]``; the result as a dict.  ``hooks``
    may hold ``wrap``, which replaces the stitched call for the tests."""
    import jax

    from repro import StitchOptions, stitch

    hooks = hooks or {}
    prog = _program(cell)
    cfg, tr = cell.config, cell.traffic
    rng = np.random.default_rng(seed)
    with jax.default_matmul_precision("highest"):
        args = prog.make_args(cfg, tr, seed, tr["variants"])
        jax.block_until_ready(args)
        fn = prog.program(cfg, tr)
        st = stitch(fn, options=StitchOptions(**tr.get("options", {})))
        jit_fn = jax.jit(fn)
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
        }
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
        run_info.update(
            counters=counters, flops=flops, bytes=nbytes, chips=cell.chips,
            peaks=harness.peaks(devices[0].device_kind), jit_calls=jit_calls,
        )
        res["run"] = run_info
    return res


def _reduce(events: dict):
    """The stitched half of the traced window (busy time, breakdown), with
    the jit half and the spans of both for the comparison."""
    spans = events["spans"]
    st = [s for s in spans if s[0] == "stitch_call"]
    jt = [s for s in spans if s[0] == "jit_call"]
    if not st or not jt:
        return None
    half_st = tr_mod.reduce(events, (st[0][1], st[-1][2]))
    half_jt = tr_mod.reduce(events, (jt[0][1], jt[-1][2]))
    if half_st is None or half_jt is None:
        return None
    half_st["span_busy_s"] = (
        [x for x in half_st["span_busy_s"] if x[0] == "stitch_call"]
        + [x for x in half_jt["span_busy_s"] if x[0] == "jit_call"])
    half_st["stitch_call_s"] = [(s[2] - s[1]) * 1e-9 for s in st]
    return {"trace": half_st}
