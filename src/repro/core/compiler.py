"""The FusionStitching compiler facade — paper Fig. 4.

The actual pipeline (deep fusion -> schedule tuning -> memory planning ->
code generation, with the memory feedback loop of §5.1.2 and
fusion-signature kernel deduplication) lives in ``pipeline.py`` as explicit
passes over a ``CompilationState``.  ``compile_module`` stays the one-call
entry point: it builds the state, runs the default pass pipeline, and
returns a ``CompiledModule`` wrapping the planned executable and stats.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..tracing import span
from .codegen import StitchedKernel, resolve_interpret, stamp_native_layouts
from .executor import LaunchStats, StitchedExecutable
from .fusion import FusionPlan
from .memory import SCOPED_VMEM_BYTES
from .perf_library import PerfLibrary
from .pipeline import CompilationState, default_pipeline
from .signature import KernelCache
from .xla_baseline import xla_baseline_kernel_count


@dataclass
class StitchOptions:
    fuse_dot: bool = True                    # user decision (paper §2.1)
    # VMEM budget per kernel: double-buffered I/O blocks + scratch
    vmem_limit: int = SCOPED_VMEM_BYTES
    replicate_limit: int = 512 * 1024
    max_blocks: int = 4096
    ew_footprint_limit: int = 64 * 1024 * 1024
    max_fusion_ops: int = 256
    perf_library_path: Optional[str] = None
    kernel_cache_path: Optional[str] = None  # persistent tuning records
    dedup_kernels: bool = True               # fusion-signature kernel reuse
    # Pallas interpreter: None = exactly when the backend is not a TPU
    # (resolved at compile time); True forces it, False forbids it.
    interpret: Optional[bool] = None
    # "cost": candidate-plan exploration under the shared LatencyModel with
    # the greedy result as the floor; "greedy": the paper's Algorithm 1.
    planner: str = "cost"
    # Multi-phase stitching (arXiv:1911.11576 / 2009.10924): groups with no
    # single consistent schedule lower as ONE kernel of sequential phases
    # stitched through full VMEM staging buffers, and the planner may pack
    # independent same-layer sink towers into one kernel.  Effective only
    # with planner="cost" — planner="greedy" stays the paper's hard veto.
    enable_stitching: bool = True
    # Replicate limit inside stitched phases (None = vmem_limit): a phase's
    # working set lives in VMEM staging, so replication is bounded by the
    # stitched memory plan rather than the per-block limit above.
    stitch_replicate_limit: Optional[int] = None
    # Cap on any ONE phase's grid: phases lower as sequential (trace-time
    # unrolled) loops inside the kernel, so this bounds emitted code size.
    stitch_max_blocks: int = 64
    # Runtime replay mode: True routes CompiledModule calls through the
    # single-dispatch traced ExecutionPlan (jax.jit of the pre-bound step
    # loop, released slots donated); False keeps the eager per-step loop.
    # Runtime-only — deliberately NOT part of the kernel-cache options
    # fingerprint (it changes how a plan is replayed, never what is
    # tuned/emitted).
    jit_replay: bool = True
    # Measured-cost autotuning (core/measure.py).  autotune=True times each
    # unique emitted kernel (warmup + median-of-measure_repeats) and files
    # the result in a MeasuredCostStore; the planner prefers stored
    # measurements over the analytic LatencyModel whenever a key hits.
    # tuning_store_path persists the store as JSON beside the kernel-cache
    # records; setting only the path reads an existing store without taking
    # new measurements.  All three salt the kernel-cache options fingerprint.
    autotune: bool = False
    measure_repeats: int = 5
    tuning_store_path: Optional[str] = None
    # Shard-aware compilation: the (axis name, size) shape of the mesh the
    # plan targets, e.g. (("data", 2), ("model", 4)).  Hashable on purpose —
    # it salts the options fingerprint and the measured-store keys, while
    # the live Mesh object (runtime-only) is passed to ``compile_module``
    # separately, like ``donate_params``.  None = single-device compile;
    # every pre-existing cache key stays byte-identical.
    mesh_axes: Optional[Tuple[Tuple[str, int], ...]] = None
    # Pass-boundary verification (core/verify.py): "off" = no checks at
    # all, "checkpoint" (default) = verify the finished artifact once after
    # FinalizePass, "strict" = verify after every pass so a violation names
    # the pass that introduced it.  The REPRO_VERIFY environment variable
    # overrides this at compile time (CI forces strict without touching
    # call sites).  Runtime/compile-policy only — like ``jit_replay``,
    # deliberately NOT part of the kernel-cache options fingerprint (it
    # changes what gets checked, never what is tuned or emitted).
    verify: str = "checkpoint"

    VALID_PLANNERS = ("cost", "greedy")
    VALID_VERIFY = ("off", "checkpoint", "strict")

    def __post_init__(self):
        self.validate()

    def validate(self) -> None:
        """Reject option values the pipeline would otherwise misread (an
        unknown planner string used to silently behave as "greedy")."""
        if self.planner not in self.VALID_PLANNERS:
            raise ValueError(
                f"unknown planner {self.planner!r}; valid choices: "
                f"{', '.join(self.VALID_PLANNERS)}"
            )
        if self.verify not in self.VALID_VERIFY:
            raise ValueError(
                f"unknown verify level {self.verify!r}; valid choices: "
                f"{', '.join(self.VALID_VERIFY)}"
            )
        for name in ("vmem_limit", "replicate_limit", "max_blocks",
                     "ew_footprint_limit", "max_fusion_ops",
                     "stitch_max_blocks"):
            v = getattr(self, name)
            if v < 0:
                raise ValueError(f"{name} must be >= 0, got {v}")
        if self.stitch_replicate_limit is not None and self.stitch_replicate_limit < 0:
            raise ValueError(
                f"stitch_replicate_limit must be >= 0 (or None), got "
                f"{self.stitch_replicate_limit}"
            )
        if self.measure_repeats < 1:
            raise ValueError(
                f"measure_repeats must be >= 1, got {self.measure_repeats}"
            )
        if self.mesh_axes is not None:
            for entry in self.mesh_axes:
                name, size = entry
                if not isinstance(name, str) or int(size) < 1:
                    raise ValueError(
                        f"mesh_axes entries must be (name, size>=1) pairs, "
                        f"got {entry!r}"
                    )


@dataclass
class FusionReport:
    name: str
    num_ops: int
    blocks: int
    cost_s: float
    scratch_bytes: int
    shared_bytes: int
    num_shrinks: int
    roots: List[str]
    cached: bool = False                     # kernel reused via signature
    signature: str = ""
    num_phases: int = 1                      # >1 = multi-phase stitched kernel
    interface_bytes: int = 0                 # staged phase-boundary buffers
    # cost provenance (frontend ``Lowered.cost_estimate``): the analytic
    # LatencyModel seconds, and the on-device measurement when the tuning
    # store had (or autotune took) one — ``cost_s`` above is whichever of
    # the two the planner acted on.
    model_cost_s: Optional[float] = None
    measured_cost_s: Optional[float] = None


@dataclass
class CompileStats:
    stitched_kernels: int
    standalone_kernels: int
    library_calls: int
    xla_baseline_kernels: int
    predicted_time_s: float
    library_time_s: float = 0.0
    reports: List[FusionReport] = field(default_factory=list)
    # sub-module (loop body) accounting: ``call`` loop sites in the module,
    # unique bodies compiled (after module-signature dedup), call sites
    # served by an already-compiled body, and the total kernels inside all
    # unique bodies (recursive) — fusion_ratio counts them as ours.
    loop_calls: int = 0
    sub_compiles: int = 0
    sub_call_sites: int = 0
    sub_kernels: int = 0
    # kernel-dedup + pipeline accounting
    kernel_cache_hits: int = 0               # fusion instances served by cache
    kernel_cache_misses: int = 0             # unique fusions tuned this compile
    tuning_disk_hits: int = 0                # tuning searches skipped (warm disk)
    unique_kernels: int = 0                  # distinct kernels backing the fusions
    kernels_emitted: int = 0                 # Pallas kernels emitted THIS compile
    compile_time_s: float = 0.0
    pass_times: Dict[str, float] = field(default_factory=dict)
    # fusion-planner accounting (core/fusion.py PlannerStats)
    planner_mode: str = "greedy"
    plans_explored: int = 0                  # candidate partitions scored
    plans_rejected: int = 0                  # candidates with no feasible plan
    planner_splits: int = 0                  # seeds committed non-greedily
    planner_merges: int = 0                  # horizontal merges applied
    planner_packs: int = 0                   # sink groups committed as one kernel
    planner_stitches: int = 0                # groups committed as multi-phase
    # stitched-lowering accounting (the README "stitching counters")
    stitch_lowered_kernels: int = 0          # instances using the stitched emitter
    stitch_phases_total: int = 0             # sum of phases over stitched instances
    stitch_interface_bytes: int = 0          # staged interface bytes, all instances
    planner_predicted_s: float = 0.0         # modeled latency, committed plan
    # "greedy" here = the planner's same-regime floor (see PlannerStats);
    # on stitched graphs it differs from a paper-exact planner="greedy" run
    greedy_predicted_s: float = 0.0          # modeled latency, floor plan
    greedy_kernels: int = 0                  # launches the floor plan needs
    planner_kernels: int = 0                 # fusion-pass view, pre-demotion
    unfused_kernels: int = 0                 # launches with no fusion at all
    # runtime-replay accounting (ExecutionPlan): the eager loop dispatches
    # one XLA call per pre-bound step; the traced replay dispatches one per
    # jitted segment (segments break only where XLA could alter a library
    # dot's accumulation order — 1 segment for most graphs).
    replay_mode: str = "jit"                 # "jit" | "eager"
    eager_dispatches_per_call: int = 0       # steps the eager loop runs
    traced_dispatches_per_call: int = 1      # jitted replay segments
    donated_buffers: int = 0                 # dead segment inputs donated
    # measured-cost autotuning accounting (core/measure.py): store lookups
    # THIS compile (scorer candidates + schedule-pass entries), kernels
    # timed on device this compile, and the analytic model's mean relative
    # error over every entry with both costs known.  None = no entry had a
    # measurement (autotune off, or fully cold with measurement disabled).
    measured_hits: int = 0
    measured_misses: int = 0
    measurements_taken: int = 0
    model_error_pct: Optional[float] = None
    # Shard-aware compilation accounting (zero on single-device compiles):
    # collective steps in the plan (ICI traffic — counted apart from kernels
    # and library calls), the bytes each chip hands them per call (their
    # per-shard operands), their modeled wire time, how many of them sit
    # BETWEEN two stitched kernels (compute fused on both sides of the
    # break — the tentpole's acceptance metric), and how many instructions
    # carry a non-trivial shard layout.
    collective_calls: int = 0
    collective_bytes: int = 0
    collective_time_s: float = 0.0
    collective_breaks_spanned: int = 0
    sharded_instrs: int = 0
    # Pass-boundary verifier accounting (core/verify.py): the resolved
    # level this compile ran under (REPRO_VERIFY may override the option),
    # boundaries checked, warning-severity diagnostics (errors raise), and
    # the total verification time — also surfaced as pass_times["verify"].
    verify_mode: str = "off"
    verify_boundaries: int = 0
    verify_warnings: int = 0
    verify_time_s: float = 0.0
    # whether the kernels run in the Pallas interpreter (resolved option)
    interpret: bool = True
    # kernel-instance operands bound in their native layout: parameters the
    # plan's device lays out with their minor dims swapped, read so by the
    # kernel instead of after an XLA relayout copy (0 on a CPU)
    native_layout_operands: int = 0
    # kernel instances whose block of a batched-dot operand holds whole
    # matrices of more than one batch element (``LaunchStats``)
    dot_batch_blocks: int = 0
    # the executable's live LaunchStats, which the per-call counters read
    runtime: Optional[LaunchStats] = field(default=None, repr=False, compare=False)

    @property
    def feeds_passed(self) -> int:
        """Feeds bound as given, over every call so far."""
        return self.runtime.feeds_passed if self.runtime is not None else 0

    @property
    def feeds_converted(self) -> int:
        """Feeds converted with ``jnp.asarray``, over every call so far."""
        return self.runtime.feeds_converted if self.runtime is not None else 0

    @property
    def replay_dispatch_reduction(self) -> int:
        """Per-call dispatches the traced replay saves over the eager loop."""
        return self.eager_dispatches_per_call - self.traced_dispatches_per_call

    @property
    def fusion_ratio(self) -> float:
        """paper Fig. 7: our kernel count / XLA baseline kernel count.
        Sub-module (loop body) kernels count as ours — the baseline count
        recurses into loop bodies the same way."""
        ours = self.stitched_kernels + self.standalone_kernels + self.sub_kernels
        return ours / self.xla_baseline_kernels if self.xla_baseline_kernels else 1.0

    @property
    def launches_saved_vs_unfused(self) -> int:
        """Kernel launches the committed plan saves over one-launch-per-op."""
        return self.unfused_kernels - (
            self.stitched_kernels + self.standalone_kernels
        )

    @property
    def launches_saved_vs_greedy(self) -> int:
        return self.greedy_kernels - (
            self.stitched_kernels + self.standalone_kernels
        )

    @property
    def cache_hit_rate(self) -> float:
        total = self.kernel_cache_hits + self.kernel_cache_misses
        return self.kernel_cache_hits / total if total else 0.0

    @property
    def smem_average(self) -> float:
        allocs = [r.scratch_bytes for r in self.reports]
        return float(np.mean(allocs)) if allocs else 0.0

    @property
    def smem_max(self) -> int:
        return max((r.scratch_bytes for r in self.reports), default=0)

    @property
    def total_shrinks(self) -> int:
        return sum(r.num_shrinks for r in self.reports)

    @property
    def shared_ratio(self) -> float:
        tot = sum(r.scratch_bytes for r in self.reports)
        sh = sum(r.shared_bytes for r in self.reports)
        return sh / tot if tot else 0.0


class CompiledModule:
    def __init__(self, executable: StitchedExecutable, stats: CompileStats):
        self.executable = executable
        self.stats = stats

    def __call__(self, feeds):
        return self.executable(feeds)


def build_outputs(state: CompilationState) -> None:
    """FinalizePass body: final FusionPlan, planned executable, stats."""
    lib = state.library

    kernels: Dict[str, StitchedKernel] = {}
    reports: List[FusionReport] = []
    predicted = 0.0
    final_fusions = []
    stitched_instances = 0
    stitch_phases_total = 0
    stitch_iface_bytes = 0
    for p in state.planned:
        kernels[p.fusion.name] = p.kernel
        final_fusions.append(p.fusion)
        predicted += p.entry.cost_s
        mem = p.entry.memory
        st = p.entry.stitched
        if st is not None:
            stitched_instances += 1
            stitch_phases_total += st.num_phases
            stitch_iface_bytes += st.interface_bytes
        reports.append(
            FusionReport(
                p.fusion.name,
                len(p.fusion.members),
                p.entry.blocks,
                p.entry.cost_s,
                mem.total_bytes,
                mem.shared_bytes,
                mem.num_shrinks,
                [r.name for r in p.fusion.roots],
                cached=p.cache_hit,
                signature=p.entry.signature,
                num_phases=st.num_phases if st is not None else 1,
                interface_bytes=st.interface_bytes if st is not None else 0,
                model_cost_s=p.entry.model_cost_s,
                measured_cost_s=p.entry.measured_cost_s,
            )
        )

    plan = FusionPlan(
        final_fusions,
        state.fusion_plan.standalone + state.demoted,
        state.module,
        planner=state.fusion_plan.planner,
    )
    library_time = 0.0
    collective_time = 0.0
    collective_calls = 0
    collective_bytes = 0
    mesh_sizes = dict(getattr(state.options, "mesh_axes", None) or ())
    for s in plan.standalone:
        if s.opcode == "get":
            continue   # projection of a loop output — no launch, no cost
        if s.is_collective:
            # ICI traffic, not a kernel launch: charged by the ring model,
            # reported apart from both kernel and library time.
            g = 1
            for a in s.attrs.get("axes", ()):
                g *= mesh_sizes.get(a, 1)
            collective_time += lib.model.collective_op_time(s, g)
            collective_calls += 1
            collective_bytes += s.operands[0].bytesize
            continue
        if s.opcode == "call":
            # a loop costs its body's predicted time per iteration
            sub = s.attrs["compiled_body"].stats
            trip = int(s.attrs["trip_count"])
            predicted += trip * sub.predicted_time_s
            library_time += trip * sub.library_time_s
            continue
        # standalone kernels are costed as single-op launches; library-call
        # time (cuBLAS/MXU dots) is tracked separately — it is common to the
        # baseline and the stitched build (paper Fig. 6/8 methodology).
        t = lib.model.kernel_time(1, lib.model.op_time(s, _whole(s), 1))
        if s.is_library_call:
            library_time += t
        else:
            predicted += t

    # Collective breaks SPANNED by stitched compute: some fused kernel runs
    # upstream of the collective and another downstream — the plan stitched
    # compute into phases around the break (transitively: the value feeding
    # an all-reduce is typically a library dot, with the fused compute one
    # hop further).
    fused_ids = set()
    for f in final_fusions:
        fused_ids.update(m.id for m in f.members)

    def _reaches(start_ops, follow) -> bool:
        seen, stack = set(), list(start_ops)
        while stack:
            i = stack.pop()
            if i.id in seen:
                continue
            seen.add(i.id)
            if i.id in fused_ids:
                return True
            stack.extend(follow(i))
        return False

    breaks_spanned = sum(
        1
        for s in plan.standalone
        if s.is_collective
        and _reaches(s.operands, lambda i: i.operands)
        and _reaches(s.users, lambda i: i.users)
    )

    executable = StitchedExecutable(
        state.module, plan, kernels,
        jit_replay=state.options.jit_replay,
        donate_params=state.donate_params,
        mesh=state.mesh,
        param_layouts=state.param_layouts,
        out_layouts=state.out_layouts,
    )
    st = executable.launch_stats()
    hits = sum(1 for p in state.planned if p.cache_hit)
    from .fusion import constant_like

    unfused = sum(
        1
        for i in state.module.instructions
        if i.opcode not in ("parameter", "constant", "call", "get")
        and not constant_like(i)
        and not i.is_library_call
    )
    # a loop site's no-fusion-at-all launch count is its body's, recursively
    unfused += sum(
        i.attrs["compiled_body"].stats.unfused_kernels
        for i in state.module.instructions
        if i.opcode == "call"
    )
    sub_kernels = sum(
        cm.stats.stitched_kernels
        + cm.stats.standalone_kernels
        + cm.stats.sub_kernels
        for cm in state.sub_compiled.values()
    )
    pstats = state.fusion_plan.planner
    mstore = state.measured_store
    m_hits = mstore.hits - state.measured_base_hits if mstore else 0
    m_misses = mstore.misses - state.measured_base_misses if mstore else 0
    errors = [
        abs(e.model_cost_s - e.measured_cost_s) / e.measured_cost_s * 100.0
        for e in {id(p.entry): p.entry for p in state.planned}.values()
        if e.model_cost_s is not None
        and e.measured_cost_s is not None
        and e.measured_cost_s > 0.0
    ]
    state.executable = executable
    state.stats = CompileStats(
        stitched_kernels=st.stitched_kernels,
        standalone_kernels=st.standalone_kernels,
        library_calls=st.library_calls,
        loop_calls=st.loop_calls,
        sub_compiles=len(state.sub_compiled),
        sub_call_sites=state.sub_call_sites,
        sub_kernels=sub_kernels,
        xla_baseline_kernels=xla_baseline_kernel_count(state.module),
        predicted_time_s=predicted,
        library_time_s=library_time,
        reports=reports,
        kernel_cache_hits=hits,
        kernel_cache_misses=len(state.planned) - hits,
        tuning_disk_hits=sum(1 for p in state.planned if p.tuned_from_disk),
        unique_kernels=len({id(p.entry) for p in state.planned}),
        kernels_emitted=sum(1 for p in state.planned if p.is_representative),
        planner_mode=pstats.mode if pstats else "greedy",
        plans_explored=pstats.plans_explored if pstats else 0,
        plans_rejected=pstats.plans_rejected if pstats else 0,
        planner_splits=pstats.splits_taken if pstats else 0,
        planner_merges=pstats.merges_taken if pstats else 0,
        planner_packs=pstats.packs_taken if pstats else 0,
        planner_stitches=pstats.stitches_taken if pstats else 0,
        stitch_lowered_kernels=stitched_instances,
        stitch_phases_total=stitch_phases_total,
        stitch_interface_bytes=stitch_iface_bytes,
        planner_predicted_s=pstats.predicted_s if pstats else 0.0,
        greedy_predicted_s=pstats.greedy_predicted_s if pstats else 0.0,
        greedy_kernels=pstats.greedy_kernels if pstats else 0,
        planner_kernels=pstats.planned_kernels if pstats else 0,
        unfused_kernels=unfused,
        replay_mode=(
            "sharded"
            if state.mesh is not None
            else ("jit" if state.options.jit_replay else "eager")
        ),
        eager_dispatches_per_call=st.eager_dispatches_per_call,
        traced_dispatches_per_call=st.traced_dispatches_per_call,
        donated_buffers=st.donated_buffers,
        measured_hits=m_hits,
        measured_misses=m_misses,
        measurements_taken=state.measurements_taken,
        model_error_pct=float(np.mean(errors)) if errors else None,
        collective_calls=collective_calls,
        collective_bytes=collective_bytes,
        collective_time_s=collective_time,
        collective_breaks_spanned=breaks_spanned,
        sharded_instrs=state.shard_stats.get("sharded_instrs", 0),
        interpret=state.options.interpret,
        native_layout_operands=sum(sum(p.kernel.native) for p in state.planned),
        dot_batch_blocks=st.dot_batch_blocks,
        runtime=executable.execution_plan.stats,
    )


def resolve_options(opts: StitchOptions) -> StitchOptions:
    """``opts`` with ``interpret`` resolved against the running backend."""
    interpret = resolve_interpret(opts.interpret)
    return opts if opts.interpret is interpret else replace(opts, interpret=interpret)


def compile_module(
    module,
    options: Optional[StitchOptions] = None,
    kernel_cache: Optional[KernelCache] = None,
    measured_store=None,
    donate_params=None,
    mesh=None,
    param_layouts=None,
    out_layouts=None,
    device=None,
) -> CompiledModule:
    """Compile a StitchIR module through the default pass pipeline.

    ``kernel_cache`` may be shared across calls so repeated compiles of
    structurally-identical graphs (per-layer blocks, per-request recompiles)
    reuse tuned schedules and emitted kernels.  ``measured_store`` (a
    ``core.measure.MeasuredCostStore``) may likewise be shared so autotune
    measurements taken by one compile guide the next; when None, one is
    created if ``options.autotune`` or ``options.tuning_store_path`` asks
    for it.  ``donate_params`` names parameters whose buffers the caller
    donates (the frontend's ``donate_argnums``) — runtime-only, never part
    of any cache fingerprint.

    ``mesh``/``param_layouts``/``out_layouts`` make this a sharded compile:
    the module must hold the PER-SHARD computation (a shard_map body, as
    ``frontend.jaxpr_lower.lower_sharded_jaxpr`` produces), ``mesh`` is the
    live Mesh the one ExecutionPlan replays on, and the layouts map
    parameter names / outputs to ``core.shard`` layout tuples.  The mesh's
    (name, size) shape must match ``options.mesh_axes`` — the hashable half
    that salts every cache key.

    ``device`` is the device the plan runs on: kernels read each parameter
    in that device's default layout for it (``codegen.stamp_native_layouts``).
    None reads every parameter row-major, as a loop body must.
    """
    opts = resolve_options(options or StitchOptions())
    with span("repro.compile") as timed:
        stamp_native_layouts(module, device)
        library = PerfLibrary(opts.perf_library_path)
        store = measured_store
        if store is None and (opts.autotune or opts.tuning_store_path):
            from .measure import MeasuredCostStore, device_fingerprint

            store = MeasuredCostStore(
                opts.tuning_store_path,
                device_fp=device_fingerprint(library.model.spec, opts.interpret),
            )
        state = CompilationState(
            module=module,
            options=opts,
            library=library,
            kernel_cache=(
                kernel_cache
                if kernel_cache is not None
                else KernelCache(opts.kernel_cache_path)
            ),
            measured_store=store,
            measured_base_hits=store.hits if store else 0,
            measured_base_misses=store.misses if store else 0,
            donate_params=frozenset(donate_params) if donate_params else None,
            mesh=mesh,
            param_layouts=param_layouts,
            out_layouts=out_layouts,
        )
        default_pipeline().run(state)
    state.stats.compile_time_s = timed.seconds
    state.stats.pass_times = dict(state.pass_times)
    if opts.perf_library_path:
        state.library.save()
    if opts.kernel_cache_path:
        state.kernel_cache.save()
    if store is not None and opts.tuning_store_path:
        store.save()
    return CompiledModule(state.executable, state.stats)


def _whole(instr):
    from .schedule import REPLICATED

    return REPLICATED
