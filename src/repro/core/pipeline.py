"""The FusionStitching pass pipeline — paper Fig. 4 as explicit passes.

``compile_module`` used to be one monolithic function; here every stage of
the paper's pipeline is a ``Pass`` over a shared ``CompilationState``
artifact, so stages can be tested, timed, and reordered in isolation:

    FusionPass     deep fusion (§3.2) with the ScheduleConsistencyChecker
    SchedulePass   per-fusion schedule tuning (§4.3) with fusion-signature
                   kernel-cache lookup — structurally identical fusions
                   (stacked transformer layers) tune once
    MemoryPass     VMEM scratch planning (§5.1) with the memory-infeasible
                   feedback loop back into tuning (shrink + retune)
    CodegenPass    IrEmitterStitched Pallas emission (§5.2), deduplicated:
                   one emitted kernel per unique fusion signature
    FinalizePass   execution-plan construction + CompileStats

The memory feedback edge of Fig. 4 is preserved: MemoryPass re-invokes the
tuner when a fusion must shrink to fit the scratch budget, and members it
drops are demoted to standalone kernels (never silently lost).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..tracing import span
from .codegen import StitchedKernel, emit_fusion, emit_stitched_fusion, resolve_interpret
from .fusion import (
    FusedComputation,
    FusionConfig,
    FusionPlan,
    FusionScorer,
    deep_fuse,
)
from .ir import Instruction, Module
from .measure import measure_kernel
from .memory import MemoryInfeasible, plan_memory, plan_stitched_memory
from .perf_library import PerfLibrary
from .schedule import (
    CONSISTENT,
    PhaseSolution,
    Unsatisfiable,
    resolve_schedules,
    resolve_stitched,
    stitchable,
)
from .shard import propagate_layouts
from .signature import CacheEntry, KernelCache, fusion_signature
from .tuning import TunedPlan, score, tune


@dataclass
class PlannedFusion:
    """One fusion instance bound to its (possibly shared) cache entry."""

    fusion: FusedComputation
    entry: CacheEntry
    is_representative: bool          # this instance built the entry
    kernel: Optional[StitchedKernel] = None
    tuned_from_disk: bool = False
    # Signature provenance for the verifier's cache-collision audit
    # (EXEC005): the content hash of the fusion body as SchedulePass hashed
    # it, and whether memory feedback later shrank this instance — a shrunk
    # fusion keeps its pre-shrink signature by design (``kept_members``
    # records the shrink), so the audit skips re-hashing it.
    raw_signature: Optional[str] = None
    shrunk: bool = False
    # Measured-store key for this fusion (options salt + the signature the
    # planner SCORED — see FusedComputation.scored_signature).  Recorded by
    # SchedulePass so AutotunePass files measurements under the exact key
    # the next compile's scorer will look up.
    measure_sig: Optional[str] = None

    @property
    def cache_hit(self) -> bool:
        return not self.is_representative


@dataclass
class CompilationState:
    """The artifact every pass reads and extends."""

    module: Module
    options: "StitchOptions"              # noqa: F821 — compiler facade type
    library: PerfLibrary
    kernel_cache: KernelCache
    fusion_plan: Optional[FusionPlan] = None
    planned: List[PlannedFusion] = field(default_factory=list)
    demoted: List[Instruction] = field(default_factory=list)
    pass_times: Dict[str, float] = field(default_factory=dict)
    # Autotuning: the MeasuredCostStore for this compile (None = analytic
    # only).  The hit/miss counters live on the store and accumulate across
    # compiles when it is shared, so FinalizePass reports deltas against the
    # snapshot taken when the state was built.
    measured_store: Optional[object] = None
    measured_base_hits: int = 0
    measured_base_misses: int = 0
    measurements_taken: int = 0
    # Parameter names whose buffers the caller donated (frontend
    # ``donate_argnums``): threaded to the ExecutionPlan, which lifts the
    # donation protection on those slots.  Runtime-only — never part of any
    # cache fingerprint (like ``jit_replay``, it changes how a plan is
    # replayed, not what is tuned or emitted).
    donate_params: Optional[frozenset] = None
    # Shard-aware compilation (set when ``options.mesh_axes`` is): the Mesh
    # the plan replays on (runtime-only — never fingerprinted; its (name,
    # size) shape IS fingerprinted via options.mesh_axes), parameter/output
    # layouts from the shard_map trace, and ShardingPass counters.
    mesh: Optional[object] = None
    param_layouts: Optional[Dict[str, tuple]] = None
    out_layouts: Optional[List] = None
    shard_stats: Dict[str, int] = field(default_factory=dict)
    # Sub-module (loop body) compiles, filled by SubModulePass: unique
    # compiled bodies by structural module signature, plus call-site count.
    sub_compiled: Dict[str, object] = field(default_factory=dict)
    sub_call_sites: int = 0
    # filled by FinalizePass
    executable: Optional[object] = None
    stats: Optional[object] = None


class Pass:
    name = "pass"

    def run(self, state: CompilationState) -> None:
        raise NotImplementedError


class PassPipeline:
    def __init__(self, passes: List[Pass]):
        self.passes = list(passes)

    def run(self, state: CompilationState) -> CompilationState:
        from .verify import ERROR, VerificationError, resolve_verify_mode, verify_state

        mode = resolve_verify_mode(state.options)
        verify_time = 0.0
        boundaries = 0
        warnings = 0
        for p in self.passes:
            with span(f"repro.pass.{p.name}") as timed:
                p.run(state)
            state.pass_times[p.name] = timed.seconds
            # "off" does zero verification work (no pass_times["verify"]
            # entry either — the no-overhead contract is testable);
            # "checkpoint" verifies the finished artifact once; "strict"
            # checks every boundary so a violation names the pass that
            # introduced it.
            if mode == "off" or (mode == "checkpoint" and p is not self.passes[-1]):
                continue
            with span("repro.pass.verify") as timed:
                diags = verify_state(state, pass_name=p.name)
            verify_time += timed.seconds
            boundaries += 1
            errors = [d for d in diags if d.severity == ERROR]
            warnings += len(diags) - len(errors)
            if errors:
                state.pass_times["verify"] = verify_time
                raise VerificationError(errors)
        if mode != "off":
            state.pass_times["verify"] = verify_time
            if state.stats is not None:
                state.stats.verify_mode = mode
                state.stats.verify_boundaries = boundaries
                state.stats.verify_warnings = warnings
                state.stats.verify_time_s = verify_time
        return state


# --------------------------------------------------------------------------
# Passes
# --------------------------------------------------------------------------


class SubModulePass(Pass):
    """Compile every loop body (``call`` instruction) as its own module
    through the full pipeline, BEFORE the parent's fusion pass runs.

    Bodies are deduplicated by structural ``module_signature``: the N
    scan layers of a stacked model lower to N ``call`` sites whose bodies
    hash equal, so one compiled sub-module serves them all.  The parent's
    ``kernel_cache`` and ``measured_store`` are shared into the sub-compile,
    so structurally identical fusions inside different (or repeated) bodies
    also dedup at the kernel level across layers and across compiles.
    Idempotent — a ``call`` that already carries a ``compiled_body`` is
    left alone; nested loops recurse naturally because the sub-compile runs
    this same pipeline.
    """

    name = "submodule"

    def run(self, state: CompilationState) -> None:
        from .compiler import compile_module
        from .signature import module_signature

        for instr in state.module.instructions:
            if instr.opcode != "call" or "compiled_body" in instr.attrs:
                continue
            state.sub_call_sites += 1
            sig = module_signature(instr.attrs["body"])
            cm = state.sub_compiled.get(sig)
            if cm is None:
                cm = compile_module(
                    instr.attrs["body"],
                    state.options,
                    kernel_cache=state.kernel_cache,
                    measured_store=state.measured_store,
                )
                state.sub_compiled[sig] = cm
            instr.attrs["compiled_body"] = cm
            instr.attrs["body_sig"] = sig


class ShardingPass(Pass):
    """Resolve shard layouts before fusion (the tentpole's pipeline hook).

    When the compile targets a mesh (``options.mesh_axes`` set), walk the
    module once with ``shard.propagate_layouts``: derive a layout for every
    instruction from the parameter layouts, stamp non-trivial results into
    ``attrs["shard"]`` (which salts ``fusion_signature`` downstream — the
    kernel cache can never alias per-shard and full-shape kernels), track
    pending partial sums, and validate collectives against the mesh.  A
    no-mesh compile is untouched — not a single attr changes, so every
    existing signature and cache key stays byte-identical.
    """

    name = "sharding"

    def run(self, state: CompilationState) -> None:
        mesh_axes = getattr(state.options, "mesh_axes", None)
        if not mesh_axes:
            return
        state.shard_stats = propagate_layouts(
            state.module, mesh_axes, state.param_layouts
        )


class FusionPass(Pass):
    """Deep fusion with the schedule+memory consistency checker (Fig. 4),
    cost-guided by the shared LatencyModel when ``options.planner`` is
    ``"cost"`` (candidate partitions + horizontal merging)."""

    name = "fusion"

    def run(self, state: CompilationState) -> None:
        opts = state.options
        srl = _stitch_replicate_limit(opts)

        scorer = None
        if opts.planner == "cost":
            # the planner scores with the SAME model the tuner's PerfLibrary
            # uses as its miss handler — one LatencyModel per compile
            scorer = FusionScorer(
                model=state.library.model,
                replicate_limit=opts.replicate_limit,
                max_blocks=opts.max_blocks,
                vmem_limit=opts.vmem_limit,
                allow_stitch=opts.enable_stitching,
                stitch_replicate_limit=srl,
                stitch_max_blocks=opts.stitch_max_blocks,
                measured=state.measured_store,
                options_salt=_measure_salt(opts),
                mesh_axes=getattr(opts, "mesh_axes", None) or (),
            )

        if scorer is not None:
            def consistency(roots, members) -> bool:
                # delegate to the scorer: same three-way verdict + memory
                # feasibility (incl. the stitched interface budget, so
                # over-budget stitches fall back to a split), memoized by
                # member-id frozenset — growth probes the same sets the
                # partition scoring later reuses.  Singletons must be
                # CONSISTENT outright: a lone op whose only schedule is the
                # stitched degenerate one cannot lower as a one-member
                # stitched kernel and would only be demoted later.
                if len(members) == 1:
                    return scorer.verdict(members).verdict == CONSISTENT
                return scorer.fused_cost(members) is not None
        else:
            def consistency(roots, members) -> bool:
                # planner="greedy" reproduces the paper's Algorithm 1
                # exactly: the boolean SchdConsistent veto, no stitching
                v = stitchable(
                    roots,
                    members,
                    replicate_limit=opts.replicate_limit,
                    max_blocks=opts.max_blocks,
                    allow_stitch=False,
                )
                if v.verdict != CONSISTENT:
                    return False
                try:
                    plan_memory(members, roots, v.solution, opts.vmem_limit)
                except MemoryInfeasible:
                    return False
                return True

        fcfg = FusionConfig(
            fuse_dot=opts.fuse_dot,
            ew_footprint_limit=opts.ew_footprint_limit,
            max_fusion_ops=opts.max_fusion_ops,
            consistency=consistency,
            planner=opts.planner,
            scorer=scorer,
            enable_stitching=opts.enable_stitching,
            # the consistency closure above IS the scorer's feasibility
            # check under the same limits — don't solve everything twice
            scorer_covers_consistency=scorer is not None,
        )
        state.fusion_plan = deep_fuse(state.module, fcfg)


def _stitch_replicate_limit(opts) -> int:
    """Resolved stitched-phase replicate limit (None = the VMEM budget);
    an explicit 0 means "no relaxed replication" and is honored."""
    if opts.stitch_replicate_limit is None:
        return opts.vmem_limit
    return opts.stitch_replicate_limit


def _options_fingerprint(opts) -> str:
    """Compile-options salt for cache keys: a kernel tuned/emitted under one
    (interpret, memory-budget, blocks, planner, stitching) regime must never
    serve a compile running under another, even through a shared or
    persistent cache.  The planner mode is part of the fingerprint because
    the planner decides *partitions*: a signature that names a greedy-built
    structure must not resurrect under a differently-partitioned compile.
    The stitching options are part of it because they decide *phases*: a
    stitched lowering must never serve a stitching-disabled compile (the
    phase structure itself additionally salts ``fusion_signature``).
    The autotune knobs are part of it because they decide which *costs* the
    planner saw: an entry partitioned under measured costs must not serve an
    analytic-only compile (or one reading a different tuning store)."""
    return (
        _measure_salt(opts)
        + f"at{int(getattr(opts, 'autotune', False))}"
        f":mr{getattr(opts, 'measure_repeats', 5)}"
        f":ts{getattr(opts, 'tuning_store_path', None) or ''}:"
    )


def _measure_salt(opts) -> str:
    """Salt for MeasuredCostStore keys: everything that changes what a
    kernel IS (interpret, memory budgets, blocks, planner, stitching) but
    NOT the autotune-control knobs — a measurement describes the lowering,
    not how eagerly we measure, so a store warmed under ``autotune=True``
    must still serve a later read-only ``tuning_store_path`` compile."""
    srl = _stitch_replicate_limit(opts)
    salt = (
        f"i{int(resolve_interpret(opts.interpret))}:v{opts.vmem_limit}:r{opts.replicate_limit}"
        f":b{opts.max_blocks}:p{opts.planner}"
        f":st{int(opts.enable_stitching)}:sb{opts.stitch_max_blocks}:sr{srl}:"
    )
    # Mesh shape enters the salt ONLY for sharded compiles: per-shard costs
    # measured on an 8-way mesh must not serve a 4-way (or unsharded) run,
    # while every pre-existing single-device key stays byte-identical.
    mesh_axes = getattr(opts, "mesh_axes", None)
    if mesh_axes:
        salt += "m" + ",".join(f"{a}{s}" for a, s in mesh_axes) + ":"
    return salt


class SchedulePass(Pass):
    """Tune each fusion's schedule; deduplicate by fusion signature.

    A cache hit binds the instance to the existing entry: no tuning, no
    memory planning, no emission for this instance.  A persistent-store hit
    (warm process) skips the tuning search but still resolves/validates the
    recorded root schedules against this fusion.
    """

    name = "schedule"

    def run(self, state: CompilationState) -> None:
        opts = state.options
        cache = state.kernel_cache
        salt = _options_fingerprint(opts)
        msalt = _measure_salt(opts)
        for fusion in state.fusion_plan.fusions:
            raw = fusion_signature(fusion)
            sig = salt + raw
            # Measured records are keyed by the signature the PLANNER scored
            # (pre-absorption when the two differ) — the key next compile's
            # scorer will ask the store for.
            msig = msalt + (fusion.scored_signature or raw)
            if opts.dedup_kernels:
                entry = cache.get(sig)
                if entry is not None:
                    state.planned.append(
                        PlannedFusion(
                            fusion, entry, False,
                            measure_sig=msig, raw_signature=raw,
                        )
                    )
                    continue
            tuned, from_disk = self._tune(state, fusion, sig)
            if tuned is None:
                entry = None
                if (
                    opts.enable_stitching
                    and opts.planner == "cost"
                    and len(fusion.members) > 1
                ):
                    entry = self._tune_stitched(state, fusion, sig)
                if entry is None:
                    state.demoted.extend(fusion.members)
                    continue
                self._apply_measured(state, entry, msig)
                if opts.dedup_kernels:
                    cache.put(entry)
                state.planned.append(
                    PlannedFusion(
                        fusion, entry, True,
                        measure_sig=msig, raw_signature=raw,
                    )
                )
                continue
            roots = fusion.roots
            entry = CacheEntry(
                signature=sig,
                solution=tuned.solution,
                memory=None,
                cost_s=tuned.cost_s,
                root_scheds=[tuned.solution.root_scheds[r.id] for r in roots],
                model_cost_s=tuned.cost_s,
            )
            self._apply_measured(state, entry, msig)
            if opts.dedup_kernels:
                cache.put(entry)
            state.planned.append(
                PlannedFusion(
                    fusion, entry, True,
                    tuned_from_disk=from_disk, measure_sig=msig,
                    raw_signature=raw,
                )
            )

    @staticmethod
    def _apply_measured(state, entry: CacheEntry, msig: str) -> None:
        """On a measured-store hit, the entry's actionable cost becomes the
        on-device time (the analytic number stays in ``model_cost_s`` for
        error reporting); on a miss, nothing changes and AutotunePass will
        measure the emitted kernel."""
        store = state.measured_store
        if store is None:
            return
        rec = store.get(msig)
        if rec is not None:
            entry.measured_cost_s = rec.cost_s
            entry.cost_s = rec.cost_s

    def _tune(self, state, fusion, sig):
        opts = state.options
        members, roots = fusion.members, fusion.roots
        if opts.dedup_kernels:
            hint = state.kernel_cache.tuning_hint(sig)
            if hint is not None and len(hint) == len(roots):
                try:
                    sol = resolve_schedules(
                        members,
                        roots,
                        {r.id: s for r, s in zip(roots, hint, strict=False)},
                        opts.replicate_limit,
                    )
                    return TunedPlan(sol, score(members, sol, state.library)), True
                except Unsatisfiable:
                    pass  # stale record — fall back to the full search
        tuned = tune(
            members,
            roots,
            state.library,
            max_blocks=opts.max_blocks,
            replicate_limit=opts.replicate_limit,
        )
        return tuned, False

    def _tune_stitched(self, state, fusion, sig) -> Optional[CacheEntry]:
        """No single schedule exists: resolve a multi-phase stitched plan and
        improve each phase's schedule with the performance library (the
        per-phase analogue of §4.3 tuning; phases whose only schedule needs
        the relaxed replicate limit keep the resolver's solution).

        This deliberately re-solves rather than reusing the fusion-pass
        scorer's solution: constant-like absorption extends the member list
        after planning, so the lowered phase structure must be derived from
        the FINAL members (``stitch_phases`` stays the planner's
        pre-absorption hint — a deterministic signature salt, not the
        lowering)."""
        opts = state.options
        members, roots = fusion.members, fusion.roots
        srl = _stitch_replicate_limit(opts)
        st = resolve_stitched(
            members,
            roots,
            replicate_limit=opts.replicate_limit,
            max_blocks=opts.max_blocks,
            stitch_replicate_limit=srl,
            stitch_max_blocks=opts.stitch_max_blocks,
        )
        if st is None:
            return None
        cap = min(opts.max_blocks, opts.stitch_max_blocks)
        for k, p in enumerate(st.phases):
            tuned = tune(
                p.members,
                p.roots,
                state.library,
                max_blocks=cap,
                replicate_limit=opts.replicate_limit,
            )
            if tuned is not None:
                st.phases[k] = PhaseSolution(p.members, p.roots, tuned.solution)
        cost = state.library.model.stitched_fusion_time(st)
        return CacheEntry(
            signature=sig,
            solution=None,
            memory=None,
            cost_s=cost,
            stitched=st,
            model_cost_s=cost,
        )


class MemoryPass(Pass):
    """VMEM scratch planning with the §5.1.2 feedback loop: on
    MemoryInfeasible, drop the deepest member, re-tune, retry.  Dropped
    members are demoted to standalone kernels."""

    name = "memory"

    def run(self, state: CompilationState) -> None:
        dead = set()  # entries whose representative proved unfusable
        kept: List[PlannedFusion] = []
        for p in state.planned:
            if not p.is_representative:
                if id(p.entry) in dead:
                    # the shared plan died — this instance runs standalone too
                    state.demoted.extend(p.fusion.members)
                    continue
                kept.append(p)  # shares the representative's plan
                continue
            if self._plan(state, p):
                kept.append(p)
            else:
                dead.add(id(p.entry))
                if state.options.dedup_kernels:
                    state.kernel_cache.remove(p.entry.signature)
        state.planned = kept

    def _plan(self, state, p: PlannedFusion) -> bool:
        opts = state.options
        fusion, entry = p.fusion, p.entry
        members, roots = fusion.members, fusion.roots
        if entry.stitched is not None:
            # stitched plans have no shrink loop: interface buffers are
            # required by construction, so an over-budget stitch (normally
            # vetoed during fusion) demotes to standalone kernels
            try:
                entry.memory = plan_stitched_memory(
                    entry.stitched, opts.vmem_limit
                )
            except MemoryInfeasible:
                state.demoted.extend(fusion.members)
                return False
            entry.kept_members = len(members)
            return True
        tuned: Optional[TunedPlan] = TunedPlan(entry.solution, entry.cost_s)
        dropped: List[Instruction] = []
        while tuned is not None:
            try:
                mem = plan_memory(members, roots, tuned.solution, opts.vmem_limit)
            except MemoryInfeasible:
                if len(members) <= 1:
                    tuned = None
                    break
                dropped.append(members[-1])
                members = members[:-1]
                fusion = FusedComputation(members, name=fusion.name)
                roots = fusion.roots
                tuned = tune(
                    members,
                    roots,
                    state.library,
                    max_blocks=opts.max_blocks,
                    replicate_limit=opts.replicate_limit,
                )
                continue
            # success
            state.demoted.extend(dropped)
            if dropped:
                p.shrunk = True
            p.fusion = fusion
            entry.solution = tuned.solution
            entry.cost_s = tuned.cost_s
            if dropped:
                # the structure changed: the pre-shrink measurement (and the
                # pre-shrink analytic estimate) no longer describe it
                entry.model_cost_s = tuned.cost_s
                entry.measured_cost_s = None
            entry.memory = mem
            entry.root_scheds = [
                tuned.solution.root_scheds[r.id] for r in roots
            ]
            entry.kept_members = len(members)
            if dropped and opts.dedup_kernels:
                # the persisted record (written pre-shrink by SchedulePass)
                # no longer describes the structure its signature hashes
                state.kernel_cache.discard_disk(entry.signature)
            return True
        # unfusable after all: every member (kept + dropped) runs standalone
        state.demoted.extend(fusion.members)
        state.demoted.extend(dropped)
        return False


class CodegenPass(Pass):
    """Emit one Pallas kernel per unique signature; bind instances.

    Representatives are planned before their hits (SchedulePass order), so
    an entry's kernel always exists by the time an instance binds to it.
    """

    name = "codegen"

    def run(self, state: CompilationState) -> None:
        for p in state.planned:
            entry = p.entry
            if p.is_representative:
                if entry.stitched is not None:
                    kernel = emit_stitched_fusion(
                        p.fusion, entry.stitched, entry.memory,
                        interpret=state.options.interpret,
                    )
                else:
                    kernel = emit_fusion(
                        p.fusion, entry.solution, entry.memory,
                        interpret=state.options.interpret,
                    )
                entry.kernel = kernel
                p.kernel = kernel
            else:
                # the representative may have shrunk under memory feedback;
                # apply the identical shrink to this instance before binding
                kept_n = entry.kept_members or len(p.fusion.members)
                if kept_n < len(p.fusion.members):
                    state.demoted.extend(p.fusion.members[kept_n:])
                    p.shrunk = True
                    p.fusion = FusedComputation(
                        p.fusion.members[:kept_n], name=p.fusion.name
                    )
                p.kernel = entry.kernel.bind(p.fusion)


class AutotunePass(Pass):
    """Measure each unique emitted kernel once and remember the result.

    Runs after CodegenPass (it needs the compiled callables) and only when
    ``options.autotune`` is set: every representative whose measured-store
    lookup missed in SchedulePass gets timed on device (warmup +
    median-of-``measure_repeats`` with ``block_until_ready``) and filed
    under its measure key, so the NEXT compile's scorer and SchedulePass see
    real costs.  Within this compile the plan is already committed — the
    measurement-guided loop closes across compiles, never by re-planning
    mid-pipeline.  Misses here are the store's cold-start cost; hits make
    the pass free.
    """

    name = "autotune"

    def run(self, state: CompilationState) -> None:
        store = state.measured_store
        if store is None or not getattr(state.options, "autotune", False):
            return
        repeats = getattr(state.options, "measure_repeats", 5)
        for p in state.planned:
            if not p.is_representative or p.kernel is None:
                continue
            entry = p.entry
            if entry.measured_cost_s is not None:
                continue  # store hit (or already measured this compile)
            t = measure_kernel(p.kernel, repeats=repeats)
            model_s = (
                entry.model_cost_s
                if entry.model_cost_s is not None
                else entry.cost_s
            )
            store.put(p.measure_sig, t, model_s=model_s, repeats=repeats)
            entry.measured_cost_s = t
            state.measurements_taken += 1


class FinalizePass(Pass):
    """Assemble the final FusionPlan, the planned executable, and stats."""

    name = "finalize"

    def run(self, state: CompilationState) -> None:
        # imported here: compiler is the facade above this module
        from .compiler import build_outputs

        build_outputs(state)


def default_pipeline() -> PassPipeline:
    return PassPipeline(
        [
            SubModulePass(),
            ShardingPass(),
            FusionPass(),
            SchedulePass(),
            MemoryPass(),
            CodegenPass(),
            AutotunePass(),
            FinalizePass(),
        ]
    )
