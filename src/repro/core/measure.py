"""Measured-cost autotuning — on-device timing closes the loop over the
analytic ``LatencyModel``.

Every fusion decision the cost planner makes trusts ``core/latency.py``'s
roofline math.  The XLA fusion study (arXiv:2301.13062) documents exactly
where such analytic models mispredict — replication duplication, occupancy,
cross-block cache effects — and Tensor Comprehensions (arXiv:1802.04730)
shows the remedy: *autotune on device and remember the result*.  This module
is that remedy for the FusionStitching planner:

  * ``measure_callable`` / ``measure_kernel`` time a compiled lowering with
    warmup + median-of-k, fencing async dispatch with ``block_until_ready``.
    In ``interpret`` mode the same path runs on CPU, so CI exercises the
    whole loop end to end (the timings then describe the interpreter, not
    the TPU — the device fingerprint keeps the two worlds apart).
  * ``emit_group`` compiles an *arbitrary* candidate member set as one
    kernel through the existing tune -> memory-plan -> codegen path —
    single-schedule when one exists, multi-phase stitched otherwise — so
    the harness can time stitched-vs-split alternatives, tile/block choices
    (via ``max_blocks``), and phase partitions, not just committed plans.
  * ``MeasuredCostStore`` persists results as versioned JSON rows beside the
    ``KernelCache`` disk records, keyed by ``fusion_signature`` + a
    ``DeviceSpec``/backend fingerprint.  Stale-schema, corrupt, or
    wrong-device rows are evicted on read (counted, never raised), so a
    format bump or a device swap degrades to a cold retune.

The planner side lives in ``core/fusion.py`` (``FusionScorer`` prefers a
measured cost when a key hits, analytic as the cold-start prior) and
``core/pipeline.py`` (``AutotunePass`` measures each unique committed kernel
once and remembers it).  ``CompileStats`` reports
``measured_hits/measured_misses/measurements_taken/model_error_pct`` so the
analytic model's error is visible per compile.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

import jax

from .codegen import StitchedKernel, emit_fusion, emit_stitched_fusion, resolve_interpret
from .fusion import FusedComputation
from .ir import Instruction
from .latency import DeviceSpec, device_spec
from .memory import SCOPED_VMEM_BYTES, MemoryInfeasible, plan_memory, plan_stitched_memory
from .perf_library import JsonStore, PerfLibrary
from .schedule import resolve_stitched
from .tuning import tune

# Version of the on-disk measured-cost row schema.  Bump whenever the
# persisted payload changes shape; rows written under any other version are
# evicted on read instead of crashing a warm process.
MEASURE_SCHEMA_VERSION = 1


def device_fingerprint(
    spec: Optional[DeviceSpec] = None, interpret: Optional[bool] = None
) -> str:
    """Fingerprint of the measurement substrate: the DeviceSpec constants
    plus the runtime backend actually executing kernels (platform + device
    kind + interpret flag, both resolved from the backend when None).
    Interpret-mode CPU timings must never serve a real-TPU compile and vice
    versa — they describe different machines."""
    dev = jax.devices()[0]
    feats = (
        (spec or device_spec(dev)).fingerprint(),
        jax.default_backend(),
        getattr(dev, "device_kind", "unknown"),
        resolve_interpret(interpret),
    )
    return hashlib.sha256(repr(feats).encode()).hexdigest()[:16]


@dataclass(frozen=True)
class MeasuredCost:
    """One remembered measurement: wall-clock seconds for a fusion signature
    on a device, with the analytic prediction recorded at measure time so
    model error stays reportable without re-deriving it."""

    cost_s: float
    model_s: float
    repeats: int


class MeasuredCostStore:
    """Versioned persistent map: (device fingerprint, fusion signature) ->
    measured kernel seconds.

    Storage rides the same atomic ``JsonStore`` protocol as the PerfLibrary
    and the KernelCache tuning records (write-temp + fsync + ``os.replace``;
    an interrupted save can never corrupt the store).  ``get`` validates
    every row — schema version, device field, payload shape — and evicts
    rather than raises: a bumped schema, a corrupted file, or rows from
    another device all degrade to cold-start misses, so the planner falls
    back to the analytic model and plan *feasibility* is never affected.
    """

    def __init__(
        self, path: Optional[str] = None, device_fp: Optional[str] = None
    ):
        self._disk = JsonStore(path)
        self.device_fp = device_fp or device_fingerprint()
        self.hits = 0
        self.misses = 0
        self.stale_discards = 0
        self.measurements_taken = 0

    @property
    def path(self) -> Optional[str]:
        return self._disk.path

    def key(self, signature: str) -> str:
        return f"{self.device_fp}|{signature}"

    def get(self, signature: str) -> Optional[MeasuredCost]:
        rec = self._disk.get(self.key(signature))
        if rec is None:
            self.misses += 1
            return None
        try:
            if rec.get("version") != MEASURE_SCHEMA_VERSION:
                raise ValueError(f"schema version {rec.get('version')!r}")
            if rec.get("device") != self.device_fp:
                raise ValueError(f"device {rec.get('device')!r}")
            cost = MeasuredCost(
                cost_s=float(rec["cost_s"]),
                model_s=float(rec.get("model_s", 0.0)),
                repeats=int(rec.get("repeats", 1)),
            )
            if not (cost.cost_s > 0.0) or not np.isfinite(cost.cost_s):
                raise ValueError(f"cost_s {rec['cost_s']!r}")
        except (ValueError, TypeError, KeyError, AttributeError):
            self._disk.pop(self.key(signature))
            self.stale_discards += 1
            self.misses += 1
            return None
        self.hits += 1
        return cost

    def put(
        self,
        signature: str,
        cost_s: float,
        model_s: float = 0.0,
        repeats: int = 1,
    ) -> None:
        self.measurements_taken += 1
        self._disk.put(
            self.key(signature),
            {
                "version": MEASURE_SCHEMA_VERSION,
                "device": self.device_fp,
                "cost_s": float(cost_s),
                "model_s": float(model_s),
                "repeats": int(repeats),
            },
        )

    def save(self) -> None:
        self._disk.save()

    def __len__(self) -> int:
        return len(self._disk)

    def __contains__(self, signature: str) -> bool:
        return self.key(signature) in self._disk


# --------------------------------------------------------------------------
# The timing harness
# --------------------------------------------------------------------------


def measure_callable(fn, args: Sequence, repeats: int = 5, warmup: int = 1) -> float:
    """Median wall-clock seconds of ``fn(*args)`` over ``repeats`` runs.

    ``warmup`` untimed calls first absorb trace/compile cost, then each
    timed call is fenced with ``jax.block_until_ready`` so async dispatch
    cannot leak one run's work into the next run's clock.
    """
    repeats = max(1, int(repeats))
    for _ in range(max(0, int(warmup))):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _random_args(inputs: List[Instruction], rng) -> List:
    """Random device arrays matching a kernel's input shapes/dtypes.
    Timing does not depend on values for these kernels (no data-dependent
    control flow in StitchIR), so uniform noise is enough; arrays are
    materialized on device *before* the clock starts."""
    args = []
    for i in inputs:
        dt = np.dtype(i.dtype)
        if dt == np.bool_:
            a = rng.rand(*i.shape) > 0.5
        elif np.issubdtype(dt, np.integer):
            hi = max(2, i.shape[0] if i.shape else 2)
            a = rng.randint(0, hi, size=i.shape).astype(dt)
        else:
            a = rng.uniform(-1, 1, size=i.shape).astype(dt)
        args.append(jax.numpy.asarray(a))
    return args


def measure_kernel(
    kernel: StitchedKernel, repeats: int = 5, warmup: int = 1, seed: int = 0
) -> float:
    """Time one compiled kernel on random inputs (median of ``repeats``)."""
    rng = np.random.RandomState(seed)
    args = _random_args(kernel.inputs, rng)
    return measure_callable(kernel, args, repeats=repeats, warmup=warmup)


# --------------------------------------------------------------------------
# Candidate lowerings: compile an arbitrary member set through the real path
# --------------------------------------------------------------------------


def emit_group(
    members: List[Instruction],
    library: Optional[PerfLibrary] = None,
    *,
    vmem_limit: int = SCOPED_VMEM_BYTES,
    replicate_limit: int = 512 * 1024,
    max_blocks: int = 4096,
    stitch_replicate_limit: Optional[int] = None,
    stitch_max_blocks: int = 64,
    interpret: Optional[bool] = None,
) -> Optional[StitchedKernel]:
    """Compile ``members`` as ONE kernel through the production path: §4.3
    schedule tuning + §5.1 memory planning + §5.2 emission, falling back to
    the multi-phase stitched lowering when no single schedule exists.

    This is the harness's candidate-lowering entry point: any partition the
    planner can score — the whole group (stitched), a split piece, a
    singleton — can be emitted and timed without going through a full module
    compile.  Returns None when the group is infeasible under the limits
    (exactly the sets the scorer returns None for).
    """
    lib = library or PerfLibrary()
    fusion = FusedComputation(list(members), name="measured")
    roots = fusion.roots
    tuned = tune(
        members, roots, lib,
        max_blocks=max_blocks, replicate_limit=replicate_limit,
    )
    if tuned is not None:
        try:
            mem = plan_memory(members, roots, tuned.solution, vmem_limit)
        except MemoryInfeasible:
            return None
        return emit_fusion(fusion, tuned.solution, mem, interpret=interpret)
    srl = vmem_limit if stitch_replicate_limit is None else stitch_replicate_limit
    st = resolve_stitched(
        members, roots,
        replicate_limit=replicate_limit, max_blocks=max_blocks,
        stitch_replicate_limit=srl, stitch_max_blocks=stitch_max_blocks,
    )
    if st is None:
        return None
    try:
        mem = plan_stitched_memory(st, vmem_limit)
    except MemoryInfeasible:
        return None
    return emit_stitched_fusion(fusion, st, mem, interpret=interpret)


def measure_group(
    members: List[Instruction],
    library: Optional[PerfLibrary] = None,
    repeats: int = 5,
    seed: int = 0,
    **emit_kwargs,
) -> Optional[float]:
    """Median measured seconds for ``members`` lowered as one kernel, or
    None when the group has no feasible lowering under the limits."""
    kernel = emit_group(members, library, **emit_kwargs)
    if kernel is None:
        return None
    return measure_kernel(kernel, repeats=repeats, seed=seed)
