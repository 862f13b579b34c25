"""repro — FusionStitching (Long et al., 2018) reproduced as a production
JAX/Pallas TPU framework: stitching compiler core, stitched kernels, model
zoo, distributed training/serving substrate, serve and train launchers.

Public surface (one coherent top level):

  * ``repro.stitch`` — the jit-shaped frontend: capture a real ``jax.numpy``
    function (control flow and gradients included) into StitchIR and compile
    it through the stitching pipeline.  ``static_argnums`` /
    ``static_argnames`` / ``donate_argnums`` mirror ``jax.jit``; the
    returned ``StitchedFunction`` exposes ``.lower()`` -> ``Lowered`` for
    introspection (``.as_text()``, ``.num_kernels``, ``.cost_estimate()``).
  * ``repro.StitchOptions`` — compile options (planner, budgets, stitching).
  * ``repro.compile_module`` — the documented low-level path for hand-built
    StitchIR modules.
  * ``repro.ServeEngine`` / ``repro.PagedServeEngine`` — continuous-batching
    serve engines behind the shared ``repro.BaseEngine`` protocol
    (``admit`` / ``tick`` / ``run_until_done`` / ``stats``).

Lower-level names (``GraphBuilder``, ``trace``, ``lower_jaxpr``,
``reference_execute``, primitive tables) live in ``repro.core`` and
``repro.frontend``.
"""
__version__ = "1.2.0"

from .core import (  # noqa: F401
    CompiledModule,
    CompileStats,
    Diagnostic,
    Module,
    StitchOptions,
    VerificationError,
    compile_module,
)
from .frontend import (  # noqa: F401
    CostEstimate,
    Lowered,
    StitchedFunction,
    UnsupportedPrimitiveError,
    stitch,
)
from .serve import (  # noqa: F401
    BaseEngine,
    PagedServeEngine,
    Request,
    ServeEngine,
)

__all__ = [
    # frontend
    "stitch",
    "StitchOptions",
    "StitchedFunction",
    "Lowered",
    "CostEstimate",
    "UnsupportedPrimitiveError",
    # compiler core
    "CompiledModule",
    "CompileStats",
    "Module",
    "compile_module",
    # verification (core/verify.py)
    "Diagnostic",
    "VerificationError",
    # serving
    "BaseEngine",
    "ServeEngine",
    "PagedServeEngine",
    "Request",
]
