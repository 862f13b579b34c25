import os

# Sharded-compile tests need a real multi-device mesh; jax locks the device
# count on first init, so the flag must be set before `import jax`.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import numpy as np
import pytest

import jax

jax.config.update("jax_platform_name", "cpu")


def compile_and_compare(module, feeds, rtol=2e-5, atol=2e-5, **opt_kwargs):
    """Compile a StitchIR module and assert stitched == reference."""
    from repro.core import StitchOptions, compile_module, reference_execute

    opts = StitchOptions(max_blocks=opt_kwargs.pop("max_blocks", 32), **opt_kwargs)
    compiled = compile_module(module, opts)
    ref = reference_execute(module, feeds)
    out = compiled(feeds)
    assert set(out) == set(ref)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(out[k], dtype=np.float64),
            np.asarray(ref[k], dtype=np.float64),
            rtol=rtol,
            atol=atol,
            err_msg=f"root {k} diverged",
        )
    return compiled


from graphs import random_feeds as make_feeds  # noqa: E402,F401  (canonical copy)


@pytest.fixture
def rng():
    return np.random.RandomState(0)
