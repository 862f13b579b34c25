"""The chip smoke's phases on the CPU at small sizes, in interpret mode,
and its refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduced_config  # noqa: E402


def test_serve_phase_reduced():
    cfg = reduced_config(get_config("qwen1.5-0.5b"))
    r = chip_smoke.serve_phase(
        cfg, pool=2, max_len=64, block_size=16, prompt_lens=(8, 20, 33),
        max_new=4,
    )
    assert r["done"] == r["requests"] == 3
    assert r["remaining"] == 0
    assert r["tokens"] == 12
    # f32 on the CPU: the engine's greedy tokens are the forward's argmax
    assert r["agree"] == r["agree_of"] == 4


@pytest.mark.parametrize("name", ["swiglu", "attention", "softmax_transpose"])
def test_stitch_phase_small(name):
    fn, args = chip_smoke.stitch_programs(full=False)[name]
    r = chip_smoke.stitch_phase(name, fn, args)
    assert r["fallbacks"] == 0
    assert r["interpret"] is True          # no TPU: the Pallas interpreter
    assert r["stitched"] >= 1
    assert r["err"] <= chip_smoke.STITCH_TOL


def test_sharded_phase_small():
    devices = jax.devices()[:4]
    assert len(devices) == 4               # conftest gives the CPU 8 devices
    r = chip_smoke.sharded_phase(devices, tokens=16, d_model=128, d_ff=256)
    assert r["devices"] == 4
    assert r["collectives"] == 2
    assert r["err"] <= chip_smoke.STITCH_TOL


def test_check_raises_smoke_failure():
    with pytest.raises(chip_smoke.SmokeFailure, match="broken"):
        chip_smoke.check(False, "broken")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )


def test_exits_nonzero_without_tpu():
    p = _run(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


def test_exits_nonzero_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = _run(str(tmp_path / "chip_smoke.py"), tmp_path)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
