"""The benchmark's files: ``BENCHMARK.json``'s shape, discovery by name, the
programs' operation and byte counts, and the command's refusal to run
without a TPU."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
    used = {w["config"] for w in spec["workloads"]}
    assert used == names
    cells = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["name"] not in cells
        cells.add(w["name"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(cells) // 2)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"))
        for cell in m.get("workloads", ()):
            assert cell in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]
    for cell in cells:
        reported = [m for m in spec["end_to_end"]
                    if "workloads" not in m or cell in m["workloads"]]
        assert len(reported) >= 2
        assert any("workloads" not in m or cell in m["workloads"]
                   for m in spec["per_layer"])
    assert len(json.dumps(spec)) < 64 * 1024


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_config_and_metric_found_by_name(tmp_path, spec):
    """Files added in a copy are found by name; no existing file changes
    except the index ``BENCHMARK.json``."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root / "bench")
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "qwen1.5-0.5b-f32-5layers.json")))
    cfg.update(name="qwen-new", num_hidden_layers=2)
    (root / "bench" / "configs" / "qwen-new.json").write_text(json.dumps(cfg))
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic", "decode.json")))
    mix["rows"] = 64
    (root / "bench" / "traffic" / "decode64.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "calls.stitch.py").write_text(
        "def read(run):\n    return run.get('jit_calls')\n")
    spec = json.loads(json.dumps(spec))
    spec["configs"].append({"name": "qwen-new", "source": "x", "reduced": [],
                            "file": "bench/configs/qwen-new.json", "why": "x"})
    spec["workloads"].append({"name": "stitch.qwen-new.decode64", "config": "qwen-new",
                              "traffic": "decode64", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "calls.stitch", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "runtime", "moves": "call_ms",
                              "workloads": ["stitch.qwen-new.decode64"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and "stitch.qwen1.5-0.5b.decode" in m["workloads"]:
            m["workloads"].append("stitch.qwen-new.decode64")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("stitch.qwen-new.decode64", root=str(root))
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["rows"] == 64
    assert harness.driver(cell).__name__.endswith("stitch_cell")
    assert "call_ms" in [m["name"] for m in cell.end_to_end]
    names = [m["name"] for m in cell.per_layer]
    assert "calls.stitch" in names
    got = harness.read_per_layer(cell, {"jit_calls": 3})
    assert got == {"calls.stitch": {"value": 3, "unit": "calls"}}
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_workload_and_device_kind_are_errors():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no.such.cell")
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _prog(name):
    cell = harness.load_cell(name)
    return cell, harness.module(cell, "programs", cell.traffic["program"] + ".py")


def test_qwen_decode_counts_by_hand():
    cell, prog = _prog("stitch.qwen1.5-0.5b.decode")
    cfg = dict(cell.config, hidden_size=8, num_attention_heads=2,
               intermediate_size=12, num_hidden_layers=3)
    tr = dict(cell.traffic, rows=2, context=5, context_min=1)
    flops, nbytes = prog.cost(cfg, tr)
    # per layer: 2 rows x (4 projections 8x8 + 3 of 8x12) x 2, plus scores
    # and weighted sum over 5 positions of 2 heads of 4
    per_layer = 2 * 2 * (4 * 64 + 3 * 96) + 2 * 2 * (2 * 2 * 5 * 4)
    assert flops == 3 * per_layer
    weights = 3 * (4 * 64 + 3 * 8 + 3 * 96 + 2 * 8)
    kv = 3 * 2 * (2 * 2 * 5 * 4)
    io = 2 * 8 + 2 * 5 + 2 * 2 * 4 + 2 * 8 + 3 * 2 * 2 * 8
    assert nbytes == 4 * (weights + kv + io)
    shapes = prog.arg_shapes(cfg, tr)
    leaves = [x for x in shapes[:4]] + [v for lp in shapes[4] for v in lp.values()]
    assert sum(int(np.prod(s.shape)) for s in leaves) == weights + kv + 2 * 8 + 2 * 5 + 2 * 2 * 4


def test_no_tpu_means_no_result(tmp_path):
    """Without a TPU the command exits non-zero and prints nothing on stdout;
    so it does in a copy holding only the benchmark's own files."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload", "stitch.qwen1.5-0.5b.decode",
           "--seed", "3", "--seconds", "1", "--trace", "0"]
    r = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no TPU" in r.stderr
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""
