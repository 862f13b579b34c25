"""Launch-layer tests: the compile cache, the device report, and the serve
and train launchers' sizing."""
import jax
import pytest


@pytest.fixture
def cache_dir_restored():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_restored):
    from repro.launch.runtime import CHECKOUT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert (CHECKOUT / "chip_smoke.py").exists()   # CHECKOUT is the repo root
    assert enable_compile_cache() == path          # fixed: same path every call


def test_compile_cache_follows_env(monkeypatch, cache_dir_restored, tmp_path):
    from repro.launch.runtime import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_device_info_reports_jax_devices():
    from repro.launch.runtime import device_info

    info = device_info()
    assert info == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }


class _Stop(Exception):
    pass


@pytest.mark.parametrize("reduced,d_model", [(False, 1024), (True, 64)])
def test_serve_launcher_reduces_only_when_asked(
    reduced, d_model, capsys, monkeypatch, cache_dir_restored
):
    from repro.launch import serve

    def init_params(cfg, seed=0):
        raise _Stop(cfg)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(serve, "init_params", init_params)
    with pytest.raises(_Stop) as stop:
        serve.main(["--arch", "qwen1.5-0.5b"] + (["--reduced"] if reduced else []))
    assert stop.value.args[0].d_model == d_model
    assert capsys.readouterr().out.splitlines()[0].startswith("[serve] devices: cpu")


def test_serve_launcher_serves_reduced(capsys, monkeypatch, cache_dir_restored):
    from repro.launch import serve

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    serve.main(["--arch", "qwen1.5-0.5b", "--reduced", "--requests", "2",
                "--max-new", "2"])
    assert "[serve] 2/2 done" in capsys.readouterr().out


@pytest.mark.parametrize("reduced,d_model", [(False, 1024), (True, 64)])
def test_train_launcher_reduces_only_when_asked(
    reduced, d_model, capsys, monkeypatch, cache_dir_restored
):
    from repro.launch import train

    def init_params(cfg, seed=0):
        raise _Stop(cfg)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(train, "init_params", init_params)
    with pytest.raises(_Stop) as stop:
        train.main(["--arch", "qwen1.5-0.5b"] + (["--reduced"] if reduced else []))
    assert stop.value.args[0].d_model == d_model
    assert capsys.readouterr().out.splitlines()[0].startswith("[train] devices: cpu")
