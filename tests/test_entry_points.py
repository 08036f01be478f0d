"""The user-facing entry points on the CPU: the serving launcher,
``chip_smoke.py`` and the compile-cache helper they share."""
from __future__ import annotations

import importlib.util
import pathlib
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def no_cache_change(monkeypatch, tmp_path):
    """Entry points place the compile cache; with the variable set they
    set no directory, and the threshold they lower is put back, so a test
    leaves this process's JAX config alone."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    min_time = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_time)


def _serve_main(monkeypatch, *argv):
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve", *argv])
    serve.main()


def test_serve_reduced_arch_serves_on_cpu(monkeypatch, capsys,
                                          no_cache_change):
    _serve_main(monkeypatch, "--arch", "qwen3-0.6b-reduced",
                "--containers", "1", "--requests", "2", "--max-new", "2")
    out = capsys.readouterr().out
    assert "streamed 2 requests" in out
    assert "n=1 (concurrent+stream): 2 requests, 4 tokens" in out


def test_serve_builds_the_published_widths(monkeypatch, no_cache_change):
    """``--arch qwen3-0.6b`` is the published config: nothing appends
    ``-reduced`` behind the caller's back."""
    from repro.launch import serve
    built = []

    class Stop(Exception):
        pass

    def fake_model(cfg):
        built.append(cfg)
        raise Stop
    monkeypatch.setattr(serve, "Model", fake_model)
    with pytest.raises(Stop):
        _serve_main(monkeypatch, "--arch", "qwen3-0.6b", "--containers", "1")
    (cfg,) = built
    assert (cfg.name, cfg.n_layers, cfg.d_model, cfg.vocab_size) == (
        "qwen3-0.6b", 28, 1024, 151_936)


def test_serve_refuses_process_isolation_on_a_tpu_parent(monkeypatch,
                                                         capsys,
                                                         no_cache_change):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit) as exc:
        _serve_main(monkeypatch, "--arch", "qwen3-0.6b-reduced",
                    "--containers", "2", "--isolation", "process")
    assert exc.value.code == 2
    assert "process isolation needs a CPU parent" in capsys.readouterr().err


def test_serve_exits_nonzero_after_a_container_failure(monkeypatch,
                                                       no_cache_change):
    """An engine step that raised fails the run even though the Router
    retried its requests to completion on a respawned engine."""
    from repro.serving.engine import ServingEngine
    real_step = ServingEngine.step
    raised = []

    def step_once_broken(self):
        if not raised:
            raised.append(True)
            raise RuntimeError("kernel failed")
        return real_step(self)
    monkeypatch.setattr(ServingEngine, "step", step_once_broken)
    with pytest.raises(SystemExit) as exc:
        _serve_main(monkeypatch, "--arch", "qwen3-0.6b-reduced",
                    "--containers", "1", "--requests", "2", "--max-new", "2")
    assert "container failure" in str(exc.value.code)
    assert "kernel failed" in str(exc.value.code)


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_the_cpu(monkeypatch, capsys, no_cache_change):
    smoke = _load_chip_smoke()
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert smoke.main() == 1
    out, err = capsys.readouterr()
    assert "JAX found platform 'cpu'" in err
    assert '"ok"' not in out


def test_chip_smoke_phases_on_reduced_widths(reduced_models):
    """The one-chip phases at CPU size: every request completes, n=1 and
    n=2 agree, and the only problems are the ones a CPU must report (no
    Pallas call in the served programs: the CPU runs the jnp route)."""
    smoke = _load_chip_smoke()
    model, params = reduced_models["qwen3-0.6b"]
    problems = []
    smoke.one_chip(model, params,
                   smoke.make_requests(model.cfg.vocab_size, seed=0), 0,
                   problems)
    assert problems == [f"served {name} program has no Pallas call"
                        for name in ("prefill", "decode (dense)",
                                     "decode (paged)")]


def test_compile_cache_placement(monkeypatch, tmp_path, no_cache_change):
    from repro import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # sub-second programs are cached too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
