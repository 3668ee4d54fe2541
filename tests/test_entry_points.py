"""Entry-point contracts: the compile-cache placement, the benchmark
harness's exit status, and `chip_smoke.py` refusing to run without a TPU."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache, obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defers_to_env(monkeypatch, tmp_path,
                                     restore_cache_dir):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expect = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.use_compile_cache() == expect
    assert jax.config.jax_compilation_cache_dir == expect


def test_benchmark_run_exits_nonzero_on_suite_failure(monkeypatch, tmp_path,
                                                      restore_cache_dir):
    from benchmarks import run

    def boom(*_args):
        raise RuntimeError("suite failed")

    monkeypatch.setitem(run.SUITES, "boom", boom)
    out = tmp_path / "bench.json"
    try:
        with pytest.raises(SystemExit) as exc:
            run.main(["--only", "boom", "--json", str(out)])
    finally:
        obs.disable()          # the harness turns tracing on globally
    assert exc.value.code not in (0, None)
    # The artifact is still written, carrying the failure.
    assert "suite failed" in json.loads(out.read_text())["suites"]["boom"][
        "error"]


def _python(*args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_chip_smoke_refuses_without_tpu():
    proc = _python("chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_sharding_import_has_no_deprecation_warning():
    proc = _python("-W", "error::DeprecationWarning", "-c",
                   "import repro.sharding, repro.launch.fl_round",
                   PYTHONPATH=os.path.join(ROOT, "src"))
    assert proc.returncode == 0, proc.stderr
