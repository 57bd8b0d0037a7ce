"""Where the compile cache goes, and no silent CPU carry-on.

`configure_compile_cache` (tpu_olap/utils/platform.py) leaves JAX's own
setting alone when JAX_COMPILATION_CACHE_DIR is set and otherwise picks the
fixed <checkout>/.jax_cache; bench.py and chip_smoke.py exit non-zero on a
machine with no chip unless their explicit rehearsal switch is given — and
then never print a TPU name or a success.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from tpu_olap.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restore jax_compilation_cache_dir after a test moves it."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_environment_is_left_alone(monkeypatch, tmp_path,
                                                  cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # code set nothing


def test_cache_dir_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert platform.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert platform.configure_compile_cache() == want  # idempotent


def test_engine_places_the_cache(monkeypatch, cache_dir_config):
    from tpu_olap import Engine
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    Engine()
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(REPO, ".jax_cache")


def test_test_session_writes_no_cache_entries():
    # tests/conftest.py: XLA:CPU entries must not travel with chip calls
    assert jax.config.jax_enable_compilation_cache is False


def _run_child(args, **env):
    """Run a repo entry point as a child pinned to the CPU backend (the
    child needs no chip). Returns the CompletedProcess."""
    child_env = {k: v for k, v in os.environ.items()
                 if k not in ("BENCH_FORCE_CPU", "XLA_FLAGS")}
    child_env.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=child_env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("mode", [[], ["--ingest-mode"], ["--mesh", "4"]],
                         ids=["default", "ingest", "mesh"])
def test_bench_exits_nonzero_without_a_chip(mode):
    proc = _run_child(["bench.py", *mode])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no metric line at all
    assert "BENCH_FORCE_CPU=1" in proc.stderr


def test_chip_smoke_fails_at_the_device_phase_without_a_chip():
    proc = _run_child(["chip_smoke.py"])
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": last["device"]["kind"], "count": 1}}
    assert "answers:" not in proc.stdout  # stopped before any phase ran
    assert '"platform": "tpu"' not in proc.stdout


def test_chip_smoke_takes_rows_only_as_a_rehearsal():
    # a chip run cannot end "ok": true at a toy size
    proc = _run_child(["chip_smoke.py", "--rows", "60000"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "--allow-cpu" in proc.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_rehearsal_passes_but_never_reports_ok(chips):
    proc = _run_child(
        ["chip_smoke.py", "--allow-cpu", "--rows", "60000",
         "--chips", str(chips)],
        XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    lines = proc.stdout.strip().splitlines()
    assert "[chip-smoke] rehearsal: all phases passed" in lines, \
        proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips
    assert proc.returncode not in (0, 1)  # not success, not a failed phase
    assert '"ok": true' not in proc.stdout


def test_the_engine_keeps_the_memory_it_frees(monkeypatch):
    """glibc's malloc is told to keep freed memory (no trim under 1 GiB,
    no fresh mapping under 32 MiB): every Engine asks, the call takes
    effect on this Linux, and a libc without `mallopt` is a quiet no."""
    import ctypes

    from tpu_olap import Engine

    assert platform.retain_freed_memory() is True
    asked = []
    monkeypatch.setattr(platform, "retain_freed_memory",
                        lambda: asked.append(1))
    Engine()
    assert asked == [1]
    monkeypatch.undo()

    class NoMallopt:
        def __getattr__(self, name):
            raise AttributeError(name)

    monkeypatch.setattr(ctypes, "CDLL", lambda *a, **k: NoMallopt())
    assert platform.retain_freed_memory() is False
