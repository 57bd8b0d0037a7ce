"""Platform helpers shared by tests, bench, tools, and driver entries.

Two machines run this code. The sandbox has no accelerator: tests and CPU
rehearsals pin JAX to the host platform (``JAX_PLATFORMS=cpu``, N virtual
devices via ``--xla_force_host_platform_device_count``) through
`force_cpu_devices` — the one home for that, so tests/conftest.py,
bench.py's ``BENCH_FORCE_CPU=1`` rehearsal and the tools cannot drift. The
chip machine runs JAX on its TPU by default and one process owns the chip;
nothing here ever falls back from it to the CPU on its own.

`configure_compile_cache` places JAX's persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX reads it; no code
sets another), otherwise at the fixed path ``<checkout>/.jax_cache`` — the
path is part of the cache key, so it never carries a temp name, pid or time.
"""

from __future__ import annotations

import os
import re

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compile cache (module docstring) and return
    the directory in use. Idempotent; Engine.__init__ calls it before
    the first jit. Only the location is set here — whether the cache is
    enabled stays JAX's own switch (tests/conftest.py turns it off)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_compilation_cache_dir != COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def ensure_host_device_count(n: int) -> None:
    """Set (or raise) the virtual host-platform device count to >= n.

    Only effective before jax initializes its backends; a no-op when the
    flag is already >= n.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        if "xla_force_host_platform_device_count" in flags:
            return  # caller set it in a spelling we don't parse; trust it
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}")


def force_cpu_platform() -> bool:
    """Force jax onto the CPU platform; True if the config took effect.

    Safe to call when a backend is already up (returns False then — the
    caller decides whether the current platform is acceptable).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        return True
    except Exception:
        return False


def force_cpu_devices(n: int) -> None:
    """Ensure >= n JAX devices exist on the virtual-CPU platform."""
    ensure_host_device_count(n)
    force_cpu_platform()
    import jax

    if jax.device_count() < n:
        raise RuntimeError(
            f"need {n} devices, have {jax.devices()}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "JAX_PLATFORMS=cpu before jax initializes")


def env_flag(name: str, default: bool = False) -> bool:
    """Parse a 0/1/true/false-style env flag."""
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("", "0", "false", "no", "off")


def retain_freed_memory() -> bool:
    """Keep the memory the process frees (glibc's malloc; elsewhere a
    no-op, False). Engine.__init__ calls it.

    A query's host temporaries are the same few megabytes every time:
    the tables a mesh's sparse dispatch fetches, the concatenations and
    the sort of the broker's merge. Left to its dynamic thresholds glibc
    hands them back to the kernel (a heap top past the trim threshold, a
    chunk past the mmap threshold) and faults them in again, page by
    page, at the next query -- or keeps them, as the thresholds and the
    heap's layout happen to stand, for seconds at a time. On the chip
    machine that alone moved a q10's fetch between 11 and 33 ms and its
    merge between 31 and 92 (PERF.md, PR 36). So: the mmap threshold at
    its largest (32 MiB), the trim threshold past any query's
    temporaries (1 GiB); setting either also ends the dynamic
    adjustment."""
    import ctypes

    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    m_trim_threshold, m_mmap_threshold = -1, -3   # <malloc.h>
    return bool(mallopt(m_mmap_threshold, 32 << 20)
                and mallopt(m_trim_threshold, 1 << 30))
