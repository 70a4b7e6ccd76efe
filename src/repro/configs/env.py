"""Computation-environment configuration helpers (platform, precision,
host-device count, debug flags).

One home for the ad-hoc ``jax.config`` / ``XLA_FLAGS`` fiddling the
benchmarks used to do inline: the bit-identity matrix needs x64, the
distributed smokes need a forced host-device count, and every entry
point that compiles for the chip keeps JAX's persistent compilation
cache in one place (:func:`enable_compile_cache`).  All of these only
take full effect **before** jax initializes its backends, so entry
points call them at the top of ``main()``.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count

import jax

#: Environment variable naming JAX's persistent compilation cache
#: directory; where it is set, it wins over every default here.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fixed in-repo fallback cache directory (listed in ``.gitignore``).  A
#: fixed path, never a temp, pid or time-based one: the directory is
#: part of what makes a later run find the entries again.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` where it is set, else
    :data:`REPO_CACHE_DIR`.  Call before the first compile."""
    path = os.environ.get(COMPILE_CACHE_ENV, "").strip() or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def jax_enable_x64(use_x64: bool = True) -> None:
    """Toggle 64-bit array precision (the f64 bit-identity matrix and
    every oracle comparison in ``benchmarks/`` require it on)."""
    if not use_x64:
        use_x64 = bool(os.getenv("JAX_ENABLE_X64", 0))
    jax.config.update("jax_enable_x64", use_x64)


def set_cpu_cores(n: int) -> None:
    """Expose ``n`` forced host devices on the CPU platform (the
    distributed smokes' 8-device mesh, the 256-device cluster-mapping
    bench).  Host devices are virtual — ``n`` may exceed the physical
    core count (a warning notes the oversubscription; compute then
    time-slices, which is fine for compile-only/HLO-counting runs).
    Must run before jax initializes its backends."""
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(
            f"forcing {n} host devices on {total} CPUs: compute will "
            "time-slice (fine for compile/HLO analysis)", Warning,
            stacklevel=2)
    have = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    kept = " ".join(f for f in have.split()
                    if not f.startswith("--xla_force_host_platform"
                                        "_device_count"))
    os.environ["XLA_FLAGS"] = (kept + " " + flag).strip()


def set_debug_nan(flag: bool = True) -> None:
    """Raise on NaN production (debugging aid; costs a device sync per
    op — never leave it on in a benchmark run)."""
    jax.config.update("jax_debug_nans", flag)
