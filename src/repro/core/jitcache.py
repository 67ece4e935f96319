"""Persistent (cross-process) XLA compilation cache, always on.

The shape-bucket planner (:mod:`repro.core.shapes`) bounds how many
distinct static signatures a process compiles — geometric rungs plus a
hysteresis band keep the census small.  This module makes those few
compiles survive the process, so a later run's first decision pays a
disk read instead of an XLA compile.  That matters most on the TPU,
where the float64 decision kernels are emulated and one large-cluster
compile takes minutes.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment: jax reads it
  itself and this module sets no directory of its own;
* otherwise ``results/.jax_cache`` inside the checkout, resolved from
  this file (never from the working directory, a temp name, a pid or
  the time), so every process run from one checkout shares it.

Every compile is cached, however small: the placement kernels are
individually quick to compile on the CPU but numerous across lanes.
:func:`status` feeds :func:`repro.telemetry.snapshot`.
"""

from __future__ import annotations

import os
import pathlib
from typing import Any

import jax

__all__ = ["ENV_DIR", "CHECKOUT_DIR", "cache_dir", "configure", "status"]

#: jax's own environment variable for the cache directory.
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
#: the cache directory when ``ENV_DIR`` is unset (listed in .gitignore).
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / ".jax_cache"


def cache_dir() -> pathlib.Path:
    """The directory jax reads and writes compiled programs in."""
    return pathlib.Path(os.environ.get(ENV_DIR) or CHECKOUT_DIR)


def configure() -> None:
    """Point jax's persistent compilation cache at :func:`cache_dir`.

    Called on :mod:`repro.core.shapes` import, i.e. before any kernel
    module traces its first jit — the config must precede the first
    compile for that compile to be written to (or served from) disk.
    Idempotent.
    """
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def status() -> dict[str, Any]:
    """Telemetry view: cache directory, where it came from, entry count."""
    d = cache_dir()
    return {
        "dir": str(d),
        "source": "env" if os.environ.get(ENV_DIR) else "checkout",
        "entries": sum(1 for _ in d.iterdir()) if d.is_dir() else 0,
    }
