"""Persistent XLA compilation cache: amortize cold starts across runs.

The contraction planner cuts what a cold trace *builds*; this module makes
the XLA compile of what remains a one-time cost per (program, jaxlib,
backend) by pointing JAX's persistent compilation cache at a directory that
survives the process. The second run of the same launch/serve/bench program
then deserializes executables instead of recompiling them.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when set — JAX reads it itself, and this
  module sets no other directory;
* otherwise the fixed path ``<checkout>/.xla-cache`` (git-ignored). The
  directory is part of each entry's key, so it must not move between runs.

``REPRO_COMPILATION_CACHE_MIN_COMPILE_S`` (default ``0.5``) keeps programs
that compiled faster than that out of the cache.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

import jax

from .. import settings

CHECKOUT = Path(__file__).resolve().parents[3]


def cache_dir() -> Path:
    """The persistent compilation-cache directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env).expanduser()
    return CHECKOUT / ".xla-cache"


def enable_compilation_cache() -> Optional[Path]:
    """Point JAX's persistent compilation cache at `cache_dir()`.

    Idempotent and safe to call before any JAX computation (launch mains call
    it right after argument parsing). Returns the directory in use, or None
    when it cannot be used. Never raises: an unwritable directory just means
    cold compiles stay cold."""
    path = cache_dir()
    try:
        path.mkdir(parents=True, exist_ok=True)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(path))
        # persist anything that took real compile time; leave trivial
        # executables out so the cache stays small and the hit path hot
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            settings.get_float("REPRO_COMPILATION_CACHE_MIN_COMPILE_S"),
        )
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    except Exception as exc:  # pragma: no cover - depends on fs/jax build
        print(f"warning: persistent compilation cache disabled ({exc})")
        return None
    return path


def compilation_cache_stats() -> Dict:
    """Entry count + on-disk bytes of the persistent cache directory (the
    bench stage prints this so the warm path is visibly exercised)."""
    path = cache_dir()
    if not path.is_dir():
        return {"dir": str(path), "entries": 0, "bytes": 0}
    files = [p for p in path.rglob("*") if p.is_file()]
    return {
        "dir": str(path),
        "entries": len(files),
        "bytes": sum(p.stat().st_size for p in files),
    }
