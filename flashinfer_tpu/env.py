"""Environment/flag system for flashinfer-tpu.

The reference configures itself purely through environment variables read at
import/call time (survey of ``flashinfer/jit/env.py:58-110``,
``flashinfer/api_logging.py:47-66``).  We keep the same design: a small,
documented set of ``FLASHINFER_TPU_*`` env vars, read lazily so tests can
monkeypatch them.

Principal flags
---------------
FLASHINFER_TPU_LOGLEVEL       int 0-10, api-call logging verbosity (default 0)
FLASHINFER_TPU_BACKEND        "auto" | "pallas" | "xla" — global backend override
FLASHINFER_TPU_INTERPRET      "1" forces Pallas interpret mode (CPU debugging)
FLASHINFER_TPU_CACHE_DIR      the library's own state: native planner .so,
                              autotuner tactics, quarantine list, dumps
FLASHINFER_TPU_DUMP_DIR       directory for api-logging tensor dumps

The XLA persistent compilation cache is NOT placed by these: it lives
where JAX's own ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise in one
fixed git-ignored directory of the checkout (:func:`xla_cache_dir`).
"""

from __future__ import annotations

import os
from pathlib import Path


def _env(name: str, default: str) -> str:
    return os.environ.get(name, default)


def log_level() -> int:
    try:
        return int(_env("FLASHINFER_TPU_LOGLEVEL", "0"))
    except ValueError:
        return 0


def backend_override() -> str:
    """Global backend selector: "auto" (default), "pallas", or "xla"."""
    v = _env("FLASHINFER_TPU_BACKEND", "auto").lower()
    if v not in ("auto", "pallas", "xla"):
        raise ValueError(f"FLASHINFER_TPU_BACKEND must be auto|pallas|xla, got {v!r}")
    return v


def force_interpret() -> bool:
    return _env("FLASHINFER_TPU_INTERPRET", "0") == "1"


def cache_dir() -> Path:
    d = _env(
        "FLASHINFER_TPU_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "flashinfer_tpu"),
    )
    return Path(d)


def dump_dir() -> Path:
    return Path(_env("FLASHINFER_TPU_DUMP_DIR", str(cache_dir() / "dumps")))


def xla_cache_dir() -> Path:
    """The XLA persistent compilation cache directory: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``.xla_cache`` at the root
    of the checkout.  The path is part of the cache key, so it is one
    fixed place — never one built from a temp name, a pid or a time."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(d) if d else \
        Path(__file__).resolve().parent.parent / ".xla_cache"


def enable_compilation_cache() -> None:
    """Turn on the XLA persistent compilation cache at
    :func:`xla_cache_dir` — compiled executables are re-loaded with no
    recompile by later processes (the TPU analogue of the reference's
    on-disk JIT cache, ``flashinfer/jit/core.py:225-321``).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already taken
    that directory from the environment and no other is set here."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(xla_cache_dir()))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
