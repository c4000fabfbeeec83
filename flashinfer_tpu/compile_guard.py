"""Mosaic compile-hang quarantine.

TPU-first operational infrastructure with no direct reference counterpart
(nearest analogue: the reference's compile-race regression protection,
``tests/utils/test_load_cubin_compile_race_condition.py``, and its tactics
blocklist).  On TPU the failure mode that matters is different: a bad
Mosaic compile can wedge the *chip*, not just the process — after which
every compile from any process hangs until the chip recovers.  One wedge
must therefore cost one kernel slot, never a whole session:

- Before the first compile of a kernel variant, a *pending marker*
  (fingerprint, pid, timestamp) is written to the cache dir; it is removed
  as soon as the compile+run completes.
- On startup, a stale marker whose owning process is dead and whose age
  exceeded the hang threshold is treated as evidence of a wedge: that
  fingerprint is moved to the persistent quarantine list and subsequent
  calls raise :class:`KernelQuarantined` (callers fall back to the XLA
  path) instead of re-wedging the chip.
- ``python -m flashinfer_tpu probe`` compiles a trivial kernel in a
  subprocess under a timeout — the recovery detector.

Fingerprints hash the op name, the kernel module's source text, and the
launch statics, so editing the kernel (the fix) automatically clears its
quarantine, while the same bad variant stays blocked across processes.
"""

from __future__ import annotations

import contextlib
import hashlib
import inspect
import json
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

from flashinfer_tpu import env

# a compile that survives this long without finishing is presumed wedged
# when its process is found dead (normal Mosaic compiles take 20-60s)
HANG_THRESHOLD_S = 180.0

_seen_ok: set = set()
# quarantined fps seen this process -> last disk check time: the negative
# cache keeps disk I/O off per-step fallback paths, but expires so an
# operator's external `quarantine --clear` takes effect within a minute
_seen_bad: Dict[str, float] = {}
_SEEN_BAD_TTL_S = 60.0
_source_digest_cache: Dict[str, str] = {}
_fp_cache: Dict[tuple, str] = {}


def trace_state_clean() -> bool:
    """True when not under a jax trace."""
    from jax._src.core import trace_state_clean as f

    return f()


class KernelQuarantined(RuntimeError):
    """Raised when a kernel variant is quarantined after a suspected
    compile wedge; callers should fall back to their XLA path."""


def _qdir() -> Path:
    return env.cache_dir() / "quarantine"


def _qlist_path() -> Path:
    return _qdir() / "kernels.json"


def _pending_dir() -> Path:
    return _qdir() / "pending"


def _module_source_digest(module: Any) -> str:
    key = getattr(module, "__name__", str(module))
    if key not in _source_digest_cache:
        try:
            src = inspect.getsource(module)
        except Exception:
            src = key
        _source_digest_cache[key] = hashlib.sha256(src.encode()).hexdigest()
    return _source_digest_cache[key]


def fingerprint(op_name: str, statics: Any, module: Any = None) -> str:
    # memoized per (op, statics-repr, module): the steady-state guarded()
    # pass-through sits on µs-scale decode hot paths and must not re-hash
    # kernel source text per call
    mkey = getattr(module, "__name__", None) if module is not None else None
    ck = (op_name, repr(statics), mkey)
    fp = _fp_cache.get(ck)
    if fp is None:
        blob = ck[0] + "|" + ck[1]
        if module is not None:
            blob += "|" + _module_source_digest(module)
        fp = hashlib.sha256(blob.encode()).hexdigest()[:24]
        _fp_cache[ck] = fp
    return fp


def _load_qlist() -> Dict[str, dict]:
    try:
        return json.loads(_qlist_path().read_text())
    except Exception:
        return {}


def _save_qlist(q: Dict[str, dict]) -> None:
    from flashinfer_tpu.utils import atomic_write_text

    atomic_write_text(_qlist_path(), json.dumps(q, indent=1))


def quarantine(fp: str, op_name: str, reason: str) -> None:
    q = _load_qlist()
    q[fp] = {"op": op_name, "reason": reason, "ts": time.time()}
    _save_qlist(q)


def clear(fp: Optional[str] = None) -> int:
    """Remove one fingerprint (or all) from the quarantine list."""
    q = _load_qlist()
    n = len(q)
    if fp is None:
        q = {}
        _seen_bad.clear()
    else:
        q.pop(fp, None)
        _seen_bad.pop(fp, None)
    _save_qlist(q)
    return n - len(q)


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def _sweep_stale_markers() -> None:
    """Promote dead-process pending markers older than the hang threshold
    into the quarantine list (the cross-process wedge detector)."""
    d = _pending_dir()
    if not d.is_dir():
        return
    now = time.time()
    for p in d.glob("*.json"):
        try:
            info = json.loads(p.read_text())
        except Exception:
            p.unlink(missing_ok=True)
            continue
        if _pid_alive(int(info.get("pid", -1))):
            continue
        if now - float(info.get("ts", now)) >= HANG_THRESHOLD_S:
            quarantine(
                p.stem, info.get("op", "?"),
                "stale compile marker from dead process "
                f"(pid {info.get('pid')}, started {info.get('ts')})",
            )
        p.unlink(missing_ok=True)


def _enabled() -> bool:
    flag = os.environ.get("FLASHINFER_TPU_COMPILE_GUARD")
    if flag is not None:
        return flag not in ("0", "false", "")
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:
        return False


def guarded(
    op_name: str,
    statics: Any,
    thunk: Callable[[], Any],
    module: Any = None,
):
    """Run ``thunk`` under the quarantine protocol.

    First sight of a (op, statics, kernel-source) fingerprint: check the
    quarantine list, sweep stale markers, write a pending marker, run the
    thunk to completion (``block_until_ready`` so the Mosaic compile is
    inside the guarded window), then clear the marker.  Later calls with
    the same fingerprint are zero-overhead pass-throughs."""
    fp = fingerprint(op_name, statics, module)
    if fp in _seen_ok or not _enabled():
        return thunk()
    if module is not None:
        # static wedge-pattern lint runs once per module per process,
        # BEFORE the first hardware compile: a kernel matching a
        # known-wedging Mosaic pattern refuses to compile in strict mode
        # (default on real TPU) rather than risking the chip.  The
        # wedge lint lives in the analyzer package (the old wedge_lint
        # shim is retired — docs/migration.md)
        from flashinfer_tpu.analysis import wedge

        wedge.check_module(module)
    try:
        if not trace_state_clean():
            # Under an outer jit trace the thunk returns a tracer and
            # block_until_ready is a no-op — the real Mosaic compile happens
            # later, outside this window.  Recording OK here would be a false
            # claim of guard coverage, so pass through with no bookkeeping.
            return thunk()
    except Exception:
        pass
    last = _seen_bad.get(fp)
    if last is not None and time.time() - last < _SEEN_BAD_TTL_S:
        raise KernelQuarantined(
            f"{op_name} variant {fp} is quarantined (clear with "
            f"`python -m flashinfer_tpu quarantine --clear {fp}`; an "
            f"external clear takes effect within {int(_SEEN_BAD_TTL_S)}s)"
        )
    _seen_bad.pop(fp, None)
    _sweep_stale_markers()
    if fp in _load_qlist():
        _seen_bad[fp] = time.time()
        raise KernelQuarantined(
            f"{op_name} variant {fp} is quarantined after a suspected "
            "compile wedge; falling back (clear with "
            f"`python -m flashinfer_tpu quarantine --clear {fp}`)"
        )
    d = _pending_dir()
    d.mkdir(parents=True, exist_ok=True)
    marker = d / f"{fp}.json"
    # O_EXCL: when two processes race to first-compile the same variant,
    # only one owns the marker — the other must not erase it on success
    # while the owner may still be mid-compile
    owns_marker = False
    try:
        fd = os.open(marker, os.O_WRONLY | os.O_CREAT | os.O_EXCL)
        with os.fdopen(fd, "w") as fh:
            fh.write(json.dumps(
                {"op": op_name, "pid": os.getpid(), "ts": time.time()}
            ))
        owns_marker = True
    except FileExistsError:
        pass
    t0 = time.time()
    try:
        import jax

        out = thunk()
        jax.block_until_ready(out)
    finally:
        # reached on success or a *raising* failure; a hard hang leaves the
        # marker for the next process's sweep — by design
        if owns_marker:
            with contextlib.suppress(OSError):
                marker.unlink()
    _seen_ok.add(fp)
    _record_status(fp, op_name, time.time() - t0)
    return out


def guarded_jit(fn: Callable, op_name: Optional[str] = None, **jit_kwargs):
    """``jax.jit`` whose first execution per argument-signature runs under
    :func:`guarded` — the helper bench/ad-hoc scripts must use so *every*
    first Mosaic compile is inside the quarantine protocol (the round-2
    wedge escaped through an unguarded ad-hoc bench; see repo memory).

    The signature fingerprint is (shape, dtype) of every array argument
    plus reprs of non-arrays, matching jit's own retrace key closely
    enough that each fresh compile gets its own guarded window."""
    import jax

    jf = jax.jit(fn, **jit_kwargs)
    name = op_name or getattr(fn, "__name__", "guarded_jit")

    def _sig(x):
        s = getattr(x, "shape", None)
        return (s, str(getattr(x, "dtype", ""))) if s is not None else repr(x)

    def wrapper(*args, **kwargs):
        statics = jax.tree_util.tree_map(_sig, (args, kwargs))
        return guarded(name, statics, lambda: jf(*args, **kwargs))

    wrapper.__wrapped__ = jf
    return wrapper


def _status_path() -> Path:
    return _qdir() / "compile_status.json"


def _record_status(fp: str, op_name: str, duration: float) -> None:
    """Compile-status registry (reference jit-core's module status role):
    every first compile that completed under the guard, with its duration —
    ``python -m flashinfer_tpu module-status`` surfaces it."""
    try:
        try:
            reg = json.loads(_status_path().read_text())
        except Exception:
            reg = {}
        reg[fp] = {
            "op": op_name, "status": "ok",
            "compile_s": round(duration, 2), "ts": round(time.time(), 1),
        }
        from flashinfer_tpu.utils import atomic_write_text

        atomic_write_text(_status_path(), json.dumps(reg, indent=1))
    except Exception:
        pass  # telemetry must never break the op


def compile_status() -> Dict[str, dict]:
    try:
        return json.loads(_status_path().read_text())
    except Exception:
        return {}


def probe(timeout_s: float = 240.0, interpret: bool = False) -> dict:
    """Compile a trivial Pallas kernel in a subprocess under a timeout.

    Returns ``{"healthy": bool, "elapsed": s, "detail": str}`` — the
    recovery detector to run after a wedge before resuming kernel work.
    ``interpret=True`` probes the interpret path instead (pallas_call on
    CPU refuses the compiled path outright, so an off-TPU bring-up
    selftest would read every probe as a wedge without it)."""
    import subprocess
    import sys

    flag = ", interpret=True" if interpret else ""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "def k(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] * 2.0\n"
        "x = jnp.ones((8, 128), jnp.float32)\n"
        "y = pl.pallas_call(k, out_shape=jax.ShapeDtypeStruct((8, 128), "
        f"jnp.float32){flag})(x)\n"
        "jax.block_until_ready(y)\n"
        "print('PROBE_OK')\n"
    )
    t0 = time.time()
    # Popen + bounded reaps, not subprocess.run: a wedged compile can leave
    # the child unkillable, and run()'s internal post-kill wait() would
    # then hang the *prober* too
    p = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        out, err = p.communicate(timeout=timeout_s)
        ok = "PROBE_OK" in out
        detail = out[-200:] if ok else (err or out)[-500:]
    except subprocess.TimeoutExpired:
        p.kill()
        with contextlib.suppress(Exception):
            p.communicate(timeout=10)
        ok, detail = False, f"probe timed out after {timeout_s}s (chip wedged?)"
    return {"healthy": ok, "elapsed": round(time.time() - t0, 1), "detail": detail}
