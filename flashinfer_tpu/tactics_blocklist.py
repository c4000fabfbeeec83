"""Known-bad tactic exclusion list.

TPU re-design of the reference's tactics blocklist
(``flashinfer/tactics_blocklist.py`` + generator): a JSON list of
(op_name, tactic) pairs the autotuner must never select — the escape hatch
for kernel parameters that compile but miscompute or hang on specific
hardware.  Ships with built-in entries; extendable via
``FLASHINFER_TPU_TACTICS_BLOCKLIST`` (path to a JSON file of
``[{"op": ..., "tactic": ...}, ...]``).  A malformed file logs a warning
(never silently disables the safety net).

A third source is the bring-up quarantine (``bringup_quarantine.json``,
written by ``obs bringup`` when a smoke-ladder rung wedges the chip):
entries carrying both ``op`` and ``tactic`` join the blocklist, so the
autotuner resolver and the choosers skip wedge-proven tactics without any
extra plumbing at the call sites.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, List, Optional, Sequence, Tuple

# Built-in entries: (op_name, tactic) in json-normalized form.  Populated as
# hardware regressions are found with reproduced evidence.
_BUILTIN: List[Tuple[str, Any]] = []

_ext_cache: Optional[Tuple[str, List[Tuple[str, Any]]]] = None  # (path, entries)


def _normalize(tactic: Any) -> Any:
    """Canonical comparison form: json round-trip turns nested tuples into
    nested lists so Python tactics match file entries."""
    return json.loads(json.dumps(tactic))


def _load_external() -> List[Tuple[str, Any]]:
    global _ext_cache
    path = os.environ.get("FLASHINFER_TPU_TACTICS_BLOCKLIST")
    if not path:
        return []
    if _ext_cache is not None and _ext_cache[0] == path:
        return _ext_cache[1]
    entries: List[Tuple[str, Any]] = []
    try:
        data = json.loads(open(path).read())
        entries = [(e["op"], _normalize(e["tactic"])) for e in data]
    except Exception as e:
        logging.getLogger("flashinfer_tpu").warning(
            "FLASHINFER_TPU_TACTICS_BLOCKLIST %r unreadable (%r) — "
            "blocklist entries from this file are NOT active", path, e,
        )
    _ext_cache = (path, entries)
    return entries


# (path, mtime, raw entries) — quarantine reads are on chooser hot paths,
# so cache by mtime and never let a broken file raise
_bringup_cache: Optional[Tuple[str, float, List[dict]]] = None


def bringup_quarantine_path() -> str:
    """Where ``obs bringup`` writes wedge attributions.  Defined here (not
    in obs/) so the blocklist can consult it without importing obs."""
    p = os.environ.get("FLASHINFER_TPU_BRINGUP_QUARANTINE")
    if p:
        return p
    from flashinfer_tpu import env

    return str(env.cache_dir() / "bringup_quarantine.json")


def bringup_entries() -> List[dict]:
    """Raw quarantine entries (``[]`` when absent/unreadable).  Each is a
    dict with at least ``rung_id``/``reason``; knob rungs also carry
    ``op``/``tactic`` (consulted by :func:`blocked`) and ``bench_phases``
    (read by ``obs.bringup.quarantined_bench_phases``)."""
    global _bringup_cache
    path = bringup_quarantine_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return []
    if _bringup_cache is not None and _bringup_cache[:2] == (path, mtime):
        return _bringup_cache[2]
    entries: List[dict] = []
    try:
        data = json.loads(open(path).read())
        entries = [e for e in data if isinstance(e, dict)]
    except Exception as e:
        logging.getLogger("flashinfer_tpu").warning(
            "bring-up quarantine %r unreadable (%r) — wedge-proven "
            "tactics from it are NOT being skipped", path, e,
        )
    _bringup_cache = (path, mtime, entries)
    return entries


def _bringup_pairs() -> List[Tuple[str, Any]]:
    return [(e["op"], _normalize(e["tactic"])) for e in bringup_entries()
            if e.get("op") is not None and "tactic" in e]


def blocked(op_name: str, tactic: Any) -> bool:
    """True if (op, tactic) is blocklisted."""
    t = _normalize(tactic)
    for bop, btac in _BUILTIN + _load_external() + _bringup_pairs():
        if bop == op_name and btac == t:
            return True
    return False


def filter_candidates(op_name: str, candidates: Sequence[Any]) -> List[Any]:
    """Drop blocklisted candidates (keeps at least one)."""
    kept = [c for c in candidates if not blocked(op_name, c)]
    return kept or list(candidates[:1])
