"""Hardware graduation observatory — ``python -m flashinfer_tpu.obs bringup``.

ROADMAP item 1's chip session, turned from a prose checklist into a
machine-driven harness (the reference library survives this class of
risk by making kernel specialization + validation machine-driven; our
analog consumes the machine-readable risk registries directly):

**Smoke ladder.**  One minimal real launch per risky
(kernel, construct, tactic) triple, generated from three registries —
L015 ``mosaic_risks`` in analysis/baseline.json (riskiest construct
class first: strided-lane, then lane-slice, then cast), L007
``PLANNER_KERNELS`` (plan/run contract pairs), and L009
``KNOB_LAUNCHES`` (one rung per autotuned knob, carrying the shipped
tactic for the session's chip).  Each rung runs in its own subprocess
under a timeout, with a ``compile_guard.probe`` re-check between rungs
on hardware — so a Mosaic-compile wedge is attributed to the EXACT
rung instead of poisoning fourteen hours of session (the BENCH_r04/r05
failure mode).  A wedge halts the session and writes a quarantine
entry to ``bringup_quarantine.json``; knob-rung entries carry
``op``/``tactic`` so ``tactics_blocklist.blocked`` (hence the
autotuner resolver and the choosers) skips the wedge-proven tactic,
and ``bench_phases``, which ``obs perf`` joins to the tuning sections
it reports as quarantined.

**Session journal.**  Append-only JSONL (``bringup_journal.jsonl`` in
the cache dir) recording every rung/phase/sweep/probe with outcome and
wall time.  ``--resume`` skips entries whose last outcome is ``pass``
(and quarantined rungs), so a mid-session wedge costs one rung.
Journal entries and graduated tuning sections join to BENCH_BANKED.md
rows by the RowAuditor configuration stamp (``bench_audit.row_stamp``).

**Provenance graduation.**  ``--graduate`` consumes ``--emit-config``
JSON files (no sweep in the tree writes one today) plus the journal
and rewrites tuning_configs
sections ``seed -> "provenance": "measured"``, carrying
``{journal_id, banked_row}`` references that L006 requires on every
measured section.  ``obs perf`` reports per-section graduation status
(pending | measured | quarantined) in the perf/6 ``graduation``
section.

**Selftest.**  ``--selftest`` proves the whole contract on CPU: rung
coverage (every mosaic_risks entry and every KNOB_LAUNCHES binding
maps to exactly one rung), the full ladder in interpret mode, a
simulated wedge (a rung subprocess sleeping past its timeout) with
exact-rung attribution + quarantine + resume, and a graduation rewrite
on a synthetic emit-config validated by L006.

Module import stays jax-free (the doctor section consults it on
broken trees); drivers import jax lazily inside the rung
subprocess.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from flashinfer_tpu import tactics_blocklist

# construct classes of L015 mosaic_risks, riskiest first: strided lane
# reads and lane slices are the wedge-proven Mosaic territory (the PR 14
# head_dim//2 lane slice, the stride-2 token-pair interleave, the
# rowcache lane slice); cast-heavy kernels wedge rarely but still first-
# compile on the session's Mosaic version
RISK_ORDER = {"strided-lane": 0, "lane-slice": 1, "gather": 2, "cast": 3}

SIM_WEDGE_RUNG = "sim:wedge"
DEFAULT_RUNG_TIMEOUT_S = 420.0
DEFAULT_PROBE_TIMEOUT_S = 330.0

# kernel / launcher name -> driver key (the minimal-launch recipes below).
# Coverage is a selftest invariant: every mosaic_risks ``func`` and every
# KNOB_LAUNCHES launcher must resolve here, or the selftest fails — the
# same no-silent-skip rule as PLANNER_KERNELS / KNOB_LAUNCHES themselves.
DRIVER_FOR = {
    # L015 kernel functions
    "_rms_kernel": "rmsnorm",
    "_fused_add_rms_kernel": "fused_add_rmsnorm",
    "_bsr_kernel": "bsr",
    "_bsr_token_select_kernel": "bsr_token_select",
    "_vbsr_kernel": "vbsr",
    "_flash_kernel": "flash_attention",
    "_gdn_chunk_kernel": "gdn",
    "_kda_chunk_kernel": "kda",
    "_ssd_chunk_kernel": "mamba",
    "_mla_decode_kernel": "mla_decode",
    "_stream_kernel": "sparse_mla_stream",
    "_block_sparse_paged_kernel": "block_sparse_paged",
    "_gather_gmm_rowcache_kernel": "gather_gmm_rowcache",
    "_decode_split_kernel_fused_heads": "decode_split",
    "_fp4_decode_kernel": "fp4_decode",
    "_fused_prefill_ingest_kernel": "prefill_ingest",
    "_fused_prefill_kernel": "fused_prefill",
    # L009 launchers (KNOB_LAUNCHES values) and L007 planners
    "fused_paged_prefill": "fused_prefill",
    "flash_attention": "flash_attention",
    "paged_decode_attention_split": "decode_split",
    "_paged_decode_hnd_launch": "paged_decode",
    "gmm": "gmm",
    "fused_paged_prefill_ingest": "prefill_ingest",
    "build_prefill_work_units": "fused_prefill",
    "build_prefill_ingest_units": "prefill_ingest",
    "build_decode_split_units": "decode_split",
    "build_engine_work_units": "engine_step",
}

# the engine.attention_backend knob launches through the whole serving
# engine, not a bare kernel — give it the engine driver, not the
# launcher-derived fused_prefill one
KNOB_DRIVER = {"engine.attention_backend": "engine_step"}

# measurement phases a wedged rung poisons (written into the quarantine
# entry; `obs perf` reports their tuning sections as quarantined).  Knob
# rungs by knob name, kernel/planner rungs by kernel function.
KNOB_BENCH_PHASES = {
    "decode.splits": ["decode_splits"],
    "fused_prefill.blocks": ["prefill"],
    "flash_attention.blocks": ["prefill"],
    "prefill.fused_ingest": ["prefill"],
    "paged_decode.pages_per_chunk": ["decode"],
    "moe_gmm.tiles": ["moe"],
    "engine.attention_backend": ["serving_engine"],
}
KERNEL_BENCH_PHASES = {
    "_flash_kernel": ["prefill"],
    "_fused_prefill_kernel": ["prefill"],
    "_fused_prefill_ingest_kernel": ["prefill"],
    "_decode_split_kernel_fused_heads": ["decode_splits"],
    "_fp4_decode_kernel": ["decode"],
    "_mla_decode_kernel": ["mla"],
    "_gather_gmm_rowcache_kernel": ["moe"],
    "_gdn_chunk_kernel": ["scans"],
    "_kda_chunk_kernel": ["scans"],
    "_ssd_chunk_kernel": ["scans"],
}

# tuning_configs section -> banked phases whose RowAuditor stamps back a
# graduation (the join demanded by ISSUE 20's banked_row reference)
SECTION_BANK_PHASES = {
    "decode": ("decode_splits", "decode"),
    "prefill": ("prefill",),
    "prefill_ingest": ("prefill",),
    "parallel": ("serving_sharded",),
    "engine": ("serving_engine",),
    "kv_tier": ("serving_disagg",),
    "paged_decode": ("decode",),
    "moe": ("moe",),
}


# --------------------------------------------------------------------------
# Paths / journal
# --------------------------------------------------------------------------


def journal_path() -> str:
    p = os.environ.get("FLASHINFER_TPU_BRINGUP_JOURNAL")
    if p:
        return p
    from flashinfer_tpu import env

    return str(env.cache_dir() / "bringup_journal.jsonl")


def quarantine_path() -> str:
    return tactics_blocklist.bringup_quarantine_path()


class Journal:
    """Append-only JSONL session journal.  Every write is a full line
    flushed before return — a wedged process loses at most the entry it
    never got to write, never a partial file."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or journal_path()

    def entries(self) -> List[dict]:
        out: List[dict] = []
        try:
            with open(self.path) as fh:
                for line in fh:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        e = json.loads(line)
                    except ValueError:
                        continue  # a torn tail line from a killed writer
                    if isinstance(e, dict):
                        out.append(e)
        except OSError:
            pass
        return out

    def append(self, **entry) -> dict:
        entry.setdefault("ts", round(time.time(), 1))
        entries = None
        if "seq" not in entry:
            entries = self.entries()
            entry["seq"] = (max((e.get("seq", 0) for e in entries),
                                default=0) + 1)
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        with open(self.path, "a") as fh:
            fh.write(json.dumps(entry) + "\n")
            fh.flush()
        return entry

    def rung_outcomes(self) -> Dict[str, str]:
        """Last recorded outcome per rung id (skipped entries don't
        overwrite a real outcome — a resumed run must not launder a
        ``pass`` into ``skipped``)."""
        out: Dict[str, str] = {}
        for e in self.entries():
            if e.get("kind") != "rung" or not e.get("id"):
                continue
            if e.get("outcome") == "skipped" and e["id"] in out:
                continue
            out[e["id"]] = e.get("outcome", "")
        return out

    def step_outcomes(self, kind: str) -> Dict[str, str]:
        out: Dict[str, str] = {}
        for e in self.entries():
            if e.get("kind") == kind and e.get("id"):
                if e.get("outcome") == "skipped" and e["id"] in out:
                    continue
                out[e["id"]] = e.get("outcome", "")
        return out

    def last_session_id(self) -> Optional[str]:
        for e in reversed(self.entries()):
            if e.get("journal_id"):
                return e["journal_id"]
        return None


def new_journal_id() -> str:
    return "bringup-%s-%d" % (time.strftime("%Y%m%d-%H%M%S"), os.getpid())


def _load_quarantine(path: Optional[str] = None) -> List[dict]:
    path = path or quarantine_path()
    try:
        data = json.loads(open(path).read())
        return [e for e in data if isinstance(e, dict)]
    except Exception:
        return []


def quarantine_add(entry: dict, path: Optional[str] = None) -> None:
    path = path or quarantine_path()
    entries = _load_quarantine(path)
    entries.append(entry)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        fh.write(json.dumps(entries, indent=1) + "\n")
    os.replace(tmp, path)


def quarantined_bench_phases() -> List[str]:
    """Measurement phases any quarantine entry names."""
    out: List[str] = []
    for e in tactics_blocklist.bringup_entries():
        for p in e.get("bench_phases") or ():
            if p not in out:
                out.append(p)
    return out


def _counter_inc(outcome: str) -> None:
    try:  # telemetry must never cost a rung
        from flashinfer_tpu import obs

        obs.counter_inc("bringup.rungs", outcome=outcome)
    except Exception:
        pass


# --------------------------------------------------------------------------
# Ladder generation
# --------------------------------------------------------------------------


def _pkg_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_mosaic_risks() -> List[dict]:
    path = os.path.join(_pkg_root(), "analysis", "baseline.json")
    data = json.loads(open(path).read())
    return [e for e in data.get("mosaic_risks", []) if isinstance(e, dict)]


def _config_tactics(chip: str) -> Dict[str, Any]:
    path = os.path.join(_pkg_root(), "tuning_configs", f"{chip}.json")
    try:
        cfg = json.loads(open(path).read())
    except Exception:
        return {}
    out: Dict[str, Any] = {}
    for sec in cfg.values():
        if isinstance(sec, dict) and isinstance(sec.get("tactics"), dict):
            out.update(sec["tactics"])
    if isinstance(cfg.get("tactics"), dict):
        out.update(cfg["tactics"])
    return out


def _knob_tactic(knob: str, tactics: Dict[str, Any]):
    """(shape_key, tactic) of the first shipped entry for ``knob``, or
    (None, None) — the rung then smokes the driver's default tactic."""
    prefix = knob + "|"
    for key in sorted(tactics):
        if key.startswith(prefix):
            return key[len(prefix):], tactics[key]
    return None, None


def build_ladder(chip: str = "v5e") -> List[dict]:
    """The session's rung list: L015 mosaic_risks (riskiest class
    first), then L007 planner pairs, then L009 knob bindings with the
    shipped tactic for ``chip``.  Deterministic — the subprocess child
    rebuilds it to find its rung by id."""
    rungs: List[dict] = []
    risks = sorted(
        enumerate(load_mosaic_risks()),
        key=lambda ie: (RISK_ORDER.get(ie[1].get("rule"), 9), ie[0]))
    for _, e in risks:
        rungs.append({
            "rung_id": "l015:%s:%s" % (e.get("rule"), e.get("func")),
            "kind": "mosaic_risk", "rule": e.get("rule"),
            "path": e.get("path"), "func": e.get("func"),
            "driver": DRIVER_FOR.get(e.get("func")),
            "bench_phases": KERNEL_BENCH_PHASES.get(e.get("func"), []),
        })
    from flashinfer_tpu.analysis.pallas_contract import PLANNER_KERNELS

    for planner, kernel in PLANNER_KERNELS.items():
        rungs.append({
            "rung_id": f"l007:{planner}",
            "kind": "planner", "planner": planner, "func": kernel,
            "driver": DRIVER_FOR.get(planner),
            "bench_phases": KERNEL_BENCH_PHASES.get(kernel, []),
        })
    from flashinfer_tpu.analysis.vmem_budget import KNOB_LAUNCHES

    tactics = _config_tactics(chip)
    for knob, kl in KNOB_LAUNCHES.items():
        shape_key, tactic = _knob_tactic(knob, tactics)
        rungs.append({
            "rung_id": f"l009:{knob}",
            "kind": "knob", "knob": knob, "launcher": kl.launcher,
            "shape_key": shape_key, "tactic": tactic,
            "driver": KNOB_DRIVER.get(knob, DRIVER_FOR.get(kl.launcher)),
            "op": knob,
            "bench_phases": KNOB_BENCH_PHASES.get(knob, []),
        })
    return rungs


def coverage_problems(rungs: List[dict]) -> List[str]:
    """The selftest's bijection proof: every registry entry maps to
    exactly one rung, and every rung has a driver."""
    problems: List[str] = []
    ids = [r["rung_id"] for r in rungs]
    dupes = sorted({i for i in ids if ids.count(i) > 1})
    if dupes:
        problems.append(f"duplicate rung ids: {dupes}")
    by_id = {r["rung_id"]: r for r in rungs}
    for e in load_mosaic_risks():
        rid = "l015:%s:%s" % (e.get("rule"), e.get("func"))
        if rid not in by_id:
            problems.append(f"mosaic_risks entry without a rung: {rid}")
    from flashinfer_tpu.analysis.vmem_budget import KNOB_LAUNCHES

    for knob in KNOB_LAUNCHES:
        if f"l009:{knob}" not in by_id:
            problems.append(f"KNOB_LAUNCHES binding without a rung: {knob}")
    from flashinfer_tpu.analysis.pallas_contract import PLANNER_KERNELS

    for planner in PLANNER_KERNELS:
        if f"l007:{planner}" not in by_id:
            problems.append(f"PLANNER_KERNELS pair without a rung: {planner}")
    for r in rungs:
        if not r.get("driver") or r["driver"] not in DRIVERS:
            problems.append(
                "rung %s has no launch driver (kernel %r) — extend "
                "bringup.DRIVER_FOR" % (r["rung_id"],
                                        r.get("func") or r.get("launcher")))
    return problems


# --------------------------------------------------------------------------
# Minimal-launch drivers (cribbed from the hw tier recipes; shapes kept
# tiny but tile-legal so the interpret-mode selftest stays fast).  Each
# driver runs ONE real launch of its kernel and blocks on the result.
# ``tactic`` is the knob rung's shipped value, clamped to the minimal
# shape where needed — the rung proves the construct (and the tactic
# where it is shape-independent) Mosaic-compiles, not its performance.
# --------------------------------------------------------------------------


def _keys(n):
    import jax

    k = jax.random.PRNGKey(0)
    return [jax.random.fold_in(k, i) for i in range(n)]


def _drv_rmsnorm(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import rmsnorm

    x = jax.random.normal(_keys(1)[0], (256, 512), jnp.bfloat16)
    w = jnp.ones((512,), jnp.bfloat16)
    jax.block_until_ready(rmsnorm(x, w, backend="pallas"))


def _drv_fused_add_rmsnorm(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import fused_add_rmsnorm

    ka, kb = _keys(2)
    x = jax.random.normal(ka, (256, 512), jnp.bfloat16)
    r = jax.random.normal(kb, (256, 512), jnp.bfloat16)
    w = jnp.ones((512,), jnp.bfloat16)
    jax.block_until_ready(fused_add_rmsnorm(x, r, w, backend="pallas"))


def _drv_flash_attention(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops import flash_attention

    T, HQ, HKV, D = 256, 4, 2, 128
    ka, kb, kc = _keys(3)
    q = jax.random.normal(ka, (T, HQ, D), jnp.bfloat16)
    k = jax.random.normal(kb, (T, HKV, D), jnp.bfloat16)
    v = jax.random.normal(kc, (T, HKV, D), jnp.bfloat16)
    seg = jnp.zeros((T,), jnp.int32)
    pos = jnp.arange(T)
    kw = {}
    if isinstance(tactic, (list, tuple)) and len(tactic) == 2:
        kw = dict(block_q=min(int(tactic[0]), T),
                  block_kv=min(int(tactic[1]), T))
    jax.block_until_ready(flash_attention(
        q, k, v, seg, seg, pos, pos, causal=True, sm_scale=D ** -0.5, **kw))


def _drv_bsr(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi

    MB = NB = 2
    R = C = 128
    HQ, HKV, D = 4, 2, 128
    indptr = np.asarray([0, 2, 4], np.int32)   # dense 2x2 block mask
    indices = np.asarray([0, 1, 0, 1], np.int32)
    w = fi.sparse.BlockSparseAttentionWrapper(jnp.zeros(1024, jnp.uint8),
                                              backend="pallas")
    w.plan(indptr, indices, MB * R, NB * C, R, C, HQ, HKV, D)
    ka, kb, kc = _keys(3)
    q = jax.random.normal(ka, (MB * R, HQ, D), jnp.bfloat16)
    k = jax.random.normal(kb, (NB * C, HKV, D), jnp.bfloat16)
    v = jax.random.normal(kc, (NB * C, HKV, D), jnp.bfloat16)
    jax.block_until_ready(w.run(q, k, v))


def _drv_bsr_token_select(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.msa_ops import msa_sparse_attention

    N, HQ, HKV, D = 256, 4, 2, 128
    ka, kb, kc = _keys(3)
    q = jax.random.normal(ka, (N, HQ, D), jnp.bfloat16)
    k = jax.random.normal(kb, (N, HKV, D), jnp.bfloat16)
    v = jax.random.normal(kc, (N, HKV, D), jnp.bfloat16)
    jax.block_until_ready(msa_sparse_attention(
        q, k, v, top_k=2, backend="pallas", granularity="token"))


def _drv_vbsr(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flashinfer_tpu as fi

    HQ, HKV, D = 4, 2, 128
    row_sz = np.asarray([128, 128], np.int32)
    col_sz = np.asarray([128, 128], np.int32)
    mask = np.ones((1, 2, 2), bool)
    M = int(row_sz.sum())
    N = int(col_sz.sum())
    w = fi.sparse.VariableBlockSparseAttentionWrapper(
        jnp.zeros(1024, jnp.float32), backend="pallas")
    w.plan(block_mask_map=mask[0], block_row_sz=row_sz,
           block_col_sz=col_sz, num_qo_heads=HQ, num_kv_heads=HKV,
           head_dim=D, q_data_type=jnp.bfloat16)
    ka, kb, kc = _keys(3)
    q = jax.random.normal(ka, (HQ, M, D), jnp.bfloat16)
    k = jax.random.normal(kb, (HKV, N, D), jnp.bfloat16)
    v = jax.random.normal(kc, (HKV, N, D), jnp.bfloat16)
    jax.block_until_ready(w.run(q, k, v))


def _drv_gdn(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.ops.gdn_kernel import gdn_chunk_prefill_pallas

    rng = np.random.default_rng(0)
    B, L, H, dk, dv = 1, 128, 2, 128, 128
    qn = rng.standard_normal((B, L, H, dk))
    kn = rng.standard_normal((B, L, H, dk))
    q = jnp.asarray(qn / np.linalg.norm(qn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    k = jnp.asarray(kn / np.linalg.norm(kn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, L, H, dv)), jnp.bfloat16)
    alpha = jnp.asarray(np.exp(-0.1 * rng.random((B, L, H))), jnp.float32)
    beta = jnp.asarray(rng.random((B, L, H)), jnp.float32)
    jax.block_until_ready(gdn_chunk_prefill_pallas(q, k, v, alpha, beta))


def _drv_kda(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.gdn import kda_chunk_prefill

    rng = np.random.default_rng(2)
    B, L, H, dk, dv = 1, 128, 2, 128, 128
    qn = rng.standard_normal((B, L, H, dk))
    kn = rng.standard_normal((B, L, H, dk))
    q = jnp.asarray(qn / np.linalg.norm(qn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    k = jnp.asarray(kn / np.linalg.norm(kn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, L, H, dv)), jnp.bfloat16)
    alpha = jnp.asarray(np.exp(-0.05 * rng.random((B, L, H, dk))),
                        jnp.float32)
    beta = jnp.asarray(rng.random((B, L, H)), jnp.float32)
    jax.block_until_ready(
        kda_chunk_prefill(q, k, v, alpha, beta, backend="pallas"))


def _drv_mamba(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.mamba import mamba_chunk_scan_combined

    rng = np.random.default_rng(1)
    B, L, H, G, dim, ds = 1, 128, 2, 1, 64, 128
    x = jnp.asarray(rng.standard_normal((B, L, H, dim)), jnp.bfloat16)
    dt = jnp.asarray(rng.random((B, L, H)) + 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.standard_normal(H)) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, L, G, ds)) * 0.3, jnp.bfloat16)
    Cm = jnp.asarray(rng.standard_normal((B, L, G, ds)) * 0.3, jnp.bfloat16)
    jax.block_until_ready(
        mamba_chunk_scan_combined(x, dt, A, Bm, Cm, backend="pallas"))


def _drv_mla_decode(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.mla_decode import mla_paged_decode_attention

    B, H, d_ckv, d_kpe, PS, ctx = 2, 128, 512, 64, 16, 128
    npages = B * (ctx // PS)
    ka, kb, kc, kd = _keys(4)
    ckv = jax.random.normal(ka, (npages, PS, d_ckv), jnp.bfloat16)
    kpe = jax.random.normal(kb, (npages, PS, d_kpe), jnp.bfloat16)
    qn = jax.random.normal(kc, (B, H, d_ckv), jnp.bfloat16)
    qp = jax.random.normal(kd, (B, H, d_kpe), jnp.bfloat16)
    pt = jnp.arange(npages, dtype=jnp.int32).reshape(B, ctx // PS)
    lens = jnp.asarray([ctx, ctx // 2], jnp.int32)
    sm = (d_ckv + d_kpe) ** -0.5
    # packed layout is the lane-slice risk entry (0:512 / 512:640 dst
    # slices); the split layout rides along in the same compile session
    jax.block_until_ready(mla_paged_decode_attention(
        qn, qp, ckv, kpe, pt, lens, sm_scale=sm, layout="packed"))


def _drv_sparse_mla_stream(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.sparse_mla import sparse_mla_stream

    # one 16-token tile of 8 heads against two blocks of a 640-wide window
    # (512 value columns: the 0:512 lane slice, the bf16 cast of the
    # probabilities), every second position selected
    Q, H, D, S, d_v = 16, 8, 640, 1024, 512
    ka, kb = _keys(2)
    q = jax.random.normal(ka, (Q, H, D), jnp.bfloat16)
    window = jax.random.normal(kb, (S, D), jnp.bfloat16)
    mask = (jnp.arange(S)[None, :] % 2 == 0) & (
        jnp.arange(S)[None, :] <= 600 + jnp.arange(Q)[:, None])
    jax.block_until_ready(sparse_mla_stream(
        q, window, mask, jnp.int32(615), d_v=d_v, sm_scale=D ** -0.5))


def _drv_block_sparse_paged(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops import block_sparse_paged as bsp

    # two tiles of 8 query tokens x 16 heads a K/V head, each reading a
    # union of 5 blocks (4 pages of 16 rows a block) of a 32-block request
    # through its page list: the K|V buffer split at 128 lanes, the bias
    # sliced at multiples of 512, the bf16 cast of the probabilities
    hkv, group, d, ps, per, blocks, tile, chunk = 2, 16, 128, 16, 4, 32, 8, 8
    ka, kb = _keys(2)
    q = jax.random.normal(ka, (hkv, 2, tile * group, d), jnp.bfloat16)
    pool = jax.random.normal(kb, (blocks * per + 1, hkv, ps, 2 * d),
                             jnp.bfloat16)
    table = jnp.arange(1, blocks * per + 1, dtype=jnp.int32)[None]
    pos = 1900 + jnp.arange(2 * tile).reshape(2, tile)
    mask = jnp.zeros((2 * tile, hkv, blocks), bool).at[
        :, :, jnp.asarray([0, 7, 19, 28, 29])].set(True)
    union, count, hit = bsp.tile_unions(
        mask, jnp.ones((2 * tile,), bool), tile=tile, cap=chunk)
    bias = bsp.selection_bias(union, hit, pos, block_size=ps * per)
    jax.block_until_ready(bsp.block_sparse_paged_attention(
        q, pool, table, jnp.zeros((hkv, 2), jnp.int32), union, count, bias,
        block_pages=per, chunk_blocks=chunk, sm_scale=d ** -0.5))


def _drv_gather_gmm_rowcache(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.ops.moe_gmm import gather_gmm

    rng = np.random.default_rng(9)
    t_rows, k, n, m = 64, 256, 256, 128
    sizes = np.asarray([37, 91], np.int32)  # mid-tile group starts
    x = jnp.asarray(rng.standard_normal((t_rows, k)), jnp.bfloat16)
    row_ids = jnp.asarray(rng.integers(0, t_rows, m), jnp.int32)
    rhs = jnp.asarray(rng.standard_normal((2, k, n)) / np.sqrt(k),
                      jnp.bfloat16)
    jax.block_until_ready(gather_gmm(
        x, row_ids, rhs, jnp.asarray(sizes), tm=64, tn=128, tk=128,
        variant="rowcache"))


def _drv_gmm(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.ops.moe_gmm import gmm

    rng = np.random.default_rng(3)
    M, K, N, E = 256, 512, 256, 2
    lhs = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((E, K, N)) / np.sqrt(K),
                      jnp.bfloat16)
    sizes = jnp.asarray([128, 128], jnp.int32)
    kw = {}
    if isinstance(tactic, (list, tuple)) and len(tactic) == 3:
        kw = dict(tm=min(int(tactic[0]), M), tn=min(int(tactic[1]), N),
                  tk=min(int(tactic[2]), K))
    jax.block_until_ready(gmm(lhs, rhs, sizes, **kw))


def _paged_inputs(B, ctx, HKV, D, PS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ppr = ctx // PS
    npages = B * ppr
    pt = jnp.asarray(
        np.random.default_rng(0).permutation(npages).astype(np.int32)
    ).reshape(B, ppr)
    lens = jnp.asarray(
        np.random.default_rng(1).integers(1, ctx + 1, B).astype(np.int32))
    ka, kb, kc = _keys(3)
    kc_ = jax.random.normal(ka, (npages, HKV, PS, D), jnp.bfloat16)
    vc_ = jax.random.normal(kb, (npages, HKV, PS, D), jnp.bfloat16)
    return pt, lens, kc_, vc_, kc


def _drv_paged_decode(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops import paged_decode_attention

    B, ctx, HQ, HKV, D, PS = 4, 256, 32, 8, 128, 16
    pt, lens, kc, vc, kq = _paged_inputs(B, ctx, HKV, D, PS)
    q = jax.random.normal(kq, (B, HQ, D), jnp.bfloat16)
    kw = {}
    if isinstance(tactic, int):
        kw = dict(pages_per_chunk=max(1, min(tactic, ctx // PS)))
    jax.block_until_ready(paged_decode_attention(
        q, kc, vc, pt, lens, sm_scale=D ** -0.5, kv_layout="HND", **kw))


def _drv_decode_split(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.paged_decode import (build_decode_split_units,
                                                 paged_decode_attention_split,
                                                 split_pages_per_chunk)

    B, ctx, HQ, HKV, D, PS = 4, 256, 32, 8, 128, 16
    pt, lens, kc, vc, kq = _paged_inputs(B, ctx, HKV, D, PS)
    q = jax.random.normal(kq, (B, HQ, D), jnp.bfloat16)
    S = tactic if isinstance(tactic, int) else 2
    S = max(1, min(S, ctx // PS))
    ppc = split_pages_per_chunk(PS, HKV, D, itemsize=2)
    plan_np = build_decode_split_units(
        pt, lens, num_splits=S, page_size=PS, pages_per_chunk=ppc)
    statics = {k: plan_np.pop(k) for k in
               ("num_units", "num_splits", "single_chunk",
                "pages_per_chunk")}
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    jax.block_until_ready(paged_decode_attention_split(
        q, kc, vc, plan, sm_scale=D ** -0.5, **statics))


def _drv_fp4_decode(tactic=None):
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu.ops.paged_decode_fp4 import (
        fp4_paged_decode_attention, quantize_kv_int4_paged)

    B, ctx, HQ, HKV, D, PS = 2, 128, 32, 8, 128, 16
    npages = B * (ctx // PS)
    ka, kb, kc = _keys(3)
    pt = jnp.arange(npages, dtype=jnp.int32).reshape(B, ctx // PS)
    lens = jnp.full((B,), ctx, jnp.int32)
    kcache = jax.random.normal(ka, (npages, HKV, PS, D), jnp.float32)
    vcache = jax.random.normal(kb, (npages, HKV, PS, D), jnp.float32)
    q = jax.random.normal(kc, (B, HQ, D), jnp.bfloat16)
    k4, ksc = quantize_kv_int4_paged(kcache)
    v4, vsc = quantize_kv_int4_paged(vcache)
    jax.block_until_ready(fp4_paged_decode_attention(
        q, k4, ksc, v4, vsc, pt, lens, sm_scale=D ** -0.5))


def _drv_fused_prefill(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.ops.paged_prefill import (build_prefill_work_units,
                                                  fused_paged_prefill)

    PS, HQ, HKV, D = 16, 4, 2, 128
    qo_len, kv_len = 256, 256
    pages = kv_len // PS
    block_q, ppc = 128, 8
    if isinstance(tactic, (list, tuple)) and len(tactic) == 2:
        block_q = min(int(tactic[0]), 256)
        ppc = min(int(tactic[1]), pages)
    plan_np = build_prefill_work_units(
        np.asarray([0, qo_len], np.int64), np.asarray([0, pages], np.int64),
        np.arange(pages, dtype=np.int64), np.asarray([kv_len], np.int64),
        block_q=block_q, pages_per_chunk=ppc, page_size=PS)
    num_units = plan_np.pop("num_units")
    plan_np.pop("block_q"), plan_np.pop("pages_per_chunk")
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    ka, kb, kc = _keys(3)
    q = jax.random.normal(ka, (qo_len, HQ, D), jnp.bfloat16)
    kcache = jax.random.normal(kb, (pages, HKV, PS, D), jnp.bfloat16)
    vcache = jax.random.normal(kc, (pages, HKV, PS, D), jnp.bfloat16)
    jax.block_until_ready(fused_paged_prefill(
        q, kcache, vcache, plan, num_units=num_units, block_q=block_q,
        pages_per_chunk=ppc, sm_scale=D ** -0.5, causal=True))


def _drv_prefill_ingest(tactic=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.ops.paged_prefill import (build_prefill_ingest_units,
                                                  fused_paged_prefill_ingest)

    PS, HQ, HKV, D = 16, 4, 2, 128
    lens = [128, 64]
    BQ, PPC = 128, 8
    qo_indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    pages_per = [int(np.ceil(n / PS)) for n in lens]
    kv_page_indptr = np.concatenate(
        [[0], np.cumsum(pages_per)]).astype(np.int64)
    npages = int(kv_page_indptr[-1])
    kv_page_indices = np.arange(npages, dtype=np.int64)
    plan_np = build_prefill_ingest_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(lens, np.int64), block_q=BQ, pages_per_chunk=PPC,
        page_size=PS, causal=True, fused_ingest=True)
    statics = {k: plan_np.pop(k) for k in
               ("num_units", "block_q", "pages_per_chunk")}
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    total = int(qo_indptr[-1])
    pad = (-total) % BQ
    ka, kb, kc = _keys(3)
    q = jax.random.normal(ka, (total, HQ, D), jnp.bfloat16)
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    k = jax.random.normal(kb, (total, HKV, D), jnp.bfloat16)
    v = jax.random.normal(kc, (total, HKV, D), jnp.bfloat16)
    kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    kcache = jnp.zeros((npages, HKV, PS, D), jnp.bfloat16)
    vcache = jnp.zeros((npages, HKV, PS, D), jnp.bfloat16)
    out, caches = fused_paged_prefill_ingest(
        qp, kp, vp, kcache, vcache, plan, sm_scale=D ** -0.5, causal=True,
        attend=True, **statics)
    jax.block_until_ready((out, caches))


def _drv_engine_step(tactic=None):
    import jax
    import numpy as np
    import jax.numpy as jnp

    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    backend = tactic if tactic in ("kernel", "reference") else "kernel"
    eng = ServingEngine(cfg, params, EngineConfig(
        num_pages=32, page_size=8, max_batch=2, prefill_budget_tokens=16,
        max_seq_tokens=32, sampling=SamplingConfig(temperature=0.8,
                                                   top_k=10),
        attention_backend=backend))
    rng = np.random.default_rng(0)
    for i in range(2):
        prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 9 + i)]
        eng.submit(EngineRequest(f"r{i}", prompt, max_new_tokens=2))
    eng.run()


DRIVERS: Dict[str, Callable] = {
    "rmsnorm": _drv_rmsnorm,
    "fused_add_rmsnorm": _drv_fused_add_rmsnorm,
    "flash_attention": _drv_flash_attention,
    "bsr": _drv_bsr,
    "bsr_token_select": _drv_bsr_token_select,
    "vbsr": _drv_vbsr,
    "gdn": _drv_gdn,
    "kda": _drv_kda,
    "mamba": _drv_mamba,
    "mla_decode": _drv_mla_decode,
    "sparse_mla_stream": _drv_sparse_mla_stream,
    "block_sparse_paged": _drv_block_sparse_paged,
    "gather_gmm_rowcache": _drv_gather_gmm_rowcache,
    "gmm": _drv_gmm,
    "paged_decode": _drv_paged_decode,
    "decode_split": _drv_decode_split,
    "fp4_decode": _drv_fp4_decode,
    "fused_prefill": _drv_fused_prefill,
    "prefill_ingest": _drv_prefill_ingest,
    "engine_step": _drv_engine_step,
}


# --------------------------------------------------------------------------
# Rung execution
# --------------------------------------------------------------------------


def run_rung_inproc(rung_id: str, chip: str = "v5e") -> None:
    """Execute one rung's launch in THIS process (the subprocess child
    entry).  The simulated wedge never imports jax — it exists to hang."""
    if rung_id == SIM_WEDGE_RUNG:
        time.sleep(3600)
        return
    rung = next((r for r in build_ladder(chip) if r["rung_id"] == rung_id),
                None)
    if rung is None:
        raise SystemExit(f"unknown rung id {rung_id!r}")
    drv = DRIVERS.get(rung.get("driver") or "")
    if drv is None:
        raise SystemExit(f"rung {rung_id!r} has no driver")
    drv(tactic=rung.get("tactic"))


def _spawn_rung(rung: dict, *, timeout_s: float, interpret: bool,
                chip: str = "v5e") -> dict:
    """One rung in its own subprocess under a timeout.  Outcome:
    ``pass`` | ``fail`` (driver error, chip presumed healthy) |
    ``wedge`` (timeout — the subprocess had to be killed)."""
    cmd = [sys.executable, "-m", "flashinfer_tpu.obs.bringup",
           "--run-rung", rung["rung_id"], "--chip", chip]
    child_env = dict(os.environ)
    if interpret:
        child_env.setdefault("JAX_PLATFORMS", "cpu")
        child_env["FLASHINFER_TPU_INTERPRET"] = "1"
    t0 = time.time()
    # Popen + bounded reaps (the compile_guard.probe pattern): a wedged
    # Mosaic compile can leave the child unkillable
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True, env=child_env)
    try:
        out, err = p.communicate(timeout=timeout_s)
        if p.returncode == 0:
            outcome, detail = "pass", ""
        else:
            tail = (err or out or "").strip().splitlines()[-8:]
            outcome, detail = "fail", "\n".join(tail)[-800:]
    except subprocess.TimeoutExpired:
        p.kill()
        try:
            p.communicate(timeout=10)
        except Exception:
            pass
        outcome = "wedge"
        detail = f"rung timed out after {timeout_s:.0f}s (chip wedged?)"
    return {"outcome": outcome, "wall_s": round(time.time() - t0, 2),
            "detail": detail}


def quarantine_entry(rung: dict, journal_id: str, detail: str) -> dict:
    entry = {
        "rung_id": rung["rung_id"], "kind": rung.get("kind"),
        "op": rung.get("op"), "kernel": rung.get("func"),
        "reason": detail, "journal_id": journal_id,
        "bench_phases": rung.get("bench_phases") or [],
        "ts": round(time.time(), 1),
    }
    if rung.get("op") is not None and "tactic" in rung:
        entry["tactic"] = rung.get("tactic")
    return entry


def run_ladder(rungs: List[dict], *, journal: Journal, journal_id: str,
               quarantine: Optional[str] = None,
               rung_timeout_s: float = DEFAULT_RUNG_TIMEOUT_S,
               interpret: Optional[bool] = None,
               probe_every: Optional[int] = None,
               probe_timeout_s: float = DEFAULT_PROBE_TIMEOUT_S,
               resume: bool = False, chip: str = "v5e",
               runner: Optional[Callable] = None,
               prober: Optional[Callable] = None,
               verbose: bool = True) -> dict:
    """Walk the smoke ladder.  A wedge (rung timeout, or an unhealthy
    post-rung probe) is attributed to the current rung, quarantined,
    and HALTS the session — remaining rungs are journaled ``pending``
    so ``--resume`` picks up exactly there after recovery."""
    from flashinfer_tpu import compile_guard

    if interpret is None:
        interpret = not _is_tpu()
    if probe_every is None:
        # interpret-mode rungs cannot wedge a chip: probe only around
        # suspicious outcomes off-hardware, after every rung on it
        probe_every = 0 if interpret else 1
    quarantine = quarantine or quarantine_path()
    runner = runner or _spawn_rung
    prober = prober or (lambda: compile_guard.probe(
        timeout_s=probe_timeout_s, interpret=interpret))
    done = {rid for rid, o in journal.rung_outcomes().items()
            if o == "pass"} if resume else set()
    qids = {e.get("rung_id") for e in _load_quarantine(quarantine)}
    summary = {"total": len(rungs), "passed": 0, "skipped": 0,
               "failed": [], "wedged": [], "pending": [], "halted": False}
    ran = 0
    for rung in rungs:
        rid = rung["rung_id"]
        if summary["halted"]:
            journal.append(journal_id=journal_id, kind="rung", id=rid,
                           outcome="pending",
                           detail="session halted by earlier wedge")
            summary["pending"].append(rid)
            continue
        if rid in done or rid in qids:
            why = "already passed (resume)" if rid in done else "quarantined"
            journal.append(journal_id=journal_id, kind="rung", id=rid,
                           outcome="skipped", detail=why)
            summary["skipped"] += 1
            continue
        res = runner(rung, timeout_s=rung_timeout_s, interpret=interpret,
                     chip=chip)
        outcome, detail = res["outcome"], res.get("detail", "")
        ran += 1
        probe_state = None
        if outcome != "pass" or (probe_every and ran % probe_every == 0):
            probe_state = prober()
            if not probe_state.get("healthy"):
                # the rung may have "passed" or "failed" cleanly and
                # still left the chip wedged — the probe is the arbiter
                outcome = "wedge"
                detail = (detail + "\npost-rung probe unhealthy: "
                          + str(probe_state.get("detail", ""))[:300]).strip()
        journal.append(journal_id=journal_id, kind="rung", id=rid,
                       outcome=outcome, wall_s=res.get("wall_s"),
                       probe=probe_state, detail=detail)
        _counter_inc(outcome)
        if verbose:
            print(f"  rung {rid}: {outcome} ({res.get('wall_s', 0):.1f}s)")
        if outcome == "pass":
            summary["passed"] += 1
        elif outcome == "fail":
            summary["failed"].append(rid)
        elif outcome == "wedge":
            quarantine_add(quarantine_entry(rung, journal_id, detail),
                           quarantine)
            summary["wedged"].append(rid)
            summary["halted"] = True
    journal.append(journal_id=journal_id, kind="session", id="ladder",
                   outcome="halted" if summary["halted"] else "complete",
                   detail=json.dumps({k: v for k, v in summary.items()
                                      if k != "pending"}))
    return summary


def _is_tpu() -> bool:
    try:
        from flashinfer_tpu.utils import is_tpu

        return bool(is_tpu())
    except Exception:
        return False


# --------------------------------------------------------------------------
# Provenance graduation
# --------------------------------------------------------------------------


def _default_configs_dir() -> str:
    return os.path.join(_pkg_root(), "tuning_configs")


def _default_banked_path() -> str:
    return os.path.join(os.path.dirname(_pkg_root()), "BENCH_BANKED.md")


def graduate(emit_paths: List[str], *, chip: str = "v5e",
             journal: Optional[Journal] = None,
             journal_id: Optional[str] = None,
             configs_dir: Optional[str] = None,
             banked_path: Optional[str] = None,
             write: bool = True) -> dict:
    """Rewrite tuning_configs sections named by the emit-config outputs
    to ``"provenance": "measured"``, carrying the session journal id
    and the RowAuditor stamps of the banked rows that measured them
    (L006 refuses a measured section without both references)."""
    from flashinfer_tpu.obs import bench_audit

    journal = journal or Journal()
    journal_id = journal_id or journal.last_session_id() or new_journal_id()
    cfg_path = os.path.join(configs_dir or _default_configs_dir(),
                            f"{chip}.json")
    cfg = json.loads(open(cfg_path).read())
    rows = bench_audit.load_banked_history(
        banked_path or _default_banked_path())
    by_phase: Dict[str, List[str]] = {}
    for r in rows:
        ph = r.get("phase")
        if isinstance(ph, str):
            stamp = bench_audit.row_stamp(r)
            if stamp not in by_phase.setdefault(ph, []):
                by_phase[ph].append(stamp)
    result = {"config": cfg_path, "journal_id": journal_id,
              "graduated": [], "skipped": []}
    for path in emit_paths:
        try:
            data = json.loads(open(path).read())
        except Exception as e:
            result["skipped"].append({"emit": path,
                                      "reason": f"unreadable: {e!r}"})
            continue
        for name, sec in data.items():
            if not (isinstance(sec, dict)
                    and isinstance(sec.get("tactics"), dict)
                    and sec["tactics"]):
                continue
            phases = SECTION_BANK_PHASES.get(name, (name,))
            refs = [rid for ph in phases for rid in by_phase.get(ph, [])]
            if not refs:
                result["skipped"].append({
                    "section": name,
                    "reason": "no banked rows for phase(s) %s — bank the "
                              "sweep before graduating" % list(phases)})
                continue
            old = cfg.get(name) if isinstance(cfg.get(name), dict) else {}
            tactics = dict(old.get("tactics") or {})
            tactics.update(sec["tactics"])
            merged = {
                "comment": sec.get("comment") or old.get("comment")
                or f"measured by obs bringup session {journal_id}",
                "provenance": "measured",
                "journal_id": journal_id,
                # cap the reference list: the join is by configuration
                # stamp, a handful anchors the audit without bloating
                # the shipped config
                "banked_row": refs[:8],
                "tactics": tactics,
            }
            seed_left = sorted(k for k in tactics
                               if k not in sec["tactics"])
            if seed_left:
                merged["seed_keys"] = seed_left
            cfg[name] = merged
            journal.append(journal_id=journal_id, kind="graduate", id=name,
                           outcome="pass",
                           detail=f"{len(sec['tactics'])} tactic(s), "
                                  f"{len(refs)} banked row ref(s)")
            result["graduated"].append(name)
    if write and result["graduated"]:
        tmp = cfg_path + ".tmp"
        with open(tmp, "w") as fh:
            fh.write(json.dumps(cfg, indent=1) + "\n")
        os.replace(tmp, cfg_path)
    return result


# --------------------------------------------------------------------------
# Doctor / status
# --------------------------------------------------------------------------


def doctor_summary() -> dict:
    """The ``obs doctor`` bringup section: session state at a glance,
    import-light and never raising."""
    j = Journal()
    entries = j.entries()
    outcomes = j.rung_outcomes()
    counts: Dict[str, int] = {}
    for o in outcomes.values():
        counts[o] = counts.get(o, 0) + 1
    qentries = _load_quarantine()
    seed_sections: Dict[str, List[str]] = {}
    cfg_dir = _default_configs_dir()
    try:
        for fn in sorted(os.listdir(cfg_dir)):
            if not fn.endswith(".json"):
                continue
            try:
                cfg = json.loads(open(os.path.join(cfg_dir, fn)).read())
            except Exception:
                continue
            pending = [name for name, sec in cfg.items()
                       if isinstance(sec, dict) and "tactics" in sec
                       and name != "tactics"
                       and sec.get("provenance") != "measured"]
            seed_sections[fn[:-5]] = pending
    except OSError:
        pass
    return {
        "journal": j.path,
        "journal_entries": len(entries),
        "session": j.last_session_id(),
        "rungs": counts,
        "quarantined": [e.get("rung_id") for e in qentries],
        "seed_sections_remaining": seed_sections,
    }


# --------------------------------------------------------------------------
# Selftest (the CI gate)
# --------------------------------------------------------------------------


def selftest(chip: str = "v5e", rung_timeout_s: float = 240.0,
             skip_ladder: bool = False) -> int:
    """CPU proof of the whole bring-up contract; exit 2 on any
    violation (the obs trace/steploop selftest convention)."""
    import shutil
    import tempfile

    problems: List[str] = []
    tmp = tempfile.mkdtemp(prefix="bringup_selftest_")
    jpath = os.path.join(tmp, "journal.jsonl")
    qpath = os.path.join(tmp, "quarantine.json")
    try:
        # -- A: ladder coverage (registry <-> rung bijection) ----------
        rungs = build_ladder(chip)
        problems += coverage_problems(rungs)
        n_risks = len(load_mosaic_risks())
        print(f"selftest: ladder has {len(rungs)} rungs "
              f"({n_risks} mosaic_risks + planners + knobs); "
              f"coverage problems: {len(problems)}")

        # -- B: simulated wedge attributes to its exact rung -----------
        sim = {"rung_id": SIM_WEDGE_RUNG, "kind": "sim", "driver": None,
               "op": "sim.wedge", "tactic": "on", "bench_phases": ["sim"]}
        journal = Journal(jpath)
        jid = new_journal_id()
        s1 = run_ladder([sim] + rungs, journal=journal, journal_id=jid,
                        quarantine=qpath, rung_timeout_s=3.0,
                        interpret=True, probe_every=0, chip=chip,
                        verbose=False)
        if s1["wedged"] != [SIM_WEDGE_RUNG]:
            problems.append(f"simulated wedge not attributed: {s1}")
        if len(s1["pending"]) != len(rungs):
            problems.append(
                "wedge did not halt the session: %d pending, expected %d"
                % (len(s1["pending"]), len(rungs)))
        qids = [e.get("rung_id") for e in _load_quarantine(qpath)]
        if qids != [SIM_WEDGE_RUNG]:
            problems.append(f"quarantine list wrong: {qids}")
        # the quarantined (op, tactic) pair reaches the blocklist
        os.environ["FLASHINFER_TPU_BRINGUP_QUARANTINE"] = qpath
        try:
            if not tactics_blocklist.blocked("sim.wedge", "on"):
                problems.append(
                    "quarantined tactic not visible to tactics_blocklist")
        finally:
            os.environ.pop("FLASHINFER_TPU_BRINGUP_QUARANTINE", None)
            tactics_blocklist._bringup_cache = None

        # -- C: --resume skips the quarantined rung, completes the rest
        if not skip_ladder:
            t0 = time.time()
            s2 = run_ladder([sim] + rungs, journal=journal, journal_id=jid,
                            quarantine=qpath,
                            rung_timeout_s=rung_timeout_s, interpret=True,
                            probe_every=0, resume=True, chip=chip)
            print("selftest: resume ladder %d passed / %d failed / "
                  "%d skipped in %.0fs" % (s2["passed"], len(s2["failed"]),
                                           s2["skipped"], time.time() - t0))
            if s2["skipped"] != 1:
                problems.append(
                    f"resume should skip exactly the quarantined rung, "
                    f"skipped {s2['skipped']}")
            for rid in s2["failed"]:
                o = journal.rung_outcomes().get(rid)
                problems.append(f"rung {rid} failed in interpret mode "
                                f"(outcome {o})")
            if s2["wedged"]:
                problems.append(f"interpret ladder wedged: {s2['wedged']}")
            # a third run must skip everything (journal-complete)
            s3 = run_ladder([sim] + rungs, journal=journal, journal_id=jid,
                            quarantine=qpath, rung_timeout_s=5.0,
                            interpret=True, probe_every=0, resume=True,
                            chip=chip, verbose=False,
                            runner=lambda *a, **k: problems.append(
                                "resume re-ran a completed rung") or
                            {"outcome": "fail", "wall_s": 0, "detail": ""})
            if s3["skipped"] != len(rungs) + 1 - len(s2["failed"]):
                problems.append(
                    f"journal-complete resume skipped {s3['skipped']} of "
                    f"{len(rungs) + 1}")

        # -- D: graduation flips seed -> measured with valid refs ------
        cfg_dir = os.path.join(tmp, "tuning_configs")
        os.makedirs(cfg_dir)
        shipped = json.loads(open(os.path.join(
            _default_configs_dir(), f"{chip}.json")).read())
        json.dump(shipped, open(os.path.join(cfg_dir, f"{chip}.json"), "w"),
                  indent=1)
        emit = {"decode": {"comment": "selftest sweep", "seed": False,
                           "tactics": {
                               "decode.splits|256_32_32_8_128_16_16_bfloat16": 2}}}
        emit_path = os.path.join(tmp, "emit_decode.json")
        json.dump(emit, open(emit_path, "w"))
        banked = os.path.join(tmp, "BENCH_BANKED.md")
        row = {"phase": "decode_splits", "bs": 32, "ctx": 256,
               "num_splits": 2, "us": 12.0}
        open(banked, "w").write(
            "```json\n" + json.dumps({"rows": [row]}) + "\n```\n")
        g = graduate([emit_path], chip=chip, journal=journal,
                     journal_id=jid, configs_dir=cfg_dir,
                     banked_path=banked)
        if g["graduated"] != ["decode"]:
            problems.append(f"graduation did not flip decode: {g}")
        graduated = json.loads(open(os.path.join(cfg_dir,
                                                 f"{chip}.json")).read())
        sec = graduated.get("decode", {})
        if sec.get("provenance") != "measured" \
                or sec.get("journal_id") != jid \
                or not sec.get("banked_row"):
            problems.append(f"graduated section missing references: "
                            f"{ {k: sec.get(k) for k in ('provenance', 'journal_id', 'banked_row')} }")
        # L006 must accept the rewrite (and would reject it without refs)
        from flashinfer_tpu.analysis import tuning_schema
        from flashinfer_tpu.analysis.core import Project

        proj_dir = os.path.join(tmp, "proj")
        os.makedirs(os.path.join(proj_dir, "tuning_configs"))
        open(os.path.join(proj_dir, "mod.py"), "w").write("x = 1\n")
        shutil.copy(os.path.join(cfg_dir, f"{chip}.json"),
                    os.path.join(proj_dir, "tuning_configs", "gen.json"))
        findings = tuning_schema.run(Project.from_paths([proj_dir]))
        if findings:
            problems.append("L006 rejects the graduated config: %s"
                            % [f.message[:120] for f in findings])
        stripped = dict(sec)
        stripped.pop("journal_id", None)
        json.dump({"decode": stripped},
                  open(os.path.join(proj_dir, "tuning_configs", "gen.json"),
                       "w"), indent=1)
        findings = tuning_schema.run(Project.from_paths([proj_dir]))
        if not any("journal_id" in f.message for f in findings):
            problems.append("L006 accepts a measured section WITHOUT a "
                            "journal_id reference")

        # -- E: perf/6 graduation section ------------------------------
        from flashinfer_tpu.obs.roofline import build_perf_report

        report = build_perf_report([])
        if report.get("schema") != "flashinfer_tpu.obs.perf/6":
            problems.append(f"perf schema is {report.get('schema')!r}, "
                            "expected perf/6")
        grad = report.get("graduation")
        if not (isinstance(grad, dict) and grad.get("sections")):
            problems.append("perf report missing the graduation section")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    print(json.dumps({"bringup_selftest": "ok" if not problems else "FAIL",
                      "problems": problems}, indent=1))
    return 2 if problems else 0


# --------------------------------------------------------------------------
# Full hardware session + CLI
# --------------------------------------------------------------------------


def run_session(args) -> int:
    """The smoke ladder as one journaled, resumable session."""
    journal = Journal(args.journal)
    jid = (journal.last_session_id() if args.resume else None) \
        or new_journal_id()
    print(f"bringup session {jid} (journal: {journal.path})")
    rungs = build_ladder(args.chip)
    summary = run_ladder(
        rungs, journal=journal, journal_id=jid,
        quarantine=args.quarantine, rung_timeout_s=args.timeout,
        probe_every=args.probe_every, resume=args.resume, chip=args.chip)
    print(json.dumps({k: v for k, v in summary.items() if k != "pending"}))
    if summary["halted"]:
        print("session halted: wedge quarantined — recover the chip and "
              "re-run `obs bringup --resume`")
        return 3
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="obs bringup",
        description="hardware graduation session harness (ISSUE 20)")
    ap.add_argument("--selftest", action="store_true",
                    help="prove the whole contract on CPU (CI gate)")
    ap.add_argument("--resume", action="store_true",
                    help="skip journal-completed rungs")
    ap.add_argument("--graduate", action="store_true",
                    help="only run provenance graduation on --emit-config")
    ap.add_argument("--emit-config", action="append", default=[],
                    metavar="PATH", help="sweep emit-config JSON(s)")
    ap.add_argument("--list", action="store_true",
                    help="print the generated ladder and exit")
    ap.add_argument("--chip", default="v5e")
    ap.add_argument("--journal", default=None)
    ap.add_argument("--quarantine", default=None)
    ap.add_argument("--banked", default=None)
    ap.add_argument("--timeout", type=float,
                    default=DEFAULT_RUNG_TIMEOUT_S,
                    help="per-rung subprocess timeout (s)")
    ap.add_argument("--probe-every", type=int, default=None,
                    help="probe cadence in rungs (default: 1 on TPU, "
                         "suspicious-only off it)")
    ap.add_argument("--run-rung", default=None, metavar="RUNG_ID",
                    help=argparse.SUPPRESS)  # internal subprocess entry
    args = ap.parse_args(argv)

    if args.run_rung:
        run_rung_inproc(args.run_rung, chip=args.chip)
        print(f"RUNG_OK {args.run_rung}")
        return 0
    if args.list:
        for r in build_ladder(args.chip):
            print(json.dumps(r))
        return 0
    if args.selftest:
        return selftest(chip=args.chip)
    if args.graduate:
        if not args.emit_config:
            ap.error("--graduate requires at least one --emit-config")
        journal = Journal(args.journal)
        g = graduate(args.emit_config, chip=args.chip, journal=journal,
                     banked_path=args.banked)
        print(json.dumps(g, indent=1))
        return 0 if not g["skipped"] or g["graduated"] else 1
    return run_session(args)


if __name__ == "__main__":
    sys.exit(main())
