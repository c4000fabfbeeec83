"""Tiered KV subsystem: host-RAM offload + disaggregated prefill→decode.

The KVCache-centric serving pattern the reference's production
consumers converged on (Mooncake-style disaggregation layered on the
PagedAttention block pool), built on three legs:

- :class:`HostKVStore` — the tier BELOW the device
  :class:`~flashinfer_tpu.serve.engine.BlockPool`: preempted and idle
  requests spill their materialized KV page runs to host RAM and
  restore on resume, so the engine's effective cache capacity exceeds
  the chip's ``hwspec.hbm_gib`` budget.  Spilled pages are stored at
  the CACHE'S OWN STORAGE DTYPE — for int8/fp8-KV engines that IS the
  compressed host format (1 byte/element, the existing KV quant
  appends already produced the quantized bits), and dtype preservation
  is what makes restore BIT-exact: restore-path tokens are pinned
  bitwise-equal to both recompute-on-resume and the never-preempted
  run (tests/test_kv_tier.py).
- **spill/restore/migrate ops** — :func:`spill_request`,
  :func:`restore_request`, :func:`migrate_request`: the decorated
  public movements (``engine.kv_spill`` / ``engine.kv_restore`` /
  ``engine.kv_migrate``), each priced by its ``obs.costmodel`` family
  (``kv_page_io`` pure-bandwidth page runs; ``kv_migrate`` adds the
  point-to-point ICI wire leg) and metered as ``engine.kv_tier.*``
  counters + flight-recorder spans.
- :class:`DisaggServing` — prefill/decode DISAGGREGATION: two
  :class:`~flashinfer_tpu.serve.engine.ServingEngine` instances with
  ``EngineConfig.role`` ``"prefill"`` and ``"decode"``.  The prefill
  pool runs each request through admission → chunked prefill → FIRST
  token, keeps the finished KV pages alive, and the coordinator hands
  them to the decode pool via :func:`migrate_request` — the handoff
  rides the same staging/restore machinery as the host tier, so one
  restore path serves both legs.  Decode continues from token 1 with
  the migrated request's original ``arrival`` (the per-lane sampling
  seeds are ``fold_in(base, arrival*K + token_index)``), which is why
  disaggregated tokens are BITWISE-equal to the unified engine's:
  same KV bits, same seeds, same position-determined windows —
  packing/scheduling differences cannot move a bit (the engine's
  module-doc contract).

Spill-vs-recompute policy (``EngineConfig.spill_policy``, the
``engine.spill_policy`` knob): ``"recompute"`` keeps PR 11's
recompute-on-resume; ``"spill"`` always offloads; ``"auto"`` compares
the cost model's two floors per victim — restore bytes over the HBM
roofline (:func:`~flashinfer_tpu.obs.costmodel.kv_page_io`) against
the recompute prefill's ``predict_step_seconds`` — and spills exactly
when moving bytes is cheaper than recomputing FLOPs
(:func:`spill_beats_recompute`).

The fold contract (the PR 11 regression this module fixes forward):
EVERY preemption — spill or recompute — folds the generated tokens
into the resume prompt (``ServingEngine._preempt``).  A spilled entry
can be LRU-evicted from the host store under capacity pressure, and
the fallback is recompute over ``req.prompt``; if the spill path
skipped the fold, that fallback would recompute the ORIGINAL prompt
only and silently drop every generated token mid-sequence.  With the
fold unconditional, a restore resumes from the spilled ``kv_len`` and
a host-evicted entry degrades to exactly the pinned recompute path —
both bitwise-equal to never-preempted (the satellite regression in
tests/test_kv_tier.py pins all three across f32 and int8-KV with real
sampling configs).

See docs/serving.md §"Tiered KV & disaggregation" for the tier
diagram, knobs, and the bitwise contract; docs/observability.md for
the ``engine.kv_tier.*`` catalog rows and the perf/3
``serving_disagg`` join.
"""

from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Dict, List, Optional, Tuple

import numpy as np

from flashinfer_tpu.api_logging import flashinfer_api

if TYPE_CHECKING:  # import cycle: engine.py calls into this module
    from flashinfer_tpu.serve.engine import EngineRequest, ServingEngine


# ---------------------------------------------------------------------------
# Host-RAM tier
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HostKVEntry:
    """One spilled (or in-flight migrated) request's KV pages in host
    RAM: per layer, the K and V page planes ``[pages, Hkv, ps, hd]`` at
    the cache's storage dtype (bit-exact restore), plus the
    ``kv_len`` the resume continues from."""

    rid: str
    kv_len: int
    layers: List[Tuple[np.ndarray, np.ndarray]]
    nbytes: int
    last_use: int = 0

    @property
    def num_pages(self) -> int:
        return int(self.layers[0][0].shape[0]) if self.layers else 0


class HostKVStore:
    """LRU host-RAM store of spilled KV page runs — the tier below the
    device block pool.

    Invariants (stress-pinned in tests/test_kv_tier.py):

    - one entry per request id; a second ``put`` for a live entry
      raises (double-spill is a bug, not a state — the engine released
      the device pages exactly once);
    - ``pop`` of an absent id raises (restoring pages nobody spilled
      would hand the engine fabricated KV);
    - ``bytes_used`` equals the sum of live entry payloads at all
      times; admission over ``capacity_bytes`` LRU-evicts other
      entries first (leaf == entry here: entries are flat) and rejects
      the put only when the entry alone exceeds the capacity.

    An evicted entry's request falls back to PR 11's
    recompute-on-resume — correct (the fold already happened), just
    slower; the eviction is counted (``engine.kv_tier.host_evictions``)
    so a thrashing store is visible, never silent.
    """

    def __init__(self, capacity_bytes: int):
        if capacity_bytes <= 0:
            raise ValueError("HostKVStore needs a positive capacity")
        self.capacity_bytes = int(capacity_bytes)
        self.bytes_used = 0
        self.evictions = 0
        self._entries: Dict[str, HostKVEntry] = {}
        self._clock = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def pages_used(self) -> int:
        return sum(e.num_pages for e in self._entries.values())

    def has(self, rid: str) -> bool:
        return rid in self._entries

    def peek(self, rid: str) -> Optional[HostKVEntry]:
        """The entry without removing it (bumps its LRU clock)."""
        e = self._entries.get(rid)
        if e is not None:
            self._clock += 1
            e.last_use = self._clock
        return e

    def put(self, rid: str, layers: List[Tuple[np.ndarray, np.ndarray]],
            kv_len: int) -> Optional[HostKVEntry]:
        """Admit one spilled page run.  Returns the entry, or None when
        it cannot fit even after evicting everything else (the caller
        falls back to recompute-on-resume).  Raises on double-spill."""
        from flashinfer_tpu import obs

        if rid in self._entries:
            raise ValueError(f"double spill: {rid!r} already has a "
                             "live host entry")
        nbytes = int(sum(k.nbytes + v.nbytes for k, v in layers))
        if nbytes > self.capacity_bytes:
            return None
        while self.bytes_used + nbytes > self.capacity_bytes:
            victim = min(self._entries.values(),
                         key=lambda e: e.last_use)
            self._drop(victim.rid)
            self.evictions += 1
            obs.counter_inc("engine.kv_tier.host_evictions")
        self._clock += 1
        entry = HostKVEntry(rid=rid, kv_len=int(kv_len), layers=layers,
                            nbytes=nbytes, last_use=self._clock)
        self._entries[rid] = entry
        self.bytes_used += nbytes
        return entry

    def pop(self, rid: str) -> HostKVEntry:
        """Remove and return the entry for restore; raises KeyError on
        an absent id (a restore nobody spilled)."""
        if rid not in self._entries:
            raise KeyError(f"no host KV entry for {rid!r} — restore of "
                           "pages that were never spilled")
        return self._drop(rid)

    def drop(self, rid: str) -> None:
        """Discard an entry if present (request finished elsewhere)."""
        if rid in self._entries:
            self._drop(rid)

    def _drop(self, rid: str) -> HostKVEntry:
        entry = self._entries.pop(rid)
        self.bytes_used -= entry.nbytes
        return entry


# ---------------------------------------------------------------------------
# page movement helpers (engine <-> host <-> peer pool)
# ---------------------------------------------------------------------------


def _extract_pages(engine: "ServingEngine", req: "EngineRequest"
                   ) -> Tuple[List[Tuple[np.ndarray, np.ndarray]], int]:
    """Copy the request's MATERIALIZED page run out of the engine's
    device caches: pages [0, ceil(kv_len/ps)) hold KV positions
    [0, kv_len) (the position-determined layout).  Returns
    (per-layer (k, v) host arrays, payload bytes)."""
    import jax.numpy as jnp

    ps = engine.config.page_size
    n = -(-req.kv_len // ps)
    idx = jnp.asarray(np.asarray(req.pages[:n], np.int32))
    layers: List[Tuple[np.ndarray, np.ndarray]] = []
    nbytes = 0
    for kc, vc in engine.caches:
        hk = np.asarray(kc[idx])
        hv = np.asarray(vc[idx])
        layers.append((hk, hv))
        nbytes += hk.nbytes + hv.nbytes
    return layers, nbytes


def _inject_pages(engine: "ServingEngine", req: "EngineRequest",
                  entry: HostKVEntry) -> None:
    """Write a host entry's page planes into the request's freshly
    allocated device pages — the bit-exact inverse of
    :func:`_extract_pages` (same dtype, same page-major layout)."""
    import jax.numpy as jnp

    n = entry.num_pages
    if n > len(req.pages):
        raise ValueError(
            f"restore of {req.rid!r}: entry holds {n} pages but the "
            f"request allocated only {len(req.pages)}")
    kv_dtype = engine.caches[0][0].dtype
    if entry.layers and entry.layers[0][0].dtype != kv_dtype:
        raise ValueError(
            f"restore of {req.rid!r}: host entry dtype "
            f"{entry.layers[0][0].dtype} != cache dtype {kv_dtype} — "
            "the bit-exact tier contract forbids a converting restore")
    idx = jnp.asarray(np.asarray(req.pages[:n], np.int32))
    new_caches = []
    for (kc, vc), (hk, hv) in zip(engine.caches, entry.layers):
        kc = kc.at[idx].set(jnp.asarray(hk))
        vc = vc.at[idx].set(jnp.asarray(hv))
        new_caches.append((kc, vc))
    engine.caches = new_caches


@flashinfer_api(name="engine.kv_spill")
def spill_request(engine: "ServingEngine", req: "EngineRequest") -> bool:
    """Spill a request's materialized KV pages to the engine's host
    store (``engine.kv_offload="host"``).  Called by the engine's
    preemption path (and :meth:`ServingEngine.offload_idle`) BEFORE the
    device pages are released.  Returns False when the run has nothing
    materialized or the host store rejected the payload — the caller
    then falls back to recompute-on-resume."""
    from flashinfer_tpu import obs

    if engine._own_state:
        engine._refuse_own_state("spill_request (host spill)")
    if engine.host_store is None:
        raise ValueError("spill_request on an engine without a host "
                         "tier (EngineConfig.kv_offload is 'off')")
    if req.kv_len <= 0 or not req.pages:
        return False
    t0 = time.perf_counter()
    layers, nbytes = _extract_pages(engine, req)
    entry = engine.host_store.put(req.rid, layers, kv_len=req.kv_len)
    if entry is None:
        return False
    t1 = time.perf_counter()
    st = engine.kv_tier_stats
    st["spills"] += 1
    st["spill_bytes"] += nbytes
    obs.counter_inc("engine.kv_tier.spills")
    obs.counter_inc("engine.kv_tier.spill_bytes", nbytes)
    obs.gauge_set("engine.kv_tier.host_bytes",
                  engine.host_store.bytes_used)
    obs.gauge_set("engine.kv_tier.host_pages",
                  engine.host_store.pages_used)
    obs.record_span("engine.kv_spill", "host", t0, t1, rid=req.rid,
                    bytes=nbytes, pages=entry.num_pages,
                    kv_len=req.kv_len)
    return True


@flashinfer_api(name="engine.kv_restore")
def restore_request(engine: "ServingEngine", req: "EngineRequest") -> int:
    """Restore a staged entry (host-tier spill OR in-flight migration)
    into the request's freshly allocated device pages at admission.
    Sets ``req.kv_len`` to the spilled length and returns it.  Raises
    when no entry is staged — the admission path must only call this
    after :func:`staged_entry` said one exists."""
    from flashinfer_tpu import obs

    t0 = time.perf_counter()
    if req.rid in engine._migrated:
        entry = engine._migrated.pop(req.rid)
    elif engine.host_store is not None:
        entry = engine.host_store.pop(req.rid)
    else:
        raise KeyError(f"no staged KV entry for {req.rid!r}")
    _inject_pages(engine, req, entry)
    req.kv_len = entry.kv_len
    t1 = time.perf_counter()
    st = engine.kv_tier_stats
    st["restores"] += 1
    st["restore_bytes"] += entry.nbytes
    obs.counter_inc("engine.kv_tier.restores")
    obs.counter_inc("engine.kv_tier.restore_bytes", entry.nbytes)
    if engine.host_store is not None:
        obs.gauge_set("engine.kv_tier.host_bytes",
                      engine.host_store.bytes_used)
        obs.gauge_set("engine.kv_tier.host_pages",
                      engine.host_store.pages_used)
    obs.record_span("engine.kv_restore", "host", t0, t1, rid=req.rid,
                    bytes=entry.nbytes, pages=entry.num_pages,
                    kv_len=entry.kv_len)
    return entry.kv_len


def staged_entry(engine: "ServingEngine", rid: str
                 ) -> Optional[HostKVEntry]:
    """The restore source for ``rid`` if one is staged: an in-flight
    migration first (the disagg handoff), else the host spill store."""
    e = engine._migrated.get(rid)
    if e is not None:
        return e
    if engine.host_store is not None:
        return engine.host_store.peek(rid)
    return None


def spill_beats_recompute(engine: "ServingEngine",
                          req: "EngineRequest") -> bool:
    """The ``spill_policy="auto"`` decision: restore the spilled bytes
    (spill read + restore write over the HBM roofline) vs recompute
    the prefill (``costmodel.engine_step`` over the folded span through
    ``predict_step_seconds``).  Same physics ``obs perf`` attributes
    with, used forward — the PR 6 ``choose_decode_splits`` pattern."""
    from flashinfer_tpu.obs import costmodel, hwspec

    cfg, mcfg = engine.config, engine.cfg
    spec = hwspec.current_spec()
    pages = -(-req.kv_len // cfg.page_size)
    if pages <= 0:
        return False
    kv_bytes = engine.kv_dtype.itemsize
    io = costmodel.kv_page_bytes(
        pages, page_size=cfg.page_size,
        num_kv_heads=mcfg.num_kv_heads, head_dim=mcfg.head_dim,
        layers=mcfg.num_layers, kv_bytes=kv_bytes)
    restore_s = 2.0 * io / (spec.hbm_tbps * 1e12)
    tokens = req.kv_len
    recompute = costmodel.engine_step(
        num_tokens=tokens, batch=1, layers=mcfg.num_layers,
        hidden=mcfg.hidden_size, inter=mcfg.intermediate_size,
        hq=mcfg.num_qo_heads, hkv=mcfg.num_kv_heads, hd=mcfg.head_dim,
        vocab=mcfg.vocab_size, kv_tokens=tokens * (tokens + 1) / 2,
        kv_bytes=kv_bytes)
    recompute_s = costmodel.predict_step_seconds(
        recompute, hbm_tbps=spec.hbm_tbps,
        peak_tflops=spec.peak_tflops(str(engine.kv_dtype)),
        ici_gbps=0.0)
    return restore_s < recompute_s


# ---------------------------------------------------------------------------
# disaggregated prefill -> decode handoff
# ---------------------------------------------------------------------------


@flashinfer_api(name="engine.kv_migrate")
def migrate_request(src: "ServingEngine", dst: "ServingEngine",
                    req: "EngineRequest", *,
                    max_new_tokens: Optional[int] = None) -> dict:
    """Hand one finished-prefill request from the prefill pool to the
    decode pool: extract its KV page run from ``src`` (the prefill-role
    engine kept the pages alive past finish), release the source
    pages, and stage the run + a continuation request on ``dst`` —
    the decode engine's admission restores it through the same
    :func:`restore_request` path the host tier uses.

    The continuation carries the ORIGINAL ``arrival`` and the frozen
    cascade ``split``, so the decode pool samples the same seed stream
    from the same KV bits the unified engine would — disaggregated
    tokens are bitwise-equal to unified serving (pinned).

    Returns the handoff facts: ``bytes`` moved, ``pages``, and the
    ``kv_migrate`` cost (ICI wire + both HBM legs) priced by the
    model — what the ``serving_disagg`` bench phase aggregates and
    stamps."""
    from flashinfer_tpu import obs
    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.serve.engine import EngineRequest

    for eng in (src, dst):
        if eng._own_state:
            eng._refuse_own_state("migrate_request (migration)")
    if src.config.role != "prefill":
        raise ValueError("migrate_request source must be a "
                         "prefill-role engine")
    if not req.pages or req.kv_len <= 0:
        raise ValueError(f"migrate_request: {req.rid!r} has no "
                         "materialized KV to hand off")
    t0 = time.perf_counter()
    layers, nbytes = _extract_pages(src, req)
    n_pages = -(-req.kv_len // src.config.page_size)
    cost = costmodel.kv_migrate(
        pages=n_pages, page_size=src.config.page_size,
        num_kv_heads=src.cfg.num_kv_heads, head_dim=src.cfg.head_dim,
        layers=src.cfg.num_layers,
        kv_bytes=src.kv_dtype.itemsize)
    cont = EngineRequest(
        rid=req.rid, prompt=list(req.prompt),
        max_new_tokens=(req.max_new_tokens if max_new_tokens is None
                        else int(max_new_tokens)),
        priority=req.priority, slo_ttft_s=req.slo_ttft_s)
    cont.out_tokens = list(req.out_tokens)
    cont.arrival = req.arrival
    cont.split = req.split
    entry = HostKVEntry(rid=req.rid, kv_len=req.kv_len, layers=layers,
                        nbytes=nbytes)
    # adopt BEFORE releasing the source pages: a decode pool that
    # rejects the continuation (capacity / max_seq bounds) must leave
    # the request fully intact on the prefill side — the destructive
    # decref happens only once the handoff is committed
    dst.adopt_migrated(cont, entry)
    src.pool.decref(req.pages)
    req.pages = []
    t1 = time.perf_counter()
    for eng in (src, dst):
        eng.kv_tier_stats["migrations"] += 1
        eng.kv_tier_stats["migrate_bytes"] += nbytes
    obs.counter_inc("engine.kv_tier.migrations")
    obs.counter_inc("engine.kv_tier.migrate_bytes", nbytes)
    obs.record_span("engine.kv_migrate", "host", t0, t1, rid=req.rid,
                    bytes=nbytes, pages=n_pages, kv_len=req.kv_len)
    return {"rid": req.rid, "bytes": nbytes, "pages": n_pages,
            "kv_len": req.kv_len, "seconds": t1 - t0, "cost": cost}


class DisaggServing:
    """Disaggregated serving: a prefill-pool engine and a decode-pool
    engine joined by the :func:`migrate_request` handoff.

    >>> disagg = DisaggServing(cfg, params, EngineConfig(num_pages=65))
    >>> disagg.submit(EngineRequest("r0", prompt, max_new_tokens=8))
    >>> results = disagg.run()   # bitwise == the unified engine's

    Each submitted request runs on the prefill pool with
    ``max_new_tokens=1`` (admission, prefix-cache reuse, chunked
    prefill, the FIRST token), then its KV pages migrate to the decode
    pool, which decodes the remaining tokens.  ``migration_stats``
    aggregates the handoff traffic (count, bytes, wall seconds, and
    the summed ``kv_migrate`` cost) for the ``serving_disagg`` bench
    rows; :meth:`aggregate_cost` is both pools' ``engine_step`` work
    plus the migration cost — one stampable Cost for the whole
    disaggregated run."""

    def __init__(self, model_cfg, params, config, *, decode_config=None):
        from flashinfer_tpu.serve.engine import ServingEngine

        pcfg = dataclasses.replace(config, role="prefill")
        dcfg = dataclasses.replace(decode_config or config, role="decode")
        if dcfg.sampling != pcfg.sampling or dcfg.seed != pcfg.seed:
            raise ValueError(
                "prefill and decode pools must share the sampling "
                "config and seed — the per-lane seed stream is the "
                "bitwise handoff contract")
        self.prefill = ServingEngine(model_cfg, params, pcfg)
        self.decode = ServingEngine(model_cfg, params, dcfg)
        self._max_new: Dict[str, int] = {}
        self._prefill_only: Dict[str, List[int]] = {}
        self.migration_stats = {
            "migrations": 0, "bytes": 0.0, "seconds": 0.0,
            "ici_bytes": 0.0,
        }
        self._migration_cost = None

    def submit(self, req: "EngineRequest") -> None:
        """Enqueue on the prefill pool (capped at the first token; the
        original ``max_new_tokens`` rides the migration)."""
        from flashinfer_tpu.serve.engine import EngineRequest

        self._max_new[req.rid] = req.max_new_tokens
        self.prefill.submit(EngineRequest(
            rid=req.rid, prompt=list(req.prompt), max_new_tokens=1,
            priority=req.priority, slo_ttft_s=req.slo_ttft_s))

    def has_work(self) -> bool:
        return (self.prefill.has_work() or self.decode.has_work()
                or bool(self.prefill._finished))

    def step(self) -> None:
        """One coordinator tick: advance the prefill pool, migrate
        every freshly finished prefill, advance the decode pool."""
        if self.prefill.has_work():
            self.prefill.step()
        for req in self.prefill.harvest_finished():
            if self._max_new[req.rid] <= 1:
                # single-token request: the prefill pool already
                # produced everything; release its kept pages
                if req.pages:
                    self.prefill.pool.decref(req.pages)
                    req.pages = []
                self._prefill_only[req.rid] = list(req.out_tokens)
                continue
            facts = migrate_request(self.prefill, self.decode, req,
                                    max_new_tokens=self._max_new[req.rid])
            ms = self.migration_stats
            ms["migrations"] += 1
            ms["bytes"] += facts["bytes"]
            ms["seconds"] += facts["seconds"]
            ms["ici_bytes"] += facts["cost"].ici_bytes
            self._migration_cost = (
                facts["cost"] if self._migration_cost is None
                else self._migration_cost + facts["cost"])
        if self.decode.has_work():
            self.decode.step()

    def run(self, max_steps: int = 100000) -> Dict[str, List[int]]:
        steps = 0
        while self.has_work():
            if steps >= max_steps:
                raise RuntimeError(
                    f"disagg coordinator exceeded {max_steps} ticks "
                    "with work left")
            self.step()
            steps += 1
        results = dict(self._prefill_only)
        results.update({rid: list(r.out_tokens)
                        for rid, r in self.decode._finished.items()})
        return results

    def aggregate_cost(self):
        """Both pools' run-aggregate ``engine_step`` cost plus the
        summed ``kv_migrate`` handoff cost — the one Cost the
        ``serving_disagg`` bench row stamps (its ``ici_bytes`` make
        the migration traffic visible to ``obs perf``)."""
        total = (self.prefill.aggregate_cost()
                 + self.decode.aggregate_cost())
        if self._migration_cost is not None:
            total = total + self._migration_cost
        return dataclasses.replace(total, op="serving_disagg")
