"""Continuous-batching serving engine on the compile-once steps.

The layer that serves *many concurrent requests* — what vLLM's
PagedAttention block pool and SGLang's RadixAttention prefix reuse
provide above a kernel library like the reference (SURVEY: "the
'model' layer lives in its consumers").  Three parts:

- :class:`BlockPool` — a paged-KV **block pool** over the existing
  page-table geometry: allocation, free list, and eviction with
  REF-COUNTED block sharing, so N requests holding the same prompt
  prefix point at one physical page run.
- :class:`PrefixCache` — a **prefix trie** keyed on token-block hashes
  (one node per full KV page, hash chained through the parent so equal
  blocks at different depths never collide).  Hits skip prefill for the
  shared span entirely; the engine composes the shared-prefix attention
  level with the per-request suffix level through the cascade merge
  operator (:func:`flashinfer_tpu.cascade.compose_cascade_levels`,
  reference ``cascade.cuh:45-471``).  Hit/miss traffic is metered as
  ``engine.prefix_{hit,miss}_tokens``.
- :class:`ServingEngine` — the **scheduler**: request admission with
  priority/SLO-aware ordering, chunked-prefill token budgeting that
  packs decode + prefill chunks onto ONE flat token axis, and
  preemption-by-eviction with recompute-on-resume.  Admission chunk
  sizing is priced by ``obs.costmodel.predict_step_seconds`` over the
  analytic ``engine_step`` cost family — not by heuristics (the
  ROADMAP item 5 direction).

Compile-once contract (the retrace-budget story ``obs trace
--selftest`` gates): the engine never re-plans per scheduling decision.
The jitted step body takes the per-step schedule — flat tokens,
positions, scatter targets, per-token window bounds, group page runs —
as ARGUMENTS with rung-padded shapes, so schedule *values* change
freely without retracing.  Padded shapes come from a small LADDER of
token-axis sizes (:attr:`EngineConfig.ladder`); each rung traces
exactly once and steady state replays compiled programs, keeping a
whole serving session inside the 9-trace budget.

Bitwise-reproducibility contract (the test anchor): attention uses
per-request KV windows whose row offset of KV position ``j`` is always
``j`` — POSITION-determined, never packing-determined.  Padding lanes
contribute exact zeros (masked ``p = 0``), and ``x + 0.0`` is exact,
so a token's attention state is bit-identical regardless of which
other requests share its step.  That makes engine output with prefix
sharing ON bitwise-equal to the no-sharing oracle (same requests, full
per-request prefill) — pinned across f32 and int8-KV caches in
tests/test_serve_engine.py.

Attention backends (``EngineConfig.attention_backend``, the
``engine.attention_backend`` autotuner knob):

- ``"reference"`` — the dense XLA windowed form above.  This is the
  engine's INTERPRET-MODE ORACLE TIER: O(T x K) through masked lanes,
  but provably bitwise vs the no-sharing oracle on CPU, so it anchors
  every correctness claim the kernel tier is measured against.
- ``"kernel"`` — the graduated path (ROADMAP item 1): the per-step
  schedule lowers through
  :func:`~flashinfer_tpu.serve.engine_kernels.build_engine_work_units`
  onto the PR 3 work-unit prefill mainloop and the PR 6 split-KV
  decode units, composed by the same cascade merge fold.  Plan arrays
  ride as rung-padded ARGUMENTS (shapes are rung statics), so the
  kernel tier keeps the compile-once ladder; it skips the masked-lane
  HBM/FLOP waste the reference tier pays.  Tokens are pinned equal to
  the reference tier (tests/test_engine_kernels.py), and interpret
  mode makes the whole path CPU-testable; ``chip_smoke.py`` compares
  the two on the chip, and every cell of ``perfbench/`` runs this tier.

See docs/serving.md for lifecycle, pool invariants, prefix-cache
semantics, scheduler knobs, and the retrace-budget contract.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flashinfer_tpu.api_logging import flashinfer_api
from flashinfer_tpu.serve import handover as _handover
from flashinfer_tpu.serve.step import SamplingConfig

_NO_SPAN = contextlib.nullcontext()


def _span(tick, name: str):
    """A child span of the step ticket's open phase (a profiler span
    and a ledger entry), or nothing when the step-loop gate is off."""
    return _NO_SPAN if tick is None else tick.phase(name)


_NEG_INF = -1e30  # matches ops/merge.py and ops/xla_ref.py


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------


class BlockPool:
    """Ref-counted paged-KV block pool.

    Physical pages are integer ids into the engine's cache arrays
    ``[num_pages, Hkv, page_size, Hd]``.  Page 0 is the SCRATCH page:
    padding lanes of every step scatter into it and it is never
    allocated, so pad writes can never clobber live KV.

    Invariants (stress-pinned in tests/test_serve_engine.py):

    - a page is in the free list iff its refcount is 0;
    - ``alloc`` never returns a page whose refcount is non-zero;
    - ``decref`` below zero raises (double-free is a bug, not a state).
    """

    SCRATCH_PAGE = 0

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("BlockPool needs >= 2 pages (page 0 is "
                             "the reserved scratch page)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._ref = np.zeros(num_pages, np.int32)
        # LIFO free list: recently-freed pages are re-used first (their
        # cache lines are warm and stale contents are masked anyway)
        self._free = list(range(num_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def ref(self, page: int) -> int:
        return int(self._ref[page])

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` free pages at refcount 1, or None (caller evicts /
        preempts and retries — partial allocations never escape)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            assert self._ref[p] == 0, f"free-list page {p} has refs"
            self._ref[p] = 1
        return pages

    def incref(self, pages: Sequence[int]) -> None:
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"incref on unowned page {p}")
            self._ref[p] += 1

    def decref(self, pages: Sequence[int]) -> int:
        """Drop one reference per page; pages reaching 0 return to the
        free list.  Returns how many pages were freed."""
        freed = 0
        for p in pages:
            if self._ref[p] <= 0:
                raise ValueError(f"decref on free page {p}")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
                freed += 1
        return freed


# ---------------------------------------------------------------------------
# Prefix cache (trie keyed on token-block hashes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _TrieNode:
    key: Tuple[int, int]  # (parent node id, block hash)
    page: int
    node_id: int
    parent: Optional[int]
    children: int = 0
    last_use: int = 0


def _block_hash(parent_hash: int, tokens: Sequence[int]) -> int:
    """Chained token-block hash: equal token blocks under different
    prefixes hash differently (the RadixAttention/vLLM block-hash
    scheme), so a trie edge fully identifies prefix CONTENT."""
    h = parent_hash
    for t in tokens:
        h = (h * 1000003 + int(t) + 1) & 0x7FFFFFFFFFFFFFFF
    return h


class PrefixCache:
    """Prefix trie over full KV pages; holds one pool reference per
    cached page (the "cache ownership" ref), so a cached page survives
    the requests that built it and is evictable exactly when only the
    cache still references it (refcount == 1)."""

    def __init__(self, pool: BlockPool):
        self._pool = pool
        self._nodes: Dict[Tuple[int, int], _TrieNode] = {}
        self._by_id: Dict[int, _TrieNode] = {}
        self._next_id = 1
        self._clock = 0

    @property
    def num_pages(self) -> int:
        return len(self._nodes)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def lookup(self, prompt: Sequence[int], max_pages: int
               ) -> Tuple[List[int], int]:
        """Longest cached page-run for ``prompt`` (full pages only,
        capped at ``max_pages``) -> (pages, hit_tokens).  Bumps LRU
        clocks; takes NO references — the caller increfs the pages it
        actually adopts."""
        ps = self._pool.page_size
        pages: List[int] = []
        parent, phash = 0, 0
        now = self._tick()
        for i in range(max_pages):
            blk = prompt[i * ps:(i + 1) * ps]
            if len(blk) < ps:
                break
            phash = _block_hash(phash, blk)
            node = self._nodes.get((parent, phash))
            if node is None:
                break
            node.last_use = now
            pages.append(node.page)
            parent = node.node_id
        return pages, len(pages) * ps

    def insert(self, prompt: Sequence[int], pages: Sequence[int],
               upto_pages: int) -> int:
        """Register the first ``upto_pages`` full pages of ``prompt``.
        Pages already cached (same content) keep the EXISTING node —
        a concurrent private copy stays private.  Newly-adopted pages
        get one cache-ownership incref.  Returns pages adopted."""
        ps = self._pool.page_size
        parent, phash = 0, 0
        now = self._tick()
        adopted = 0
        for i in range(upto_pages):
            blk = prompt[i * ps:(i + 1) * ps]
            if len(blk) < ps:
                break
            phash = _block_hash(phash, blk)
            node = self._nodes.get((parent, phash))
            if node is None:
                node = _TrieNode(key=(parent, phash), page=int(pages[i]),
                                 node_id=self._next_id, parent=parent,
                                 last_use=now)
                self._next_id += 1
                self._nodes[node.key] = node
                self._by_id[node.node_id] = node
                if parent:
                    self._by_id[parent].children += 1
                self._pool.incref([node.page])
                adopted += 1
            else:
                node.last_use = now
            parent = node.node_id
        return adopted

    def evict(self, pages_needed: int) -> int:
        """LRU-evict leaf nodes whose page only the cache references
        (pool refcount == 1) until ``pages_needed`` pages are freed or
        no candidate remains.  Returns pages actually freed.

        One scan gathers ALL current candidates sorted by LRU and
        drains them in order (admission hot path: O(nodes log nodes)
        per trie LEVEL, not O(nodes) per page); evicting a leaf can
        expose its parent as a new candidate, so the outer loop
        re-scans only when a full candidate batch was not enough."""
        from flashinfer_tpu import obs

        freed = 0
        while freed < pages_needed:
            candidates = sorted(
                (n for n in self._nodes.values()
                 if not n.children and self._pool.ref(n.page) == 1),
                key=lambda n: n.last_use)
            if not candidates:
                break
            for victim in candidates:
                del self._nodes[victim.key]
                del self._by_id[victim.node_id]
                if victim.parent:
                    self._by_id[victim.parent].children -= 1
                freed += self._pool.decref([victim.page])
                obs.counter_inc("engine.evictions")
                if freed >= pages_needed:
                    break
        return freed


# ---------------------------------------------------------------------------
# Requests + engine config
# ---------------------------------------------------------------------------

_WAITING, _RUNNING, _FINISHED = "waiting", "running", "finished"


@dataclasses.dataclass
class EngineRequest:
    """One serving request.  ``priority`` orders admission (lower is
    more urgent); ``slo_ttft_s`` turns into an admission deadline so
    SLO-pressed requests overtake equal-priority peers."""

    rid: str
    prompt: List[int]
    max_new_tokens: int = 8
    priority: int = 0
    slo_ttft_s: Optional[float] = None

    # -- runtime state (engine-owned) --
    state: str = _WAITING
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    pages: List[int] = dataclasses.field(default_factory=list)
    # a model with window layers (serve/window_pool.py): the request's pages
    # in the window layers' pool, from page index ``wfirst`` on (the pages
    # before it are released), and how many of them admission promised it
    wpages: List[int] = dataclasses.field(default_factory=list)
    wfirst: int = 0
    wreserved: int = 0
    kv_len: int = 0          # tokens whose KV is materialized
    split: int = -1          # cascade level boundary (page-aligned);
    #                          frozen at FIRST admission and preserved
    #                          across preemptions, so the two-level
    #                          decomposition — and therefore every
    #                          logit bit — is identical whether or not
    #                          the request was ever preempted
    hit_tokens: int = 0      # prefix-cache tokens adopted at admission
    inserted_pages: int = 0  # full pages registered in the trie so far
    folded_out: int = 0      # out tokens folded into prompt on preempt
    vacated: bool = False    # left the device mid-flight (preempt /
    #                          spill / idle offload) and not yet
    #                          re-admitted — the recompute-vs-restore
    #                          miss attribution keys on this, so even a
    #                          zero-token mid-prefill vacate counts
    arrival: int = -1
    enqueue_t: float = 0.0
    first_sched_t: Optional[float] = None  # start of the step that ran
    #                          its first prompt chunk (kept over preemption)
    deadline: float = float("inf")
    preemptions: int = 0

    def seq(self) -> List[int]:
        """The token sequence as the model sees it: prompt (including
        any generated tokens folded back by a preemption) plus the
        not-yet-folded generated tail."""
        return self.prompt + self.out_tokens[self.folded_out:]

    def total_len(self) -> int:
        return len(self.prompt) + len(self.out_tokens) - self.folded_out


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Frozen engine statics.  ``block_size`` / ``prefill_budget_tokens``
    / ``max_batch`` / ``attention_backend`` are autotuner knobs
    (``engine.*`` in KNOWN_KNOBS, shape-keyed on the model's
    (hidden, hq, hkv, hd)); ``from_knobs`` resolves them through the
    per-chip-gen tuning configs."""

    num_pages: int                  # physical pages incl. scratch page 0
    page_size: int = 16             # engine.block_size
    max_batch: int = 8              # engine.max_batch (batch slots)
    prefill_budget_tokens: int = 64  # engine.prefill_budget_tokens
    max_seq_tokens: int = 256       # per-request context bound
    ladder: Tuple[int, ...] = ()    # rung token sizes; () = derived
    kv_dtype: Optional[object] = None   # default model cfg dtype
    sampling: SamplingConfig = SamplingConfig()
    enable_prefix_cache: bool = True
    slo_step_seconds: Optional[float] = None  # predicted-step-time cap
    donate: bool = True
    seed: int = 0
    # engine.attention_backend: "reference" = the dense XLA oracle
    # tier (bitwise-provable on CPU), "kernel" = the Pallas work-unit
    # lowering (serve/engine_kernels.py; interpret-mode off-TPU)
    attention_backend: str = "reference"
    decode_num_splits: int = 1      # kernel tier's split-KV factor
    # prefill.fused_ingest: accepted ("off" | "on") and IGNORED.  The engine's
    # step always writes a step's K/V rows and attends over the pages
    # (``_composed_attn``).  The per-layer ``lax.cond`` onto the fused-ingest
    # launch went in PR 29: no step of a benchmark cell took the branch, and
    # its planner held the assertion a lone short prefill tripped.  The field
    # and "on" stay only because perfbench/configs/mistral-nemo-12b.json
    # passes it and perfbench/run.py prints it; the benchmark PR that drops
    # both removes the field (PERF.md section 7.1).
    fused_ingest: str = "off"
    # tiered-KV statics (serve/kv_tier.py): the engine's ROLE in a
    # disaggregated pair ("prefill" keeps finished KV pages alive for
    # the kv_migrate handoff; "decode" accepts migrated continuations;
    # "unified" = the single-pool default), the host-RAM offload tier
    # (engine.kv_offload: "host" attaches a HostKVStore of
    # engine.host_gib GiB), and the preemption policy
    # (engine.spill_policy: "recompute" = PR 11's fold + re-prefill,
    # "spill" = always offload, "auto" = cost-model comparison of
    # restore bytes vs recompute FLOPs per victim)
    role: str = "unified"
    kv_offload: str = "off"         # engine.kv_offload: off | host
    spill_policy: str = "recompute"  # engine.spill_policy
    host_gib: float = 4.0           # engine.host_gib (host tier GiB)
    # tests only, and only for a model that brings its own block
    # (serve/block_step.py): the step also hands back the float32 logits of
    # the lanes it sampled (``ServingEngine.last_logits``); a static of the
    # program, off wherever a run is timed
    return_logits: bool = False

    @staticmethod
    def from_knobs(model_cfg, *, num_pages: int, max_seq_tokens: int = 256,
                   **over) -> "EngineConfig":
        """Resolve the tunable statics through ``autotuner.KNOWN_KNOBS``
        (engine.block_size / engine.prefill_budget_tokens /
        engine.max_batch / engine.attention_backend, plus the tiered-KV
        knobs engine.kv_offload / engine.spill_policy /
        engine.host_gib), shape-keyed on the model geometry so each
        chip generation ships its own scheduler shape ladder,
        attention tier, and KV tiering."""
        from flashinfer_tpu.autotuner import AutoTuner

        t = AutoTuner.get()
        key = (model_cfg.hidden_size, model_cfg.num_qo_heads,
               model_cfg.num_kv_heads, model_cfg.head_dim)
        knobs = dict(
            page_size=int(t.lookup("engine.block_size", key, default=16)),
            prefill_budget_tokens=int(t.lookup(
                "engine.prefill_budget_tokens", key, default=64)),
            max_batch=int(t.lookup("engine.max_batch", key, default=8)),
            attention_backend=str(t.lookup(
                "engine.attention_backend", key, default="reference")),
            kv_offload=str(t.lookup(
                "engine.kv_offload", key, default="off")),
            spill_policy=str(t.lookup(
                "engine.spill_policy", key, default="recompute")),
            host_gib=float(t.lookup("engine.host_gib", key, default=4)),
        )
        knobs.update(over)
        return EngineConfig(num_pages=num_pages,
                            max_seq_tokens=max_seq_tokens, **knobs)

    def pages_per_req(self) -> int:
        return -(-self.max_seq_tokens // self.page_size)

    def rungs(self) -> Tuple[int, ...]:
        """The shape ladder, one trace a rung (at most 8): sizes doubling
        from the decode floor (max_batch) to a TOP rung that follows the
        step: max_batch + prefill_budget_tokens rounded up to the prefill
        launches' q tile (``ingest_block_q``: a power of two under 128
        rows, 128 above).  An explicit ``ladder`` is taken as written."""
        if self.ladder:
            return tuple(sorted(set(int(r) for r in self.ladder)))
        from flashinfer_tpu.ops.paged_prefill import ingest_block_q
        from flashinfer_tpu.utils import next_power_of_two, round_up

        top = self.max_batch + self.prefill_budget_tokens
        top = round_up(top, ingest_block_q(top))
        rungs = [next_power_of_two(self.max_batch)]
        while rungs[-1] < top:
            rungs.append(min(rungs[-1] * 2, top))
        return tuple(rungs[:8])


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class ServingEngine:
    """Continuous-batching engine over one jitted donated step body.

    >>> eng = ServingEngine(cfg, params, EngineConfig(num_pages=65))
    >>> eng.submit(EngineRequest("r0", prompt, max_new_tokens=8))
    >>> results = eng.run()          # {rid: [token, ...]}

    One ``jax.jit`` body serves every step; the per-step schedule rides
    as rung-padded ARGUMENTS (values change per step, shapes only per
    rung), and the KV caches are donated back into the engine state.
    ``num_traces`` counts compiles: steady state equals the number of
    distinct rungs exercised, and any trace beyond a rung's first is
    counted in ``serve.step_retraces`` + cause-attributed through the
    flight recorder (the PR 10 machinery)."""

    _STATE_NAMES = ("params", "flat_tokens", "positions", "tok_req",
                    "token_page", "token_slot", "page_table", "grp_pages",
                    "tok_grp", "split", "last_rows", "sample_seeds",
                    "kplans", "caches")
    # what the kernel tier's planner counts a step: summed into
    # ``unit_stats``; a step's own ride ``step()``'s facts (reference: 0)
    _UNIT_FACTS = ("prefill_units", "prefill_units_launched",
                   "prefill_cells_valid", "prefill_cells_launched",
                   "decode_pages_real", "decode_pages_launched",
                   "decode_lanes_whole", "prefill_tokens_whole",
                   "prefill_fallback_units")

    def __init__(self, model_cfg, params, config: EngineConfig):
        if config.attention_backend not in ("reference", "kernel"):
            raise ValueError(
                f"attention_backend must be 'reference' or 'kernel', "
                f"got {config.attention_backend!r}")
        if config.role not in ("prefill", "decode", "unified"):
            raise ValueError(f"role must be prefill|decode|unified, "
                             f"got {config.role!r}")
        if config.fused_ingest not in ("off", "on"):
            raise ValueError(f"fused_ingest must be 'off' or 'on', "
                             f"got {config.fused_ingest!r}")
        if config.kv_offload not in ("off", "host"):
            raise ValueError(f"kv_offload must be off|host, "
                             f"got {config.kv_offload!r}")
        if config.spill_policy not in ("recompute", "spill", "auto"):
            raise ValueError(
                f"spill_policy must be recompute|spill|auto, "
                f"got {config.spill_policy!r}")
        if config.spill_policy != "recompute" \
                and config.kv_offload == "off":
            raise ValueError(
                f"spill_policy {config.spill_policy!r} needs a host "
                "tier — set kv_offload='host' (engine.kv_offload)")
        self.cfg = model_cfg
        self.params = params
        self.config = config
        # the model's seam (serve/block_step.py): a model that brings its
        # own layer says which kind each layer is and what a recurrent
        # layer keeps; one that does not is the Llama-style dense block
        self._block = model_cfg if hasattr(model_cfg, "engine_layer") \
            else None
        self._kinds = (tuple(model_cfg.layer_kinds())
                       if self._block is not None
                       else ("attention",) * model_cfg.num_layers)
        self._recurrent = "recurrent" in self._kinds
        self._latent = "latent" in self._kinds
        self._window = "window" in self._kinds
        # state the K/V paths cannot move or share: a recurrent layer's
        # slots are not in the pages, a latent layer's pages are not K/V
        # pairs a head (PrefixCache and kv_tier assume those), a window
        # layer's pages behind the window are released
        self._own_state = self._recurrent or self._latent or self._window
        if self._own_state and config.kv_offload != "off":
            self._refuse_own_state("kv_offload='host' (host spill)")
        if self._own_state and config.role != "unified":
            self._refuse_own_state(f"role={config.role!r} (migration)")
        self.prefix_lookups_refused = 0
        self.last_logits: Dict[str, np.ndarray] = {}
        self.pool = BlockPool(config.num_pages, config.page_size)
        self.prefix_cache = PrefixCache(self.pool)
        self._waiting: List[EngineRequest] = []
        self._running: List[EngineRequest] = []
        self._finished: Dict[str, EngineRequest] = {}
        self._arrivals = 0
        self._slots: List[Optional[EngineRequest]] = \
            [None] * config.max_batch
        self._traces = 0
        self._rung_traced: Dict[int, int] = {}  # rung tokens -> traces
        self._last_sig: Dict[int, object] = {}
        self._steps = 0
        self.idle_steps = 0  # empty-schedule polls (no dispatch)
        self.flops_avoided = 0.0  # prefill FLOPs skipped via prefix hits
        # tiered KV (serve/kv_tier.py): the host-RAM store below the
        # block pool, the in-flight migration staging of a decode-role
        # pool, and per-engine movement totals (what the serving_disagg
        # bench rows read without the metrics gate)
        self.host_store = None
        if config.kv_offload == "host":
            from flashinfer_tpu.serve.kv_tier import HostKVStore

            self.host_store = HostKVStore(
                int(config.host_gib * (1 << 30)))
        self._migrated: Dict[str, object] = {}  # rid -> HostKVEntry
        self.kv_tier_stats = {
            "spills": 0, "restores": 0, "recomputes": 0,
            "migrations": 0, "spill_bytes": 0.0, "restore_bytes": 0.0,
            "migrate_bytes": 0.0,
        }
        # aggregate work accounting for roofline stamping
        # (costmodel.engine_step over these totals == the run's cost):
        self.tokens_total = 0     # scheduled tokens (padding excluded)
        self.sampled_total = 0    # lm_head + sampling lanes paid
        self.kv_pairs_total = 0.0  # attended (q, kv) pairs (FLOPs term)
        self.kv_rows_total = 0.0   # KV rows streamed, shared-prefix
        #                            group gathers counted ONCE (bytes)
        kv_dtype = (jnp.dtype(config.kv_dtype)
                    if config.kv_dtype is not None
                    else jnp.dtype(model_cfg.dtype))
        self.kv_dtype = kv_dtype
        self._int8_kv = kv_dtype == jnp.int8
        if (self._latent or self._window) and self._int8_kv:
            self._refuse_own_state("kv_dtype=int8 (int8 pages)")
        ps, ppr = config.page_size, config.pages_per_req()
        # window layers' pages live in a pool of their own, sized by the
        # window, the largest chunk of a step and max_batch
        self._wpages = None
        if self._window:
            from flashinfer_tpu.serve.window_pool import WindowPages

            self._wpages = WindowPages(
                window=model_cfg.attention_window,
                chunk=min(config.prefill_budget_tokens,
                          max(config.rungs())),
                page_size=ps, max_batch=config.max_batch, pages_per_req=ppr)
        # one entry a layer, donated through the step together: K/V pages
        # for an attention layer; for a recurrent layer the model's state
        # arrays ``[max_batch, ...]``, one SLOT a batch slot
        # (``EngineRequest.slot``, allocated at admission and freed at
        # release with it), so a model without recurrent layers allocates
        # nothing; for a latent layer the model's paged arrays ``[num_pages,
        # page_size, W]`` (no head axis), addressed by the same page tables
        def _layer_state(kind):
            if kind in ("attention", "window"):
                pages = (config.num_pages if kind == "attention"
                         else self._wpages.pool.num_pages)
                shape = (pages, model_cfg.num_kv_heads, ps,
                         model_cfg.head_dim)
                return (jnp.zeros(shape, kv_dtype),
                        jnp.zeros(shape, kv_dtype))
            if kind == "recurrent":
                return model_cfg.new_recurrent_state(config.max_batch)
            if kind == "latent":
                return model_cfg.new_latent_pages(config.num_pages, ps)
            raise ValueError(f"layer kind {kind!r} is none of attention, "
                             "window, recurrent, latent")

        self.caches = [_layer_state(kind) for kind in self._kinds]
        self._ppr = ppr
        # the GQA kernels' geometry and work units are planned for attention
        # and window layers only: a model without one attends by itself
        self._kernel_backend = (config.attention_backend == "kernel"
                                and ("attention" in self._kinds
                                     or self._window))
        self._geom = self._wgeom = None
        # launched-vs-effective unit accounting (kernel tier): what the
        # padded work-unit grids actually execute vs the attended pairs
        # — costmodel.engine_step prices the bench A/B from these
        # (beside the useful-against-launched pairs: the lanes whose whole
        # context rode decode units, the prompt tokens whose whole context
        # rode level 1, and the prefill plans' fallback units)
        self.unit_stats = {
            **dict.fromkeys(self._UNIT_FACTS, 0),
            "kv_pairs_launched": 0.0, "kv_rows_launched": 0.0,
        }
        # the engine adopts no fused ingest, whatever ``config.fused_ingest``
        # says (see the field): no ``ingest`` plan is built or shipped
        self._ingest = False
        if self._kernel_backend:
            from flashinfer_tpu.serve.engine_kernels import EngineKernelGeom

            def _geom_of(pages_per_req):
                return EngineKernelGeom.build(
                    page_size=ps, pages_per_req=pages_per_req,
                    max_batch=config.max_batch,
                    max_rung=max(config.rungs()),
                    num_kv_heads=model_cfg.num_kv_heads,
                    head_dim=model_cfg.head_dim,
                    kv_itemsize=kv_dtype.itemsize,
                    num_splits=config.decode_num_splits,
                )

            self._geom = _geom_of(ppr)
            if self._window:
                # the window layers' plan: the same launches over a
                # request's narrower window page list
                self._wgeom = _geom_of(self._wpages.cap)
        # each rung is one whole-model compile: keep it across processes
        from flashinfer_tpu import env

        env.enable_compilation_cache()
        if self._block is None:
            self._build_step()
        else:
            from flashinfer_tpu.serve import block_step

            self._step = block_step.build(self)

    def _refuse_own_state(self, what: str) -> None:
        """A recurrent layer's state is not in the K/V pages: a path that
        moves or reuses pages alone would resume from state nobody kept.
        State snapshots at page boundaries are not there yet.  A latent
        layer's pages hold one row a token and no K/V pair a head, which is
        what the prefix cache's, the host tier's and the int8 pages' code
        assumes."""
        if self._recurrent:
            raise NotImplementedError(
                f"{what} is not supported for a model with recurrent "
                "layers: the recurrent state slots are neither snapshotted "
                "nor moved with the K/V pages")
        if self._window:
            raise NotImplementedError(
                f"{what} is not supported for a model with window layers: "
                "their pages behind the window are released, and nothing "
                "shares, spills, migrates or quantizes the second pool yet")
        raise NotImplementedError(
            f"{what} is not supported for a model with latent layers: its "
            "latent and index pages are not K/V pairs a head, and nothing "
            "spills, migrates or quantizes them yet")

    # -- public surface ---------------------------------------------------

    @property
    def num_traces(self) -> int:
        return self._traces

    @property
    def steps(self) -> int:
        return self._steps

    def submit(self, req: EngineRequest) -> None:
        from flashinfer_tpu import obs

        if not req.prompt:
            raise ValueError("empty prompt")
        if req.total_len() + req.max_new_tokens > self.config.max_seq_tokens:
            raise ValueError(
                f"request {req.rid}: prompt+generation "
                f"{len(req.prompt) + req.max_new_tokens} exceeds "
                f"max_seq_tokens {self.config.max_seq_tokens}")
        pages = -(-(len(req.prompt) + req.max_new_tokens)
                  // self.config.page_size)
        usable = self.config.num_pages - 1  # page 0 is scratch
        if pages > usable:
            # reject HERE, not at admission: an unadmittable request
            # would otherwise preempt every lower-priority running
            # request (discarding their KV) before discovering it can
            # never fit, then abort the whole run
            raise ValueError(
                f"request {req.rid}: needs {pages} pages but the pool "
                f"has {usable} usable — grow num_pages or shrink the "
                "request")
        req.arrival = self._arrivals
        self._arrivals += 1
        req.enqueue_t = time.perf_counter()
        if req.slo_ttft_s is not None:
            req.deadline = req.enqueue_t + req.slo_ttft_s
        req.state = _WAITING
        self._waiting.append(req)
        obs.request_begin(req.rid)
        obs.counter_inc("engine.requests")

    def has_work(self) -> bool:
        return bool(self._waiting or self._running)

    def run(self, max_steps: int = 100000) -> Dict[str, List[int]]:
        """Drive steps until every submitted request finished (or the
        step cap trips — a scheduler deadlock guard, not a limiter)."""
        while self.has_work():
            if self._steps >= max_steps:
                raise RuntimeError(
                    f"engine exceeded {max_steps} steps with work left "
                    f"({len(self._waiting)} waiting, "
                    f"{len(self._running)} running)")
            self.step()
        return {rid: list(r.out_tokens)
                for rid, r in self._finished.items()}

    def aggregate_cost(self):
        """The whole run's work as one ``costmodel.engine_step`` Cost
        over the accumulated totals (the formula is linear in each
        term), shared-prefix KV dedup included via kv_rows (the
        predicted side of ``obs perf``'s engine rows).

        On the KERNEL backend the launched terms come from the REAL
        work-unit stats (the units each launch runs, whole tiles and
        chunk-aligned page walks — ``ServingEngine.unit_stats``) while
        the effective terms stay the exact attended-pair accounting, so
        the stamped ``flops`` vs ``flops_effective`` gap is the tier's
        true tile-padding waste, not the dense-window fiction the
        reference tier pays."""
        from flashinfer_tpu.obs import costmodel

        launched = {}
        if self._kernel_backend:
            launched = dict(
                kv_pairs_launched=self.unit_stats["kv_pairs_launched"],
                kv_rows_launched=self.unit_stats["kv_rows_launched"])
        return costmodel.engine_step(
            num_tokens=self.tokens_total, batch=max(self.sampled_total, 1),
            layers=self.cfg.num_layers, hidden=self.cfg.hidden_size,
            inter=self.cfg.intermediate_size, hq=self.cfg.num_qo_heads,
            hkv=self.cfg.num_kv_heads, hd=self.cfg.head_dim,
            vocab=self.cfg.vocab_size, kv_tokens=self.kv_pairs_total,
            kv_rows=self.kv_rows_total,
            kv_bytes=1 if self._int8_kv else 2, **launched)

    # -- admission + scheduling -------------------------------------------

    def _order_key(self, r: EngineRequest):
        return (r.priority, r.deadline, r.arrival)

    def _pages_needed(self, r: EngineRequest) -> int:
        return -(-(r.total_len() + self._remaining_new(r))
                 // self.config.page_size)

    def _remaining_new(self, r: EngineRequest) -> int:
        return r.max_new_tokens - len(r.out_tokens)

    def _release(self, r: EngineRequest) -> None:
        """Drop every pool reference the request holds and vacate its
        batch slot (finish and preemption share this path)."""
        if r.pages:
            self.pool.decref(r.pages)
            r.pages = []
        if self._window:
            self._wpages.release(r)
        if r.slot >= 0:
            self._slots[r.slot] = None
            r.slot = -1

    def _fold_and_requeue(self, r: EngineRequest) -> None:
        """Release a running request's device state and requeue it for
        resume — THE one site enforcing the unconditional-fold
        invariant: generated tokens fold into the resume prompt on
        EVERY vacate-the-device path (preemption, spill, idle
        offload), because a spilled host entry can be LRU-evicted
        before resume and the fallback recompute must see the full
        sequence.  A path that skipped the fold would silently drop
        every mid-sequence generated token on that fallback (the
        tiered-KV regression tests/test_kv_tier.py pins: spill-restore
        == recompute-on-resume == never-preempted, bitwise)."""
        self._release(r)
        r.prompt = r.prompt + r.out_tokens[r.folded_out:]
        r.folded_out = len(r.out_tokens)
        r.kv_len = 0
        r.hit_tokens = 0
        r.inserted_pages = 0
        r.vacated = True
        r.state = _WAITING
        self._waiting.append(r)

    def _preempt(self, victim: EngineRequest) -> None:
        """Preemption-by-eviction: spill-on-preempt when the tier +
        policy allow (serve/kv_tier.py; resume then RESTORES the exact
        KV bits), recompute-on-resume otherwise — either way the
        victim folds + requeues through :meth:`_fold_and_requeue`.
        Deterministic per-token sampling seeds make the continuation
        reproducible under every policy."""
        from flashinfer_tpu import obs

        self._running.remove(victim)
        if self.host_store is not None \
                and self.config.spill_policy != "recompute":
            from flashinfer_tpu.serve import kv_tier

            if self.config.spill_policy == "spill" \
                    or kv_tier.spill_beats_recompute(self, victim):
                # copy to host BEFORE the release frees the pages
                kv_tier.spill_request(self, victim)
        self._fold_and_requeue(victim)
        victim.preemptions += 1
        obs.counter_inc("engine.preemptions")

    def _try_admit_one(self, r: EngineRequest) -> bool:
        from flashinfer_tpu import obs

        cfg = self.config
        if r.slot < 0:
            free_slots = [i for i, s in enumerate(self._slots) if s is None]
            if not free_slots:
                return False
            slot = free_slots[0]
        else:  # pragma: no cover - slots are assigned here only
            slot = r.slot
        P = len(r.prompt)
        # the shareable span: full pages of the prompt, capped so the
        # LAST prompt token always prefills (its logits seed token 0).
        # Frozen on FIRST admission: a resume prompt is longer (the
        # generated tokens folded in), and recomputing the boundary
        # would change the cascade decomposition — correct numerically,
        # but no longer BIT-identical to the never-preempted run
        if r.split < 0:
            r.split = ((P - 1) // cfg.page_size) * cfg.page_size
        split = r.split
        # a staged restore source (host-tier spill, or an in-flight
        # kv_migrate handoff on a decode-role pool) supersedes the
        # prefix cache: the entry already holds the request's OWN KV
        # bits up to its spilled kv_len — at least the shareable span
        from flashinfer_tpu.serve import kv_tier

        staged = kv_tier.staged_entry(self, r.rid)
        hit_pages: List[int] = []
        hit_tokens = 0
        if staged is None and cfg.enable_prefix_cache:
            if self._own_state:
                # a hit would skip tokens whose recurrent state nobody
                # kept, or share latent pages the cache cannot address:
                # refused, and counted
                self.prefix_lookups_refused += 1
                obs.counter_inc("engine.prefix_lookups_refused")
            else:
                hit_pages, hit_tokens = self.prefix_cache.lookup(
                    r.prompt, split // cfg.page_size)
        # adopt the shared run BEFORE any eviction: the hit pages must
        # not be evictable while we make room (refcount >= 2 fences
        # them out of evict()'s cache-only candidate set — otherwise an
        # eviction pass could free a hit page and alloc() could hand it
        # back as a "fresh" page, aliasing the request's own table)
        self.pool.incref(hit_pages)
        need = self._pages_needed(r) - len(hit_pages)
        if need > self.pool.free_pages:
            self.prefix_cache.evict(need - self.pool.free_pages)
        if need > self.pool.free_pages or (
                self._window and not self._wpages.reserve(
                    r, r.total_len() + self._remaining_new(r))):
            self.pool.decref(hit_pages)  # admission failed: un-adopt
            return False
        fresh = self.pool.alloc(need)
        assert fresh is not None
        r.pages = hit_pages + fresh
        r.slot = slot
        self._slots[slot] = r
        if staged is not None:
            # restore path: copy the staged KV bits into the fresh
            # pages and resume from the spilled kv_len — neither a
            # prefix hit nor a miss (no prefill happens for the span)
            kv_tier.restore_request(self, r)
            r.hit_tokens = 0
            r.inserted_pages = 0
        else:
            if r.vacated:
                # resume WITHOUT a restore source: PR 11's
                # recompute-on-resume (spill disabled, the policy
                # chose recompute, or the host store evicted the
                # entry) — counted so a spill-policy bench can assert
                # the tier absorbed every resume.  The flag (set by
                # _fold_and_requeue) covers zero-token mid-prefill
                # vacates too, where preemptions/folded_out can't
                self.kv_tier_stats["recomputes"] += 1
                obs.counter_inc("engine.kv_tier.recomputes")
            r.kv_len = hit_tokens
            r.hit_tokens = hit_tokens
            r.inserted_pages = len(hit_pages)
            obs.counter_inc("engine.prefix_hit_tokens", hit_tokens)
            obs.counter_inc("engine.prefix_miss_tokens", P - hit_tokens)
        r.vacated = False
        r.state = _RUNNING
        self._running.append(r)
        if hit_tokens:
            self.flops_avoided += self._prefill_cost_flops(r, hit_tokens)
        return True

    def _admit(self) -> None:
        """Admit waiting requests in (priority, deadline, arrival)
        order.  A request that cannot fit may PREEMPT strictly
        lower-priority running requests (recompute-on-resume) — at most
        down to the point where preemption stops helping."""
        self._waiting.sort(key=self._order_key)
        admitted: List[EngineRequest] = []
        for r in list(self._waiting):
            if self._try_admit_one(r):
                admitted.append(r)
                continue
            # eviction alone was not enough: preempt strictly-worse
            # running requests while that can still free the shortfall
            while True:
                victims = [v for v in self._running
                           if v.priority > r.priority]
                if not victims:
                    break
                victims.sort(key=self._order_key)
                self._preempt(victims[-1])
                if self._try_admit_one(r):
                    admitted.append(r)
                    break
            if r.state != _RUNNING:
                break  # head-of-line blocking: keep FIFO fairness
        for r in admitted:
            self._waiting.remove(r)

    def _prefill_cost_flops(self, r: EngineRequest, tokens: int) -> float:
        """Prefill FLOPs the prefix hit avoided.

        Reference backend: the analytic ``engine_step`` formula over
        the skipped span (its dense attention IS the formula).  Kernel
        backend: the attention term comes from the REAL planner —
        ``build_prefill_work_units`` is run for the skipped span and
        its launched MXU-cell stats price the work the kernel tier
        would actually have executed (``flops_avoided`` is
        therefore unit-stats-derived, not a dense-window
        estimate)."""
        from flashinfer_tpu.obs import costmodel

        kv_pairs = tokens * (tokens + 1) // 2
        launched = {}
        if self._kernel_backend:
            from flashinfer_tpu.ops.paged_prefill import (
                build_prefill_work_units)

            g = self._geom
            pages = np.asarray(
                r.pages[:-(-tokens // g.page_size)], np.int64)
            plan = build_prefill_work_units(
                np.asarray([0, tokens], np.int64),
                np.asarray([0, len(pages)], np.int64), pages,
                np.asarray([tokens], np.int64),
                g.block_q, g.prefill_ppc, g.page_size,
                causal=True, window_left=-1, pack_tiles=True, prune=True)
            # REAL units only (plan["stats"]["units"]): the skipped
            # span's work is priced at what its units would execute,
            # not at the pow2 padding of a standalone plan — padding
            # waste belongs to the steps that actually launch it
            real_units = plan["stats"]["units"]
            chunk = g.prefill_ppc * g.page_size
            launched = dict(
                kv_pairs_launched=float(real_units * g.block_q * chunk),
                kv_rows_launched=float(real_units * chunk))
        cost = costmodel.engine_step(
            num_tokens=tokens, batch=1, layers=self.cfg.num_layers,
            hidden=self.cfg.hidden_size, inter=self.cfg.intermediate_size,
            hq=self.cfg.num_qo_heads, hkv=self.cfg.num_kv_heads,
            hd=self.cfg.head_dim, vocab=self.cfg.vocab_size,
            kv_tokens=kv_pairs,
            kv_bytes=1 if self._int8_kv else 2, **launched,
        )
        return cost.flops

    def _predict_step_seconds(self, num_tokens: int, kv_tokens: int,
                              batch: int) -> float:
        from flashinfer_tpu.obs import costmodel, hwspec

        spec = hwspec.current_spec()
        cost = costmodel.engine_step(
            num_tokens=num_tokens, batch=max(batch, 1),
            layers=self.cfg.num_layers, hidden=self.cfg.hidden_size,
            inter=self.cfg.intermediate_size, hq=self.cfg.num_qo_heads,
            hkv=self.cfg.num_kv_heads, hd=self.cfg.head_dim,
            vocab=self.cfg.vocab_size, kv_tokens=kv_tokens,
            kv_bytes=1 if self._int8_kv else 2,
        )
        return costmodel.predict_step_seconds(
            cost, hbm_tbps=spec.hbm_tbps,
            peak_tflops=spec.peak_tflops(str(self.kv_dtype)),
            ici_gbps=0.0)

    def _schedule(self) -> List[Tuple[EngineRequest, int]]:
        """Pack this step: every decoding request advances 1 token;
        prefilling requests get chunks under the token budget, with the
        marginal chunk PRICED by ``predict_step_seconds`` against the
        SLO step-latency cap (``slo_step_seconds``) instead of a
        heuristic cutoff."""
        cfg = self.config
        sched: List[Tuple[EngineRequest, int]] = []
        total = 0
        kv_tokens = 0
        decoding = [r for r in self._running
                    if r.kv_len >= len(r.prompt)]
        prefilling = [r for r in self._running
                      if r.kv_len < len(r.prompt)]
        for r in decoding:
            sched.append((r, 1))
            total += 1
            kv_tokens += r.kv_len + 1
        rung_cap = max(self.config.rungs())
        budget = cfg.prefill_budget_tokens
        prefilling.sort(key=self._order_key)
        for r in prefilling:
            room = min(budget, rung_cap - total)
            if room <= 0:
                break
            chunk = min(len(r.prompt) - r.kv_len, room)
            # cost-model-priced admission: shrink the chunk until the
            # predicted step latency clears the SLO cap (never below 0;
            # decode lanes always run)
            if cfg.slo_step_seconds is not None:
                while chunk > 0:
                    attended = chunk * r.kv_len + chunk * (chunk + 1) // 2
                    pred = self._predict_step_seconds(
                        total + chunk, kv_tokens + attended,
                        len(self._running))
                    if pred <= cfg.slo_step_seconds:
                        break
                    chunk //= 2
            if chunk <= 0:
                continue
            sched.append((r, chunk))
            total += chunk
            budget -= chunk
            kv_tokens += chunk * r.kv_len + chunk * (chunk + 1) // 2
        if not sched and prefilling:
            # forced-progress floor: an SLO cap tighter than the
            # smallest possible step must not starve prefill forever —
            # one token of the most urgent request always runs
            sched.append((prefilling[0], 1))
        return sched

    # -- the jitted step body ---------------------------------------------

    def _build_step(self):
        cfg, mcfg = self.config, self.cfg
        ps, ppr = cfg.page_size, self._ppr
        K = ppr * ps          # per-request KV window rows
        int8_kv = self._int8_kv
        sm_scale = (1.0 / float(mcfg.head_dim) ** 0.5
                    * (mcfg.kv_k_scale if int8_kv else 1.0))
        sampling = cfg.sampling
        base_key = jax.random.PRNGKey(cfg.seed)
        engine_self = self

        def _window(c, table):
            # [pages, Hkv, PS, D] -> [rows, K, Hkv, D]: the row offset
            # of KV position j is ALWAYS j (position-determined — the
            # bitwise-reproducibility contract in the module doc)
            w = c[table]  # [rows, ppr, Hkv, PS, D]
            n = w.shape[0]
            return jnp.swapaxes(w, 2, 3).reshape(
                n, K, mcfg.num_kv_heads, mcfg.head_dim)

        def _attend(q, kw, vw, lo, hi):
            # per-token windowed attention: q [T, H, D], kw/vw
            # [T, K, Hkv, D], valid rows j in [lo, hi] per token.
            # Masked lanes contribute exact zeros, so window CONTENT
            # beyond the mask (stale pages, scratch) never perturbs a
            # bit.  Returns (out f32 [T, H, D], lse f32 [T, H]).
            T = q.shape[0]
            G = mcfg.num_qo_heads // mcfg.num_kv_heads
            qg = q.reshape(T, mcfg.num_kv_heads, G,
                           mcfg.head_dim).astype(jnp.float32)
            kf = kw.astype(jnp.float32)
            vf = vw.astype(jnp.float32)
            s = jnp.einsum("tngd,tknd->tngk", qg, kf) * sm_scale
            j = jnp.arange(kw.shape[1])
            valid = (j[None, :] >= lo[:, None]) & (j[None, :] <= hi[:, None])
            vm = valid[:, None, None, :]
            s = jnp.where(vm, s, _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.where(vm, jnp.exp(s - m), 0.0)
            l = jnp.sum(p, axis=-1)
            out = jnp.einsum(
                "tngk,tknd->tngd", p / jnp.where(l > 0, l, 1.0)[..., None],
                vf)
            lse = jnp.where(l > 0, m[..., 0] + jnp.log(l), _NEG_INF)
            H = mcfg.num_qo_heads
            return out.reshape(T, H, mcfg.head_dim), lse.reshape(T, H)

        kernel_backend = self._kernel_backend
        geom = self._geom

        def _body(params, flat_tokens, positions, tok_req, token_page,
                  token_slot, page_table, grp_pages, tok_grp, split,
                  last_rows, sample_seeds, kplans, caches):
            # The parts of the program carry names (jax.named_scope ->
            # the HLO op_name of every operation under them), so a
            # device trace splits by part: embed; per layer attn.qkv,
            # attn.kv_write, attn.core, attn.out, mlp; head; sample.
            from flashinfer_tpu.activation import silu_and_mul
            from flashinfer_tpu.cascade import compose_cascade_levels
            from flashinfer_tpu.models.llama import _mm, _pre_quant
            from flashinfer_tpu.norm import rmsnorm
            from flashinfer_tpu.rope import apply_rope_pos_ids
            from flashinfer_tpu.serve.kv_write import (
                append_rows, page_cap, touched_pages)

            engine_self._traces += 1
            T = flat_tokens.shape[0]
            # the pages this step writes, once for every layer
            with jax.named_scope("attn.kv_write"):
                touched = touched_pages(
                    token_page, cap=page_cap(T, ps, cfg.max_batch))

            def _composed_attn(q, k, v, kc, vc):
                """One layer's attention: rope -> quantize -> the step's
                K/V rows into the pages, in place
                (``serve/kv_write.py``) -> attend over the pages -> v-scale
                epilogue.  Both tiers and every ``fused_ingest`` setting
                run exactly this function."""
                with jax.named_scope("attn.kv_write"):
                    q, k = apply_rope_pos_ids(q, k, positions,
                                              rope_theta=mcfg.rope_theta)
                    if int8_kv:
                        from flashinfer_tpu.quantization import (
                            quantize_symmetric_int8)

                        k_w = quantize_symmetric_int8(k, mcfg.kv_k_scale)
                        v_w = quantize_symmetric_int8(v, mcfg.kv_v_scale)
                    else:
                        k_w = k.astype(kc.dtype)
                        v_w = v.astype(vc.dtype)
                    # pad lanes go to the scratch page (pool page 0)
                    kc, vc = append_rows(kc, vc, k_w, v_w, touched,
                                         token_slot)
                with jax.named_scope("attn.core"):
                    if kernel_backend:
                        # the graduated path: the same two-level
                        # cascade, but level 1 rides the work-unit
                        # prefill mainloop + split-KV decode units and
                        # level 0 the group-masked prefill plan — all
                        # composed by the same merge fold
                        # (serve/engine_kernels.py)
                        from flashinfer_tpu.serve.engine_kernels import (
                            engine_kernel_attention)

                        o = engine_kernel_attention(
                            q, kc, vc, kplans, geom=geom,
                            sm_scale=sm_scale)
                    else:
                        # the dense XLA oracle tier (interpret-mode
                        # reference): position-determined windows
                        # attended through masked lanes — O(T x K) but
                        # bitwise-provable vs the no-sharing oracle on
                        # CPU
                        # level 1: the request's own window, rows
                        # [split, pos]
                        k1 = _window(kc, page_table)[tok_req]
                        v1 = _window(vc, page_table)[tok_req]
                        o1, lse1 = _attend(q, k1, v1, split, positions)
                        # level 0: the SHARED prefix run, gathered once
                        # per group slot, rows [0, min(split, pos + 1))
                        # — causal by position so a leader mid-prefill
                        # never sees ahead
                        k0 = _window(kc, grp_pages)[tok_grp]
                        v0 = _window(vc, grp_pages)[tok_grp]
                        hi0 = jnp.minimum(split - 1, positions)
                        o0, lse0 = _attend(q, k0, v0,
                                           jnp.zeros_like(split), hi0)
                        # cascade composition (reference cascade.cuh
                        # merge): empty levels pass through exactly via
                        # the lse guard
                        o, _ = compose_cascade_levels([(o0, lse0),
                                                       (o1, lse1)])
                    if int8_kv:
                        o = o * mcfg.kv_v_scale
                    return o.astype(mcfg.dtype), kc, vc

            with jax.named_scope("embed"):
                x = params["embed"][flat_tokens].astype(mcfg.dtype)
            new_caches = []
            for li, layer in enumerate(params["layers"]):
                with jax.named_scope("attn.qkv"):
                    h = rmsnorm(x, layer["input_norm"], mcfg.rms_eps)
                    pre = _pre_quant(h, layer)
                    q = _mm(h, layer, "q_proj", pre).reshape(
                        T, mcfg.num_qo_heads, mcfg.head_dim)
                    k = _mm(h, layer, "k_proj", pre).reshape(
                        T, mcfg.num_kv_heads, mcfg.head_dim)
                    v = _mm(h, layer, "v_proj", pre).reshape(
                        T, mcfg.num_kv_heads, mcfg.head_dim)
                kc, vc = caches[li]
                attn, kc, vc = _composed_attn(q, k, v, kc, vc)
                new_caches.append((kc, vc))
                with jax.named_scope("attn.out"):
                    x = x + _mm(attn.reshape(T, -1), layer,
                                "o_proj").astype(mcfg.dtype)
                with jax.named_scope("mlp"):
                    h2 = rmsnorm(x, layer["post_norm"], mcfg.rms_eps)
                    pre2 = _pre_quant(h2, layer, "gate_proj")
                    mlp = jnp.concatenate(
                        [_mm(h2, layer, "gate_proj", pre2),
                         _mm(h2, layer, "up_proj", pre2)], -1)
                    x = x + _mm(silu_and_mul(mlp), layer,
                                "down_proj").astype(mcfg.dtype)
            with jax.named_scope("head"):
                x_last = x[last_rows]
                xf = rmsnorm(x_last, params["final_norm"], mcfg.rms_eps)
                logits = _mm(xf, params, "lm_head").astype(jnp.float32)
            # per-lane deterministic sampling: the key depends only on
            # (request arrival id, token index), never on scheduling —
            # the same request samples the same stream under any
            # packing, preemption, or sharing mode
            with jax.named_scope("sample"):
                t = jnp.maximum(
                    jnp.asarray(sampling.temperature, jnp.float32), 1e-6)
                probs = jax.nn.softmax((logits / t).astype(jnp.float32),
                                       -1)
                if sampling.top_k:
                    from flashinfer_tpu import sampling as S

                    probs = S.top_k_renorm_probs(probs, sampling.top_k)
                if sampling.top_p < 1.0:
                    from flashinfer_tpu import sampling as S

                    probs = S.top_p_renorm_probs(probs, sampling.top_p)
                keys = jax.vmap(
                    lambda s: jax.random.fold_in(base_key, s))(
                        sample_seeds)
                tokens = jax.vmap(
                    lambda p, kk: jax.random.categorical(
                        kk, jnp.log(jnp.maximum(p, 1e-30))))(probs, keys)
            return tokens.astype(jnp.int32), new_caches

        donate = (13,) if cfg.donate else ()
        self._step = jax.jit(_body, donate_argnums=donate)

    # -- step construction + execution ------------------------------------

    def _count_units(self, facts: dict, st: dict) -> None:
        """Add one plan's useful-against-launched counts to the step's
        facts and the run's totals (a model with window layers lowers two
        plans a step)."""
        us = self.unit_stats
        for k in self._UNIT_FACTS:
            facts[k] += st[k]
            us[k] += st[k]
        us["kv_pairs_launched"] += (st["prefill_cells_launched"]
                                    + st["decode_cells_launched"])
        us["kv_rows_launched"] += (st["prefill_rows_launched"]
                                   + st["decode_rows_launched"])

    def _sample_seed(self, r: EngineRequest, token_index: int) -> int:
        return (r.arrival * 131071 + token_index) & 0x7FFFFFFF

    def _rung_for(self, tokens: int) -> int:
        for rung in self.config.rungs():
            if tokens <= rung:
                return rung
        raise RuntimeError(
            f"scheduled {tokens} tokens > largest rung "
            f"{max(self.config.rungs())} — scheduler bug")

    @flashinfer_api(name="engine.step")
    def step(self) -> dict:
        """One engine step: admit, schedule, run the compiled rung,
        scatter results.  Returns this step's facts: its index, rung,
        tokens scheduled (and how many were prompt / decode tokens),
        requests sampled / finished, the kernel tier's work units and
        cells useful against launched, its lanes on decode units alone,
        its prompt tokens on level 1 alone and its prefill fallback units
        (``_UNIT_FACTS``), the pages its K/V rows landed on
        (``kv_pages_written`` of ``kv_pages_pool``),
        the queue wait of the requests first scheduled in it, and what it
        handed to the device: ``upload_kib`` in ``upload_transfers``
        buffers, ``plans_lowered`` (1 on the kernel tier), level 0's
        ``mask_units`` and whether the whole bitmap crossed for them
        (``mask_full_uploads``); for a model with window layers the unit
        counts are of both plans, and ``window_pages_held`` (while the
        step runs) / ``_pool`` / ``_share`` / ``_released`` (after it)."""
        from flashinfer_tpu import obs

        t_step = time.perf_counter()
        tick = obs.steploop_begin("ServingEngine", step=self._steps)
        if tick is not None:
            tick.enter("admit")
        self._admit()
        if tick is not None:
            tick.enter("schedule")
        sched = self._schedule()
        if not sched:
            if self._waiting and not self._running:
                r = min(self._waiting, key=self._order_key)
                raise RuntimeError(
                    f"request {r.rid} can never be admitted: needs "
                    f"{self._pages_needed(r)} pages, pool has "
                    f"{self.pool.num_pages - 1} (evictable cache pages "
                    "included) — grow num_pages or shrink the request")
            # explicit idle tick: nothing runnable, no dispatch — count
            # it so host-gap math and step accounting never read an
            # idle poll as device time (previously a silent return)
            self.idle_steps += 1
            obs.counter_inc("engine.idle_steps")
            if tick is not None:
                tick.commit(idle=True)
            return {"rung": 0, "tokens": 0, "sampled": 0, "finished": 0}
        if tick is not None:
            tick.enter("assemble")
        cfg, mcfg = self.config, self.cfg
        ps, ppr = cfg.page_size, self._ppr
        Bpad = cfg.max_batch
        total = sum(n for _, n in sched)
        rung = self._rung_for(total)
        flat = np.zeros(rung, np.int32)
        pos = np.zeros(rung, np.int32)
        tok_req = np.zeros(rung, np.int32)
        token_page = np.zeros(rung, np.int32)  # scratch page 0 for pads
        token_slot = np.zeros(rung, np.int32)
        split = np.zeros(rung, np.int32)
        tok_grp = np.zeros(rung, np.int32)
        page_table = np.zeros((Bpad, ppr), np.int32)
        grp_pages = np.zeros((Bpad, ppr), np.int32)
        last_rows = np.zeros(Bpad, np.int32)
        sample_seeds = np.zeros(Bpad, np.int32)
        samplers: List[EngineRequest] = []
        facts = dict.fromkeys(
            ("prefill_tokens", "decode_tokens", "first_scheduled",
             "kv_pages_written", *self._UNIT_FACTS, "plans_lowered",
             "mask_units", "mask_full_uploads"), 0)
        facts.update(step=self._steps, rung=rung, tokens=total,
                     queue_wait_s=0.0, kv_pages_pool=cfg.num_pages - 1)

        # group slots: one per distinct shared page-run prefix this
        # step (sharing mode: every full hit of one cached run lands in
        # ONE group, so the run's pages are gathered once — the cascade
        # HBM dedup; oracle mode degenerates to one group per request)
        groups: Dict[Tuple[int, ...], int] = {}
        for r in self._running:
            page_table[r.slot, :len(r.pages)] = r.pages

        def _grp_key(r):
            run = tuple(r.pages[:r.split // ps])
            return run or (-1 - r.slot,)

        for r, _n in sched:
            key = _grp_key(r)
            if key not in groups:
                g = len(groups)
                groups[key] = g
                run = tuple(r.pages[:r.split // ps])
                grp_pages[g, :len(run)] = run
        # same-group requests pack ADJACENT flat rows (stable sort, so
        # within-group order is the scheduler's): the kernel backend's
        # level-0 plan gathers each shared run once per contiguous
        # group span, and tokens are packing-invariant bitwise (the
        # module-doc contract), so the reference backend is unmoved
        sched.sort(key=lambda e: groups[_grp_key(e[0])])
        segs = []
        # recurrent and latent layers: (row0, n, slot, starts from zero)
        rsegs = []
        row = 0
        for r, n in sched:
            g = groups[_grp_key(r)]
            decoding = r.kv_len >= len(r.prompt)
            if self._own_state:
                # a request (re)admitted since its last step has no
                # tokens materialized: its slot's state is zero, whatever
                # the slot's last holder left there
                rsegs.append((row, n, r.slot, r.kv_len == 0))
            facts["decode_tokens" if decoding else "prefill_tokens"] += n
            # the pages its rows land on: what the step's K/V write moves
            # (serve/kv_write.py), beside the pool it no longer copies
            facts["kv_pages_written"] += ((r.kv_len + n - 1) // ps
                                          - r.kv_len // ps + 1)
            if r.first_sched_t is None and not decoding:
                r.first_sched_t = t_step
                facts["first_scheduled"] += 1
                facts["queue_wait_s"] += t_step - r.enqueue_t
            if self._kernel_backend:
                from flashinfer_tpu.serve.engine_kernels import SchedSeg

                segs.append(SchedSeg(
                    row0=row, n=n, pages=tuple(r.pages), split=r.split,
                    kv_after=r.kv_len + n, decoding=decoding,
                    slot=r.slot, group=g))
            seq = r.seq()
            for i in range(n):
                p = r.kv_len + i
                flat[row] = seq[p]
                pos[row] = p
                tok_req[row] = r.slot
                token_page[row] = r.pages[p // ps]
                token_slot[row] = p % ps
                split[row] = r.split
                tok_grp[row] = g
                row += 1
                # work accounting: every token attends [0, p] (pairs); its
                # rows [split, p] stream per request, its run's rows once
                # per GROUP below (a lone lane's decode units stream both)
                self.kv_pairs_total += p + 1
                self.kv_rows_total += max(p + 1 - r.split, 0)
            r.kv_len += n
            if decoding or r.kv_len >= len(r.prompt):
                last_rows[r.slot] = row - 1
                sample_seeds[r.slot] = self._sample_seed(
                    r, len(r.out_tokens))
                samplers.append(r)
            if not decoding:
                obs.prefill_chunk(r.rid, n)
        # level-0 group gathers: one stream of each shared page run per
        # step regardless of how many requests ride it — the cascade
        # HBM dedup the cost model surfaces via kv_rows
        for run_key in groups:
            if run_key and run_key[0] >= 0:  # real runs, not sentinels
                self.kv_rows_total += len(run_key) * ps
        self.tokens_total += total
        self.sampled_total += len(samplers)
        facts["sampled"] = len(samplers)
        if tick is not None:
            tick.enter("lower")

        kplans: dict = {}
        if self._kernel_backend:
            from flashinfer_tpu.serve import engine_kernels as _ek

            with _span(tick, "lower.build"):
                plans = _ek.build_engine_work_units(
                    segs, rung=rung, geom=self._geom,
                    span=tick and tick.under("lower.build"))
            st = plans["stats"]
            self._count_units(facts, st)
            with _span(tick, "lower.upload"):
                kplans = _ek.plans_to_host(
                    plans, self._geom.mask_unit_cap(rung))
            facts.update(
                plans_lowered=1, mask_units=st["mask_units"],
                mask_full_uploads=int("mask_bytes" in kplans["prefill0"]))
        if self._block is not None:
            from flashinfer_tpu.serve import recurrent as _rec

            # for a model that launches no GQA kernel this IS the step's
            # plan: it goes under the planner's span names, so what reads
            # the host's planning and handing over reads it (nothing is
            # converted for the device: an empty span)
            own = not self._kernel_backend
            with _span(tick, "lower.build" if own else "lower.recurrent"):
                mplan, rfacts = _rec.build_plan(
                    rsegs, tokens=total, rung=rung, max_batch=Bpad,
                    chunk=mcfg.scan_chunk if self._recurrent or self._latent
                    else 0)
            if own:
                with _span(tick, "lower.upload"):
                    pass
            if self._recurrent:
                facts.update(rfacts)
                facts["state_slots_used"] = sum(
                    s is not None for s in self._slots)
            if self._window:
                # the window layers' side of the schedule and their plan
                # ride the model's plan: one buffer for everything
                mplan["window"], wst, wfacts = self._wpages.lower(
                    sched, self._running, rung=rung, max_batch=Bpad,
                    geom=self._wgeom, span=lambda n: _span(tick, n))
                facts.update(wfacts)
                if wst is not None:
                    self._count_units(facts, wst)
        if tick is not None:
            tick.enter("dispatch")

        # everything the step reads of this schedule crosses together
        # (serve/handover.py); a whole bitmap crosses a buffer a leaf
        host = dict(sched=(flat, pos, tok_req, token_page, token_slot,
                           page_table, grp_pages, tok_grp, split, last_rows,
                           sample_seeds), kplans=kplans,
                    mplan=mplan if self._block is not None else {})
        with _span(tick, "dispatch.upload"):
            dev, facts["upload_transfers"], facts["upload_kib"] = \
                _handover.to_device(
                    host, packed=not facts["mask_full_uploads"],
                    finish=(_ek.step_inputs_on_device
                            if self._kernel_backend else None),
                    span=tick and tick.under("dispatch.upload"))
            full_args = (self.params, *dev["sched"], dev["kplans"],
                         self.caches)
        names = self._STATE_NAMES
        if self._block is not None:
            full_args += (dev["mplan"],)
            names += ("mplan",)
        sig = obs.state_signature(full_args, names=names)
        seen = self._rung_traced.get(rung, 0)
        before = self._traces
        with _span(tick, "dispatch.call"):
            out = self._step(*full_args)
        tokens_dev, self.caches = out[:2]
        if tick is not None:
            tick.dispatched()
            tick.enter("fetch")
        if self._traces > before:
            self._rung_traced[rung] = seen + 1
            if seen:
                # a rung that already compiled traced AGAIN: the
                # compile-once contract broke — count + attribute
                obs.counter_inc("serve.step_retraces",
                                wrapper="ServingEngine")
                if sig is not None:
                    obs.record_retrace(
                        "ServingEngine",
                        obs.diff_state_sigs(self._last_sig.get(rung),
                                            sig, full_args))
        if sig is not None:
            self._last_sig[rung] = sig
        tokens = np.asarray(tokens_dev)
        if self._block is not None:
            # what the model's layers noted (an expert layer's rows per
            # expert) and the tests' logits: computed by the step the
            # tokens came from, so this fetch waits for nothing
            aux = {k: np.asarray(v) for k, v in out[2].items()}
            if "logits" in aux:
                logits = aux.pop("logits")
                self.last_logits = {r.rid: logits[r.slot]
                                    for r in samplers}
            facts.update(self._block.engine_facts(aux, total))
        if tick is not None:
            # np.asarray above IS the completion probe (tokens cross to
            # host): the ``fetch`` phase is the device window as the
            # host sees it
            tick.done()
            tick.enter("post")

        # register freshly-completed full pages of each shareable span
        # FIRST (post-run: the page KV is materialized now, and a
        # request finishing this very step must still donate its span
        # to the cache before its own references are released)
        if cfg.enable_prefix_cache and not self._own_state:
            for r, _ in sched:
                upto = min(r.kv_len, r.split) // ps
                if upto > r.inserted_pages:
                    self.prefix_cache.insert(r.prompt, r.pages, upto)
                    r.inserted_pages = upto
        if self._window:
            # pages wholly behind the window go back to the window pool
            facts["window_pages_released"] = sum(
                self._wpages.trim(r) for r, _ in sched)
        finished = 0
        for r in samplers:
            tok = int(tokens[r.slot])
            r.out_tokens.append(tok)
            obs.decode_step(r.rid)
            if len(r.out_tokens) >= r.max_new_tokens:
                self._finish(r)
                finished += 1
        self._steps += 1
        obs.counter_inc("engine.steps")
        obs.counter_inc("engine.step_tokens", total)
        obs.gauge_set("engine.pool_pages_in_use", self.pool.used_pages)
        obs.gauge_set("engine.pool_pages_free", self.pool.free_pages)
        facts["finished"] = finished
        if tick is not None:
            # a step whose call traced (and compiled) says so in the
            # ledger, and leaves a line in its compile log
            tick.commit(tokens=total, rung=rung,
                        traced=self._traces > before)
        return facts

    def _finish(self, r: EngineRequest) -> None:
        from flashinfer_tpu import obs

        self._running.remove(r)
        if self.config.role == "prefill":
            # disaggregated prefill pool: the finished KV pages stay
            # alive for the kv_migrate handoff (the coordinator owns
            # releasing them via kv_tier.migrate_request); only the
            # batch slot frees
            if r.slot >= 0:
                self._slots[r.slot] = None
                r.slot = -1
        else:
            self._release(r)
        if self.host_store is not None:
            self.host_store.drop(r.rid)  # a stale spill is dead weight
        r.state = _FINISHED
        self._finished[r.rid] = r
        obs.request_finish(r.rid)
        obs.counter_inc("engine.finished")

    # -- tiered-KV surface (serve/kv_tier.py) ------------------------------

    def harvest_finished(self) -> List[EngineRequest]:
        """Drain the finished set — the disaggregation coordinator's
        hook: on a prefill-role engine the drained requests still hold
        their KV pages (the caller owns releasing them, normally via
        ``kv_tier.migrate_request``)."""
        out = list(self._finished.values())
        self._finished.clear()
        return out

    def adopt_migrated(self, req: EngineRequest, entry) -> None:
        """Accept a migrated continuation (the prefill→decode
        handoff): the request queues for admission and its KV entry
        stages for the restore path — same machinery as a host-tier
        resume.  The request keeps its ORIGINAL ``arrival`` (the
        sampling-seed stream) and frozen cascade ``split``."""
        from flashinfer_tpu import obs

        if self._own_state:
            self._refuse_own_state("adopt_migrated (migration)")
        if self.config.role == "prefill":
            raise ValueError("a prefill-role pool cannot adopt "
                             "migrated requests")
        if req.rid in self._migrated:
            raise ValueError(f"double migration: {req.rid!r} already "
                             "staged on this pool")
        if req.arrival < 0:
            raise ValueError("migrated request must carry its source "
                             "arrival (the sampling-seed identity)")
        total = req.total_len() + req.max_new_tokens - len(req.out_tokens)
        if total > self.config.max_seq_tokens:
            raise ValueError(
                f"migrated request {req.rid}: {total} tokens exceed "
                f"this pool's max_seq_tokens "
                f"{self.config.max_seq_tokens} (the per-request KV "
                "window bound)")
        pages = -(-total // self.config.page_size)
        if pages > self.config.num_pages - 1:
            raise ValueError(
                f"migrated request {req.rid}: needs {pages} pages but "
                f"the decode pool has {self.config.num_pages - 1} "
                "usable")
        self._migrated[req.rid] = entry
        req.enqueue_t = time.perf_counter()
        if req.slo_ttft_s is not None:
            req.deadline = req.enqueue_t + req.slo_ttft_s
        req.state = _WAITING
        self._waiting.append(req)
        obs.request_begin(req.rid)
        obs.counter_inc("engine.requests")

    def offload_idle(self, rid: str) -> None:
        """Voluntarily spill a RUNNING request's KV to the host tier
        (the idle-request path: a conversation between turns frees its
        device pages now and restores bit-exactly when it next
        schedules).  The request re-queues as waiting; admission
        pressure decides when it returns."""
        if self._own_state:
            self._refuse_own_state("offload_idle (host spill)")
        if self.host_store is None:
            raise ValueError("offload_idle needs kv_offload='host'")
        r = next((x for x in self._running if x.rid == rid), None)
        if r is None:
            raise ValueError(f"offload_idle: {rid!r} is not running")
        from flashinfer_tpu.serve import kv_tier

        if r.kv_len <= 0 or not r.pages:
            raise ValueError(
                f"offload_idle: {rid!r} has no materialized KV to "
                "spill yet (admitted but not stepped)")
        if not kv_tier.spill_request(self, r):
            raise RuntimeError(
                f"offload_idle: host store rejected {rid!r} "
                f"({-(-r.kv_len // self.config.page_size)} pages "
                "exceed its capacity — grow engine.host_gib)")
        self._running.remove(r)
        self._fold_and_requeue(r)
