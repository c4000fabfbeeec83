"""Continuous-batching serving engine (serve/engine.py) — ISSUE 11.

The contracts under pin:

- **block pool**: refcounted alloc/free/evict invariants, and the
  alloc-free-realloc stress proof that a freed-and-reallocated page can
  never alias a LIVE block;
- **prefix trie**: full-page chained-hash lookup/insert/evict
  semantics, hit metering;
- **bitwise parity**: engine tokens with prefix sharing ON are
  bit-identical to the no-sharing oracle (full per-request prefill),
  across f32 AND int8-KV caches — the cascade composition + the
  position-determined KV-window layout make this exact, not
  approximate (docs/serving.md "bitwise contract");
- **compile-once**: a whole serving session traces once per rung of
  the shape ladder and never again (the 9-trace budget);
- **scheduler**: priority-ordered admission, preemption-by-eviction
  with bitwise recompute-on-resume, SLO-priced chunking that can only
  shrink chunks (never deadlock), knob-resolved config.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashinfer_tpu.models.llama import (LlamaConfig, init_llama_params,
                                         llama_decode_step)
from flashinfer_tpu.serve import (BlockPool, EngineConfig, EngineRequest,
                                  PrefixCache, SamplingConfig,
                                  ServingEngine)

CFG = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_llama_params(jax.random.PRNGKey(0), CFG)


def _mk_engine(params, share=True, **over):
    kw = dict(num_pages=64, page_size=8, max_batch=4,
              prefill_budget_tokens=16, max_seq_tokens=64,
              sampling=SamplingConfig(top_k=1),
              enable_prefix_cache=share)
    kw.update(over)
    return ServingEngine(CFG, params, EngineConfig(**kw))


def _prompts(rng, n, shared_len=17, suffix_hi=6, n_shared=2):
    shared = [[int(t) for t in rng.integers(1, CFG.vocab_size, shared_len)]
              for _ in range(n_shared)]
    out = []
    for i in range(n):
        sfx = [int(t) for t in rng.integers(
            1, CFG.vocab_size, int(rng.integers(1, suffix_hi)))]
        out.append(shared[i % n_shared] + sfx)
    return out


# ---------------------------------------------------------------------------
# Block pool
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_block_pool_invariants():
    pool = BlockPool(num_pages=8, page_size=16)
    assert pool.free_pages == 7  # page 0 is the reserved scratch page
    a = pool.alloc(3)
    assert a is not None and 0 not in a and len(set(a)) == 3
    assert pool.used_pages == 3
    pool.incref(a[:1])
    assert pool.ref(a[0]) == 2
    assert pool.decref(a) == 2  # a[0] survives at ref 1
    assert pool.ref(a[0]) == 1
    assert pool.decref(a[:1]) == 1
    assert pool.free_pages == 7
    with pytest.raises(ValueError):
        pool.decref(a[:1])  # double free raises, never corrupts
    with pytest.raises(ValueError):
        pool.incref([a[0]])  # incref on a free page raises
    assert pool.alloc(8) is None  # over-ask: nothing leaks out


def test_block_pool_alloc_free_realloc_stress():
    """The satellite-required aliasing proof: across a random
    alloc/incref/decref churn, a page handed out by alloc() is NEVER
    one a live holder still references."""
    rng = np.random.default_rng(0)
    pool = BlockPool(num_pages=33, page_size=8)
    live = {}  # page -> refs we hold
    for _ in range(2000):
        op = rng.integers(0, 3)
        if op == 0:
            n = int(rng.integers(1, 5))
            got = pool.alloc(n)
            if got is None:
                assert pool.free_pages < n
                continue
            for p in got:
                assert p != BlockPool.SCRATCH_PAGE
                assert p not in live, f"alloc aliased live page {p}"
                live[p] = 1
        elif op == 1 and live:
            p = int(rng.choice(list(live)))
            pool.incref([p])
            live[p] += 1
        elif op == 2 and live:
            p = int(rng.choice(list(live)))
            pool.decref([p])
            live[p] -= 1
            if live[p] == 0:
                del live[p]
        # global invariant: live refcounts match, free count complements
        for p, n in live.items():
            assert pool.ref(p) == n
        assert pool.free_pages == (pool.num_pages - 1) - len(live)


# ---------------------------------------------------------------------------
# Prefix trie
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_prefix_trie_lookup_insert_semantics():
    pool = BlockPool(num_pages=32, page_size=4)
    trie = PrefixCache(pool)
    prompt = list(range(100, 111))  # 11 tokens = 2 full pages + tail
    pages = pool.alloc(3)
    assert trie.insert(prompt, pages, upto_pages=2) == 2
    assert pool.ref(pages[0]) == 2  # cache ownership ref taken
    hit, tokens = trie.lookup(prompt, max_pages=2)
    assert hit == pages[:2] and tokens == 8  # full pages only
    # a longer ask still caps at what is cached
    hit, tokens = trie.lookup(prompt + [1, 2, 3, 4], max_pages=3)
    assert hit == pages[:2]
    # same block content under a DIFFERENT parent must not collide
    other = [9] * 4 + prompt[4:8]
    assert trie.lookup(other, max_pages=2) == ([], 0)
    # concurrent private copy: insert of equal content keeps the
    # existing node and adopts nothing
    dup = pool.alloc(2)
    assert trie.insert(prompt, dup + [pages[2]], upto_pages=2) == 0
    assert pool.ref(dup[0]) == 1


def test_prefix_trie_eviction_lru_and_liveness():
    pool = BlockPool(num_pages=32, page_size=4)
    trie = PrefixCache(pool)
    pa = pool.alloc(2)
    pb = pool.alloc(2)
    trie.insert([1, 2, 3, 4, 5, 6, 7, 8], pa, 2)
    trie.insert([9, 10, 11, 12, 13, 14, 15, 16], pb, 2)
    pool.decref(pa)
    pool.decref(pb)  # now cache-only (ref 1 each)
    # bump B's whole chain -> A's LEAF is the LRU eviction candidate
    trie.lookup([9, 10, 11, 12, 13, 14, 15, 16], 2)
    assert trie.evict(1) == 1
    assert trie.lookup([1, 2, 3, 4, 5, 6, 7, 8], 2)[1] == 4  # leaf gone
    assert trie.lookup([9, 10, 11, 12, 13, 14, 15, 16], 2)[1] == 8
    # a page a live request still references is never evicted
    hit, _ = trie.lookup([9, 10, 11, 12, 13, 14, 15, 16], 2)
    pool.incref(hit)  # simulate a running request holding the chain
    assert trie.evict(10) == 1  # only A's remaining cache-only page
    pool.decref(hit)
    assert trie.evict(10) == 2  # B's chain drains leaf-first


# ---------------------------------------------------------------------------
# Engine correctness
# ---------------------------------------------------------------------------


@pytest.mark.quick
@pytest.mark.parametrize("weights", ["f32", "int8"])
def test_engine_matches_stepwise_reference(params, weights):
    """Anchor against an INDEPENDENT oracle: feed the prompt token by
    token through llama_decode_step (the per-op reference path) and
    greedy-decode; the engine (chunked prefill + two-level cascade
    windows) must produce the same greedy tokens, with float32 weights
    and with every projection on the int8 path."""
    if weights == "int8":
        from flashinfer_tpu.models import quantize_llama_weights

        params = quantize_llama_weights(params)
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(1, CFG.vocab_size, 13)]
    max_new = 4

    PS, PPR = 8, 8
    npages = PPR
    caches = [(jnp.zeros((npages + 1, CFG.num_kv_heads, PS, CFG.head_dim),
                         CFG.dtype),
               jnp.zeros((npages + 1, CFG.num_kv_heads, PS, CFG.head_dim),
                         CFG.dtype)) for _ in range(CFG.num_layers)]
    pt = jnp.arange(1, npages + 1, dtype=jnp.int32)[None, :]
    seq = list(prompt)
    logits = None
    for p, tok in enumerate(seq):
        logits, caches = llama_decode_step(
            params, CFG, jnp.asarray([tok], jnp.int32),
            jnp.asarray([p], jnp.int32), caches, pt,
            jnp.asarray([p], jnp.int32), use_pallas=False)
    oracle = []
    for _ in range(max_new):
        tok = int(np.argmax(np.asarray(logits)[0]))
        oracle.append(tok)
        p = len(seq)
        logits, caches = llama_decode_step(
            params, CFG, jnp.asarray([tok], jnp.int32),
            jnp.asarray([p], jnp.int32), caches, pt,
            jnp.asarray([p], jnp.int32), use_pallas=False)
        seq.append(tok)

    eng = _mk_engine(params, page_size=PS)
    eng.submit(EngineRequest("r", list(prompt), max_new_tokens=max_new))
    assert eng.run()["r"] == oracle


def _parity_case(params, kv_dtype):
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, 6)
    res = {}
    for share in (True, False):
        eng = _mk_engine(params, share=share, kv_dtype=kv_dtype,
                         sampling=SamplingConfig(temperature=0.8,
                                                 top_k=20, top_p=0.95))
        for i, p in enumerate(prompts):
            eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=4))
        res[share] = (eng.run(), eng)
    shared_run, eng = res[True]
    oracle_run, _ = res[False]
    assert shared_run == oracle_run  # token-bitwise, every request
    assert sum(r.hit_tokens for r in eng._finished.values()) > 0
    assert eng.flops_avoided > 0


@pytest.mark.quick
def test_shared_prefix_bitwise_parity_f32(params):
    """THE acceptance pin: prefix-shared serving == full per-request
    prefill, token-bitwise (real sampling config, not greedy)."""
    _parity_case(params, None)


def test_shared_prefix_bitwise_parity_int8_kv(params):
    _parity_case(params, jnp.int8)


def test_eviction_stress_preserves_tokens(params):
    """End-to-end aliasing proof: a pool sized to force continuous
    trie eviction + preemption must still produce exactly the big-pool
    tokens (any freed-page aliasing would corrupt KV and diverge)."""
    rng = np.random.default_rng(13)
    prompts = _prompts(rng, 10, shared_len=9, n_shared=3)

    def run(npages):
        eng = _mk_engine(params, num_pages=npages, max_batch=2)
        for i, p in enumerate(prompts):
            eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=3))
        return eng.run(), eng

    small, es = run(9)    # 8 usable pages: one request at a time
    big, _ = run(64)
    assert small == big
    # the small pool actually exercised the reclaim machinery
    assert es.prefix_cache.num_pages <= 8


# ---------------------------------------------------------------------------
# Compile-once / retrace budget
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_retrace_budget_and_steady_state(params):
    rng = np.random.default_rng(17)
    eng = _mk_engine(params)
    for i, p in enumerate(_prompts(rng, 6)):
        eng.submit(EngineRequest(f"a{i}", list(p), max_new_tokens=3))
    eng.run()
    first_wave = eng.num_traces
    assert first_wave == len(eng._rung_traced) <= 9
    assert all(n == 1 for n in eng._rung_traced.values())
    # steady state: a second wave of NEW requests compiles nothing
    for i, p in enumerate(_prompts(rng, 6)):
        eng.submit(EngineRequest(f"b{i}", list(p), max_new_tokens=3))
    eng.run()
    assert eng.num_traces == first_wave


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_priority_admission_order(params):
    """One batch slot: the later-submitted HIGHER-priority request is
    admitted (and finishes) first."""
    rng = np.random.default_rng(19)
    pa, pb = _prompts(rng, 2, n_shared=1)
    eng = _mk_engine(params, max_batch=1)
    eng.submit(EngineRequest("low", list(pa), max_new_tokens=2,
                             priority=5))
    eng.submit(EngineRequest("high", list(pb), max_new_tokens=2,
                             priority=0))
    finish_order = []
    while eng.has_work():
        eng.step()
        for rid in eng._finished:
            if rid not in finish_order:
                finish_order.append(rid)
    assert finish_order == ["high", "low"]


def test_preemption_resume_bitwise(params):
    """Preemption-by-eviction with recompute-on-resume: the preempted
    request's final tokens equal the never-preempted run's, bitwise."""
    rng = np.random.default_rng(23)
    pA = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    pB = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]

    def run(npages):
        eng = _mk_engine(params, num_pages=npages, max_batch=2,
                         max_seq_tokens=48)
        eng.submit(EngineRequest("A", list(pA), max_new_tokens=8,
                                 priority=5))
        # 6 steps: prefill (2) + 4 decoded tokens, so the preempted
        # resume prompt (prompt + generated) CROSSES a page boundary —
        # pins that the cascade split stays frozen at its first-
        # admission value instead of being recomputed from the longer
        # resume prompt (which would change the level decomposition
        # and break bitwise resume)
        for _ in range(6):
            eng.step()  # A is mid-decode when B arrives
        eng.submit(EngineRequest("B", list(pB), max_new_tokens=4,
                                 priority=0))
        return eng.run(), eng

    small, es = run(7)   # 6 usable pages: B (pri 0) must preempt A
    big, eb = run(32)
    assert es._finished["A"].preemptions == 1
    assert eb._finished["A"].preemptions == 0
    assert small == big


def test_slo_pricing_shrinks_chunks_without_deadlock(params):
    """costmodel-priced admission: an SLO step-latency cap tighter
    than a full-budget chunk splits prefill into more, smaller steps;
    an impossibly tight cap still makes forced 1-token progress."""
    rng = np.random.default_rng(29)
    prompt = [int(t) for t in rng.integers(1, CFG.vocab_size, 40)]

    def steps_with(slo):
        eng = _mk_engine(params, prefill_budget_tokens=40,
                         slo_step_seconds=slo)
        eng.submit(EngineRequest("r", list(prompt), max_new_tokens=2))
        res = eng.run()
        return eng.steps, res["r"]

    free_steps, free_toks = steps_with(None)
    tight_steps, tight_toks = steps_with(1e-7)
    impossible_steps, impossible_toks = steps_with(1e-30)
    assert tight_steps > free_steps
    assert impossible_steps >= tight_steps
    # chunking never changes the tokens (packing-invariance contract)
    assert free_toks == tight_toks == impossible_toks


def test_unadmittable_request_rejected_at_submit(params):
    """An oversized request is rejected at submit() — BEFORE it can
    preempt lower-priority running work it could never benefit from."""
    eng = _mk_engine(params, num_pages=4)  # 3 usable pages
    with pytest.raises(ValueError, match="needs .* pages"):
        eng.submit(EngineRequest("big", list(range(1, 40)),
                                 max_new_tokens=4))
    assert not eng.has_work()  # nothing enqueued, nothing disturbed


# ---------------------------------------------------------------------------
# Knobs, catalog, obs wiring
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_engine_knobs_registered_and_resolved():
    from flashinfer_tpu.autotuner import KNOWN_KNOBS

    for name in ("engine.block_size", "engine.prefill_budget_tokens",
                 "engine.max_batch"):
        assert name in KNOWN_KNOBS, name
    cfg = EngineConfig.from_knobs(CFG, num_pages=64, max_seq_tokens=128,
                                  prefill_budget_tokens=32)
    assert cfg.prefill_budget_tokens == 32  # explicit override wins
    assert cfg.page_size >= 1 and cfg.max_batch >= 1
    rungs = cfg.rungs()
    assert 1 <= len(rungs) <= 8  # the 9-trace budget leaves headroom
    assert rungs[0] >= cfg.max_batch


@pytest.mark.quick
@pytest.mark.parametrize("max_batch, budget, ladder, want", [
    (64, 512, (), (64, 128, 256, 512, 640)),     # the older cells' statics
    (8, 1016, (), (8, 16, 32, 64, 128, 256, 512, 1024)),  # a full pow2
    (8, 32, (), (8, 16, 32, 64)),                # perfbench/tiny.json
    (4, 16, (), (4, 8, 16, 32)),                 # this file's engines
    (8, 100, (), (8, 16, 32, 64, 128)),          # 108: under one q tile
    (8, 376, (), (8, 16, 32, 64, 128, 256, 384)),
    (64, 448, (), (64, 128, 256, 512)),          # fills a power of two
    (8, 1016, (8, 512, 1024), (8, 512, 1024)),   # written out: as written
    (64, 512, (1024, 64, 64), (64, 1024)),
])
def test_top_rung_follows_the_step(max_batch, budget, ladder, want):
    """Rungs double from the decode floor; the top one is ``max_batch +
    prefill_budget_tokens`` rounded up to the prefill launches' q tile, so
    a step never pads a whole tile and never more than the power of two
    the ladder used to end on."""
    from flashinfer_tpu.ops.paged_prefill import ingest_block_q
    from flashinfer_tpu.utils import next_power_of_two

    rungs = EngineConfig(num_pages=9, max_batch=max_batch, ladder=ladder,
                         prefill_budget_tokens=budget).rungs()
    assert rungs == want
    assert all(a < b for a, b in zip(rungs, rungs[1:]))
    if not ladder:
        top, need = rungs[-1], max_batch + budget
        assert need <= top <= next_power_of_two(need)
        assert top % ingest_block_q(top) == 0
        assert top - need < ingest_block_q(top)
        assert rungs[0] == next_power_of_two(max_batch)
        assert all(b == 2 * a for a, b in zip(rungs, rungs[1:-1]))


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_a_top_rung_that_is_no_power_of_two_serves_the_same_tokens(
        params, backend):
    """8 slots and a budget of 376: the ladder ends on 384 where it ended
    on 512.  A 376-token chunk beside two decode lanes rides that rung, and
    every served token is the one the same engine serves on the old
    ladder, given through ``ladder=``."""
    rng = np.random.default_rng(61)
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size, n)]
               for n in (11, 19, 402)]   # 376 + 26: the tail rides rung 32

    def serve(**over):
        eng = _mk_engine(
            params, share=False, attention_backend=backend, num_pages=80,
            max_batch=8, prefill_budget_tokens=376, max_seq_tokens=416,
            sampling=SamplingConfig(temperature=0.8, top_k=20), **over)
        for i, p in enumerate(prompts[:2]):
            eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=6))
        head = [eng.step() for _ in range(2)]      # two lanes decoding ...
        eng.submit(EngineRequest("r2", list(prompts[2]), max_new_tokens=3))
        tokens, facts = _drain(eng)                # ... beside the chunks
        return eng, tokens, head + facts

    eng, got, facts = serve()
    assert eng.config.rungs() == (8, 16, 32, 64, 128, 256, 384)
    _, want, old_facts = serve(ladder=(8, 16, 32, 64, 128, 256, 512))
    assert got == want and [len(t) for t in got.values()] == [6, 6, 3]
    assert [f["tokens"] for f in facts] == [f["tokens"] for f in old_facts]
    assert max(f["tokens"] for f in facts) == 378
    assert {f["rung"] for f in facts if f["tokens"] > 256} == {384}
    assert {f["rung"] for f in old_facts if f["tokens"] > 256} == {512}
    assert eng.num_traces == len(eng._rung_traced)


@pytest.mark.quick
def test_engine_obs_coverage_closed():
    """engine.step ships observed: catalog + span category + cost
    family all present, so L005/doctor coverage stays empty-pinned."""
    from flashinfer_tpu.obs import costmodel
    from flashinfer_tpu.obs.catalog import API_OPS, METRICS, SERVING_OPS
    from flashinfer_tpu.obs.spans import SPAN_CATEGORIES

    assert "engine.step" in API_OPS
    assert "engine.step" in SERVING_OPS
    assert "engine.step" in SPAN_CATEGORIES
    assert costmodel.API_OP_COSTS["engine.step"] == "engine_step"
    assert callable(getattr(costmodel, "engine_step"))
    assert not costmodel.uncovered_api_ops()
    for name in ("engine.requests", "engine.finished", "engine.steps",
                 "engine.step_tokens", "engine.prefix_hit_tokens",
                 "engine.prefix_miss_tokens", "engine.evictions",
                 "engine.preemptions", "engine.pool_pages_in_use",
                 "engine.pool_pages_free"):
        assert name in METRICS, name


def test_engine_counters_and_doctor_section(params, monkeypatch):
    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    from flashinfer_tpu import obs

    obs.reset()
    rng = np.random.default_rng(31)
    # max_batch=2 staggers admission so later requests find the first
    # wave's prefix pages already in the trie (simultaneous admission
    # of a cold cache legitimately takes zero hits)
    eng = _mk_engine(params, max_batch=2)
    for i, p in enumerate(_prompts(rng, 4)):
        eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=2))
    eng.run()
    snap = obs.snapshot()

    def total(name):
        return sum(snap["counters"].get(name, {}).values())

    assert total("engine.requests") == 4
    assert total("engine.finished") == 4
    assert total("engine.steps") == eng.steps
    assert total("engine.prefix_hit_tokens") > 0
    assert total("engine.prefix_miss_tokens") > 0
    assert snap["gauges"]["engine.pool_pages_free"][""] == \
        float(eng.pool.free_pages)
    obs.reset()


@pytest.mark.quick
def test_cascade_compose_exact_passthrough():
    """compose_cascade_levels: an empty level (lse = -inf) passes the
    other level through BIT-exactly — the guard the engine's bitwise
    parity rests on."""
    from flashinfer_tpu.cascade import compose_cascade_levels

    rng = np.random.default_rng(37)
    o = jnp.asarray(rng.standard_normal((5, 4, 8)), jnp.float32)
    lse = jnp.asarray(rng.standard_normal((5, 4)), jnp.float32)
    empty_o = jnp.zeros_like(o)
    empty_lse = jnp.full_like(lse, -1e30)
    out, s = compose_cascade_levels([(empty_o, empty_lse), (o, lse)])
    assert (np.asarray(out) == np.asarray(o)).all()
    assert (np.asarray(s) == np.asarray(lse)).all()
    out, s = compose_cascade_levels([(o, lse), (empty_o, empty_lse)])
    assert (np.asarray(out) == np.asarray(o)).all()
    # merge math sanity: two equal states keep the value, lse + ln 2
    out, s = compose_cascade_levels([(o, lse), (o, lse)])
    np.testing.assert_allclose(np.asarray(out), np.asarray(o),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s),
                               np.asarray(lse) + np.log(2.0), rtol=1e-6)


def test_engine_lifecycle_spans(params, monkeypatch):
    """Request lifecycle rides the PR 10 span layer: TTFT/TPOT
    histograms fill from engine-served requests."""
    monkeypatch.setenv("FLASHINFER_TPU_SPANS", "1")
    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    from flashinfer_tpu import obs
    from flashinfer_tpu.obs import spans

    obs.reset()
    spans.reset()
    rng = np.random.default_rng(41)
    eng = _mk_engine(params)
    for i, p in enumerate(_prompts(rng, 3)):
        eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=3))
    eng.run()
    ls = obs.lifecycle_snapshot()
    assert ls["lifecycle.ttft_us"]["count"] == 3
    assert ls["lifecycle.tpot_us"]["count"] == 3 * 2  # gaps after 1st
    assert ls["lifecycle.tokens_per_s"]["count"] == 3
    obs.reset()
    spans.reset()


# ---------------------------------------------------------------------------
# Handing a step to the device (ISSUE 31): the kernel tier's lowering
# builds and sends what the step's launches read, in one transfer
# ---------------------------------------------------------------------------

_HANDOVER_FACTS = ("upload_kib", "upload_transfers", "mask_units",
                   "mask_full_uploads", "plans_lowered")


def _drain(eng):
    facts = []
    while eng.has_work():
        facts.append(eng.step())
    return ({rid: r.out_tokens for rid, r in eng._finished.items()},
            [f for f in facts if f["tokens"]])


def _own_span_chunks(params, backend):
    """Prompts of several pages prefilled 16 tokens a step: every chunk
    after a prompt's first lies inside the request's own level-0 span."""
    rng = np.random.default_rng(41)
    eng = _mk_engine(params, share=False, attention_backend=backend,
                     sampling=SamplingConfig(temperature=0.8, top_k=20))
    for i, n in enumerate((29, 43, 18, 35)):
        eng.submit(EngineRequest(
            f"r{i}", [int(t) for t in rng.integers(1, CFG.vocab_size, n)],
            max_new_tokens=4))
    return _drain(eng)


def _shared_prefix(params, backend):
    rng = np.random.default_rng(43)
    eng = _mk_engine(params, attention_backend=backend,
                     sampling=SamplingConfig(temperature=0.8, top_k=20))
    prompts = _prompts(rng, 6)
    for i, p in enumerate(prompts[:2]):      # the prefixes enter the trie
        eng.submit(EngineRequest(f"a{i}", list(p), max_new_tokens=3))
    first, facts = _drain(eng)
    for i, p in enumerate(prompts[2:]):      # four riders on two runs
        eng.submit(EngineRequest(f"b{i}", list(p), max_new_tokens=3))
    second, more = _drain(eng)
    assert sum(r.hit_tokens for r in eng._finished.values()) > 0
    return {**first, **second}, facts + more


def _preempt_resume(params, backend):
    rng = np.random.default_rng(47)
    pA = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    pB = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    eng = _mk_engine(params, attention_backend=backend, num_pages=7,
                     max_batch=2, max_seq_tokens=48)
    eng.submit(EngineRequest("A", list(pA), max_new_tokens=8, priority=5))
    head = [eng.step() for _ in range(6)]    # A is mid-decode ...
    eng.submit(EngineRequest("B", list(pB), max_new_tokens=4, priority=0))
    tokens, facts = _drain(eng)              # ... when B takes its pages
    assert eng._finished["A"].preemptions == 1
    return tokens, head + facts


@pytest.mark.parametrize("run", [_own_span_chunks, _shared_prefix,
                                 _preempt_resume],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_tier_handover_serves_the_reference_tokens(params, run,
                                                          level0_kept):
    """Tokens bitwise equal to the reference tier's with the bitmap sent
    as the rows that are read, and the facts of the handover in every
    step: two buffers, the whole bitmap never.  Bitmap rows cross where a
    chunk is kept at level 0; the engine's own schedules, whose chunks
    ride level 1 whole (ISSUE 44), send rows of zeros in their place."""
    ref, ref_facts = run(params, "reference")
    got, facts = run(params, "kernel")
    assert got == ref
    assert (sum(f["mask_units"] for f in facts) > 0) == level0_kept
    for f in facts:        # one int32 buffer, and the bitmap rows beside it
        assert f["plans_lowered"] == 1 and f["upload_transfers"] == 2
        assert f["mask_full_uploads"] == 0 and f["upload_kib"] > 0
    for f in ref_facts:                       # no plan on the reference tier
        assert f["plans_lowered"] == f["mask_units"] == 0
        assert f["mask_full_uploads"] == 0 and f["upload_transfers"] == 1
        assert 0 < f["upload_kib"] < min(g["upload_kib"] for g in facts)


def _lone_lanes(params, backend):
    """No sharing: every decode lane is alone in its group in every step."""
    rng = np.random.default_rng(53)
    eng = _mk_engine(params, share=False, attention_backend=backend,
                     sampling=SamplingConfig(temperature=0.8, top_k=20))
    for i, n in enumerate((29, 9, 18, 35, 3)):
        eng.submit(EngineRequest(
            f"r{i}", [int(t) for t in rng.integers(1, CFG.vocab_size, n)],
            max_new_tokens=5))
    return _drain(eng)


def _riders(params, backend, new=(6, 6, 6, 6)):
    """Two cached runs, then riders on them with ``new`` tokens to serve
    (the first and third ride one run, the second and fourth the other)."""
    rng = np.random.default_rng(59)
    eng = _mk_engine(params, attention_backend=backend,
                     sampling=SamplingConfig(temperature=0.8, top_k=20))
    prompts = _prompts(rng, 2 + len(new))
    for i, p in enumerate(prompts[:2]):      # the prefixes enter the trie
        eng.submit(EngineRequest(f"a{i}", list(p), max_new_tokens=2))
    first, facts = _drain(eng)
    for i, (p, n) in enumerate(zip(prompts[2:], new)):
        eng.submit(EngineRequest(f"b{i}", list(p), max_new_tokens=n))
    second, more = _drain(eng)
    assert all(eng._finished[f"b{i}"].hit_tokens == 16
               for i in range(len(new)))
    return {**first, **second}, facts + more


def _sharer_finishes_first(params, backend):
    """Two riders of one run; the first is done after 2 tokens, so the
    second turns from a shared lane into a lone one between two steps."""
    return _riders(params, backend, new=(2, 1, 9))


@pytest.mark.parametrize("run", [_lone_lanes, _riders,
                                 _sharer_finishes_first, _preempt_resume],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_decode_lane_costs_decode_units_only(params, run):
    """ISSUE 40: a lane alone in its group rides decode units over its
    whole context, a lane that shares its run this step keeps level 0, and
    a lane may change from one to the other between steps: the kernel tier
    serves the reference tier's tokens in each case, and the step's facts
    say which lanes went which way."""
    ref, ref_facts = run(params, "reference")
    got, facts = run(params, "kernel")
    assert got == ref
    assert all(f["decode_lanes_whole"] == f["prefill_fallback_units"] == 0
               for f in ref_facts)
    lanes = [(f["decode_tokens"], f["decode_lanes_whole"]) for f in facts]
    assert all(0 <= whole <= dec for dec, whole in lanes)
    assert sum(dec for dec, _ in lanes) > 0
    # at most a fallback unit a q tile of each level (one tile a level at
    # these rungs), and never a unit that is not launched
    for f in facts:
        assert f["prefill_fallback_units"] <= 2
        assert f["prefill_units"] == f["prefill_units_launched"] >= 2
    shared = [(dec, whole) for dec, whole in lanes if whole < dec]
    if run in (_lone_lanes, _preempt_resume):
        assert not shared                 # nobody to share with
    else:
        # sharers come in twos at least: a lane whose sharer is in the
        # step stays on level 0 with it
        assert shared and all(dec - whole >= 2 for dec, whole in shared)
    if run is _sharer_finishes_first:
        # b0 and b2 decode together, then b2 goes on alone
        assert (2, 0) in lanes and lanes[-1] == (1, 1)
        assert lanes.index((2, 0)) < len(lanes) - 1


def _chunk_then_decode(params, backend):
    """One long prompt by itself: every chunk alone in its group, then the
    lane alone in it."""
    rng = np.random.default_rng(61)
    eng = _mk_engine(params, share=False, attention_backend=backend,
                     sampling=SamplingConfig(temperature=0.8, top_k=20))
    eng.submit(EngineRequest(
        "r0", [int(t) for t in rng.integers(1, CFG.vocab_size, 43)],
        max_new_tokens=5))
    return _drain(eng)


def _chunk_beside_a_rider(params, backend, rider_new=8, budget=16):
    """A cached run, a rider that decodes on it, and a second rider whose
    prompt arrives while the first runs: its chunk shares the run with the
    first's lane (``budget`` tokens of it a step)."""
    rng = np.random.default_rng(67)
    eng = _mk_engine(params, attention_backend=backend,
                     prefill_budget_tokens=budget,
                     sampling=SamplingConfig(temperature=0.8, top_k=20))
    shared = [int(t) for t in rng.integers(1, CFG.vocab_size, 17)]

    def prompt(n):
        return shared + [int(t) for t in rng.integers(1, CFG.vocab_size, n)]

    eng.submit(EngineRequest("a0", prompt(3), max_new_tokens=2))
    first, facts = _drain(eng)               # the prefix enters the trie
    eng.submit(EngineRequest("b0", prompt(2), max_new_tokens=rider_new))
    facts.append(eng.step())                 # b0's suffix, alone
    facts.append(eng.step())                 # b0 decodes ...
    eng.submit(EngineRequest("b1", prompt(6), max_new_tokens=4))
    second, more = _drain(eng)               # ... when b1's chunk joins it
    assert all(eng._finished[r].hit_tokens == 16 for r in ("b0", "b1"))
    return {**first, **second}, [f for f in facts if f["tokens"]] + more


def _chunk_outlives_its_sharer(params, backend):
    """The same with 4 prompt tokens a step and a rider that is done a step
    after the chunk joins it: the prompt's first chunk shares the run with
    the rider's lane, its second is alone in its group."""
    return _chunk_beside_a_rider(params, backend, rider_new=3, budget=4)


def _preempt_mid_prompt(params, backend):
    """A's 40-token prompt is two chunks in when B, more urgent, takes its
    pages; A starts over when B is done."""
    rng = np.random.default_rng(71)
    pA = [int(t) for t in rng.integers(1, CFG.vocab_size, 40)]
    pB = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    eng = _mk_engine(params, attention_backend=backend, num_pages=8,
                     max_batch=2, max_seq_tokens=48)
    eng.submit(EngineRequest("A", list(pA), max_new_tokens=3, priority=5))
    head = [eng.step() for _ in range(2)]    # A is mid-prompt ...
    eng.submit(EngineRequest("B", list(pB), max_new_tokens=4, priority=0))
    tokens, facts = _drain(eng)              # ... when B takes its pages
    assert eng._finished["A"].preemptions == 1
    assert eng._finished["A"].folded_out == 0     # no token yet: mid-prompt
    return tokens, head + facts


@pytest.mark.parametrize("run", [_chunk_then_decode, _chunk_beside_a_rider,
                                 _chunk_outlives_its_sharer,
                                 _preempt_mid_prompt],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_lone_prompt_chunk_costs_level_1_units_only(params, run):
    """ISSUE 44: a prompt chunk alone in its group rides level 1 over its
    whole context (``prefill_tokens_whole``) but for the prompt's last rows,
    those past its split (page 8: 1 to 8 of them), which stay the cascade's
    two states so that the first token is its bit for bit; a chunk that
    shares a cached run with another row of the step keeps level 0 (it lies
    past its split altogether: the group's key is the run up to it).  The
    kernel tier serves the reference tier's tokens in each case, no step
    makes a mask unit, and the step's facts say which tokens went which
    way."""
    ref, ref_facts = run(params, "reference")
    got, facts = run(params, "kernel")
    assert got == ref
    assert all(f["prefill_tokens_whole"] == 0 for f in ref_facts)
    assert [f["prefill_tokens"] for f in facts] \
        == [f["prefill_tokens"] for f in ref_facts]
    toks = [(f["prefill_tokens"], f["prefill_tokens_whole"]) for f in facts]
    for f in facts:
        # a chunk beside a sharer starts at the run's end: nothing to cut
        assert f["mask_units"] == f["mask_full_uploads"] == 0
        assert f["prefill_fallback_units"] <= 2
        assert f["prefill_units"] == f["prefill_units_launched"] >= 2
    # the steps that kept rows at level 0, as (prompt tokens, of them whole)
    kept = [(n, whole) for n, whole in toks if whole < n]
    lanes = [(f["decode_tokens"], f["decode_lanes_whole"]) for f in facts]
    if run is _chunk_then_decode:
        # 43 tokens, split 40: 16, 16, then 8 inside the span and 3 past it
        assert [t for t in toks if t[0]] == [(16, 16), (16, 16), (11, 8)]
        assert all(dec == whole for dec, whole in lanes)
    elif run is _preempt_mid_prompt:
        # B's 20 (split 16) as 16 + 4; A's 40 (split 32) ends on 8 past it
        assert kept == [(4, 0), (8, 0)]
        assert [t for t in toks if t[0] and t not in kept] == [(16, 16)] * 3
        assert all(dec == whole for dec, whole in lanes)
    elif run is _chunk_beside_a_rider:
        # a0's and b0's last rows by themselves, then b1's 7 tokens in one
        # step beside b0's lane: level 0 for both rows
        assert kept == [(4, 0), (3, 0), (7, 0)]
        assert lanes[toks.index((7, 0))] == (1, 0)
    else:
        # ... then 4 of b1's 7 beside b0's last lane, and 3 by themselves
        assert kept == [(4, 0), (3, 0), (4, 0), (3, 0)]
        assert [t for t in toks if t[0] and t not in kept] == [(4, 4)] * 4
        assert [lanes[i] for i, t in enumerate(toks) if t in kept] \
            == [(0, 0), (0, 0), (1, 0), (0, 0)]


def test_back_to_back_schedules_on_one_rung_agree_with_fresh_engines(
        params, chunks_at_level0):
    """A long chunked prompt, then two short prompts, through ONE engine
    on the same rungs: the second wave's tokens and mask units are what a
    fresh engine serves for it (nothing of the larger step's plans, bitmap
    rows or schedule arrays is left in the smaller step's).  With the
    chunks kept under level 0's masks, so that there are bitmap rows to
    leave behind."""
    rng = np.random.default_rng(53)
    waves = [[[int(t) for t in rng.integers(1, CFG.vocab_size, n)]
              for n in wave] for wave in ((45,), (9, 14))]

    def serve(eng, w):
        for i, p in enumerate(waves[w]):
            eng.submit(EngineRequest(f"w{w}r{i}", list(p), max_new_tokens=3))
        tokens, facts = _drain(eng)
        return {r: t for r, t in tokens.items() if r[1] == str(w)}, facts

    one = _mk_engine(params, share=False, attention_backend="kernel")
    _, larger = serve(one, 0)
    got, facts = serve(one, 1)
    want, fresh = serve(_mk_engine(params, share=False,
                                   attention_backend="kernel"), 1)
    assert got == want and len(got) == 2
    assert [f["mask_units"] for f in facts] == \
        [f["mask_units"] for f in fresh]
    assert sum(f["mask_units"] for f in larger) > 0
    assert sum(f["mask_units"] for f in facts) > 0
    # the second wave met a rung the first had used for a larger step

    def cells(fs):
        return max(f["prefill_cells_valid"] for f in fs if f["rung"] == 16)

    assert cells(facts) < cells(larger)
    assert one.num_traces == len(one._rung_traced)


def test_lowered_step_is_the_parents_at_every_rung(params):
    """The step's program does not know how its leaves crossed: lowered
    from the parent's arguments (a ``jnp.asarray`` a leaf, the whole
    bitmap) and from this lowering's (one ``device_put``, the bitmap
    spread on the device), the text is the same, rung by rung."""
    import flashinfer_tpu.serve.engine_kernels as ek

    eng = _mk_engine(params, attention_backend="kernel", max_seq_tokens=64)
    ec = eng.config
    for rung in ec.rungs():
        # a chunk inside its span beside a rider of it: level 0's mask cuts
        plans = ek.build_engine_work_units(
            [ek.SchedSeg(row0=0, n=rung - 1, pages=tuple(range(1, 9)),
                         split=48, kv_after=7 + rung, decoding=False, slot=0,
                         group=0),
             ek.SchedSeg(row0=rung - 1, n=1, pages=(*range(1, 7), 9, 10),
                         split=48, kv_after=50, decoding=True, slot=1,
                         group=0)], rung=rung, geom=eng._geom)
        assert plans["stats"]["mask_units"] > 0
        B, ppr = ec.max_batch, eng._ppr
        sched = (*(np.zeros(rung, np.int32),) * 5,
                 *(np.zeros((B, ppr), np.int32),) * 2,
                 *(np.zeros(rung, np.int32),) * 2,
                 *(np.zeros(B, np.int32),) * 2)
        parents = dict(
            prefill0={k: jnp.asarray(plans["prefill0"][k])
                      for k in ek.PREFILL_ARRAY_KEYS},
            prefill1={k: jnp.asarray(plans["prefill1"][k])
                      for k in ek.PREFILL_ARRAY_KEYS if k != "mask_bytes"},
            decode={k: jnp.asarray(plans["decode"][k])
                    for k in ek.DECODE_ARRAY_KEYS},
            dec_rows=jnp.asarray(plans["dec_rows"]))
        old = eng._step.lower(params, *map(jnp.asarray, sched), parents,
                              eng.caches).as_text()
        sched_dev, kplans = jax.device_put(
            (sched, ek.plans_to_host(plans, eng._geom.mask_unit_cap(rung))))
        new = eng._step.lower(params, *sched_dev,
                              ek.plans_on_device(kplans),
                              eng.caches).as_text()
        assert new == old, rung
        # a lone chunk's plan (level 1 whole, no mask unit) is other VALUES
        # for the same program
        lone = ek.build_engine_work_units(
            [ek.SchedSeg(row0=0, n=rung, pages=tuple(range(1, 9)), split=48,
                         kv_after=8 + rung, decoding=False, slot=0,
                         group=0)], rung=rung, geom=eng._geom)
        assert lone["stats"]["mask_units"] == 0
        assert lone["stats"]["prefill_tokens_whole"] \
            == rung - max(8 + rung - 48, 0)      # the rows inside the span
        assert eng._step.lower(
            params, *sched_dev, ek.plans_on_device(jax.device_put(
                ek.plans_to_host(lone, eng._geom.mask_unit_cap(rung)))),
            eng.caches).as_text() == old, rung


def test_handover_at_the_cells_statics(params, level0_kept):
    """At the benchmark cells' engine statics (page 16, 2,048 tokens a
    request, 64 slots, a prefill budget of 512: 2,048 units a plan, a
    33.5 MB bitmap) a step hands over about a megabyte in two buffers,
    never the whole bitmap; a rung traces its step once and the program
    that cuts its inputs once."""
    from flashinfer_tpu.serve import handover

    eng = _mk_engine(params, attention_backend="kernel", num_pages=160,
                     page_size=16, max_batch=64, prefill_budget_tokens=512,
                     max_seq_tokens=2048, share=False)
    assert {eng._geom.prefill_unit_cap(r) for r in eng.config.rungs()} \
        == {2048}
    handover.cut.clear_cache()     # (this test's other run left its program)
    rng = np.random.default_rng(59)
    for i, n in enumerate((40, 23)):
        eng.submit(EngineRequest(
            f"r{i}", [int(t) for t in rng.integers(1, CFG.vocab_size, n)],
            max_new_tokens=2))
    tokens, facts = _drain(eng)
    assert all(len(t) == 2 for t in tokens.values())
    assert (facts[0]["mask_units"] > 0) == level0_kept and len(facts) > 1
    for f in facts:
        assert set(_HANDOVER_FACTS) <= set(f)
        assert f["plans_lowered"] == 1 and f["upload_transfers"] == 2
        assert f["mask_full_uploads"] == 0
        assert 512 < f["upload_kib"] < 1536
    assert eng.num_traces == len(eng._rung_traced) == 1
    assert handover.cut._cache_size() == 1


# ---------------------------------------------------------------------------
# The window layers' pool (ISSUE 37, serve/window_pool.py)
# ---------------------------------------------------------------------------


def test_window_pages_hold_the_window_and_never_pass_the_cap():
    """Requests grow by chunks and by single tokens under a random schedule:
    a request holds the pages from ``page_floor(kv_len - window)`` to its
    last position and never more than the cap, the pool's invariants hold,
    and everything is back when the requests end."""
    from flashinfer_tpu.serve.window_pool import WindowPages, pages_cap

    window, chunk, ps, batch = 40, 24, 8, 3
    wp = WindowPages(window=window, chunk=chunk, page_size=ps,
                     max_batch=batch, pages_per_req=64)
    assert wp.cap == pages_cap(window, chunk, ps) == 9
    assert wp.usable == batch * 9 and wp.pool.used_pages == 0
    rng = np.random.default_rng(0)
    reqs = []
    for slot, total in enumerate((300, 37, 150)):
        r = EngineRequest(f"r{slot}", [1] * total, max_new_tokens=1)
        r.slot = slot
        assert wp.reserve(r, total)
        reqs.append(r)
    assert wp.reserved == 9 + 5 + 9
    late = EngineRequest("late", [1] * 300, max_new_tokens=1)
    assert not wp.reserve(late, 300) and late.wreserved == 0
    live = list(reqs)
    while live:
        sched = []
        for r in live:
            n = min(int(rng.integers(1, chunk + 1)), len(r.prompt) - r.kv_len)
            r.kv_len += n
            sched.append((r, n))
        arrays, local, held = wp.assemble(sched, live, 64, batch)
        assert held == wp.pool.used_pages <= wp.reserved
        row = 0
        for (r, n), (_, row0, n2, kv_after) in zip(sched, local):
            assert (row0, n2) == (row, n) and len(r.wpages) <= wp.cap
            assert kv_after == r.kv_len - r.wfirst * ps
            # each row lands on the page of its position
            for i in range(n):
                p = r.kv_len - n + i
                assert arrays["token_page"][row + i] \
                    == r.wpages[p // ps - r.wfirst]
            np.testing.assert_array_equal(
                arrays["page_table"][r.slot, :len(r.wpages)], r.wpages)
            assert arrays["first_tok"][r.slot] == r.wfirst * ps
            row += n
        assert len({p for r in live for p in r.wpages}) == held   # no alias
        for r, _ in sched:
            wp.trim(r)
            assert r.wfirst == max(r.kv_len - window, 0) // ps
            assert r.wfirst + len(r.wpages) == (r.kv_len - 1) // ps + 1
        for r in [r for r in live if r.kv_len == len(r.prompt)]:
            wp.release(r)
            live.remove(r)
    assert wp.pool.used_pages == 0 and wp.reserved == 0
    assert wp.released_total > 0 and wp.reserve(late, 300)


def test_a_model_without_a_window_layer_has_no_second_pool(params):
    eng = _mk_engine(params)
    assert eng._wpages is None and eng._wgeom is None and not eng._window
    r = EngineRequest("r", [3, 4, 5, 6, 7], max_new_tokens=2)
    eng.submit(r)
    facts = eng.step()
    assert not r.wpages and r.wfirst == 0 and r.wreserved == 0
    assert not any(k.startswith("window_") for k in facts)
