"""Engine attention on the Pallas work-unit kernels — ISSUE 12.

The graduation contracts under pin:

- **cross-tier token parity** (THE acceptance anchor): the engine with
  ``attention_backend="kernel"`` (schedule lowered onto the PR 3
  work-unit prefill mainloop + PR 6 split-KV decode units, composed by
  the cascade merge fold — serve/engine_kernels.py, interpret mode on
  CPU) serves token-for-token what the ``"reference"`` tier serves —
  and the reference tier is bitwise-equal to the no-sharing oracle, so
  the kernel tier is transitively oracle-equal.  Pinned across
  {f32, int8-KV} x {prefix-hit, miss, preemption-resume, mixed
  chunked-prefill + decode rungs}, real sampling configs included
  (everything is seeded, so agreement is exact).
- **compile-once**: the kernel tier's plan-array shapes are rung
  statics (planner ``num_units_pad``, fixed decode-table width, the
  always-present level-0 mask operand), so a whole serving session
  stays on the <= 9-trace rung ladder exactly like the reference tier.
- **planner geometry**: rung-stable plan shapes across different
  schedules, the unit-cap overflow guard, and the ``return_lse``
  prefill output against the dense oracle.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                  SamplingConfig, ServingEngine)
from flashinfer_tpu.serve.engine_kernels import (EngineKernelGeom,
                                                 SchedSeg,
                                                 build_engine_work_units)

CFG = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
SAMPLING = SamplingConfig(temperature=0.8, top_k=20)


@pytest.fixture(scope="module")
def params():
    return init_llama_params(jax.random.PRNGKey(0), CFG)


def _mk_engine(params, backend, share=True, **over):
    kw = dict(num_pages=64, page_size=8, max_batch=4,
              prefill_budget_tokens=16, max_seq_tokens=64,
              sampling=SAMPLING, enable_prefix_cache=share,
              attention_backend=backend)
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


def _serve(params, prompts, backend, share=True, max_new=4, **over):
    eng = _mk_engine(params, backend, share=share, **over)
    for i, p in enumerate(prompts):
        eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=max_new))
    return eng.run(), eng


def _tier_pair(params, seed, kv_dtype=None, share=True, **kw):
    rng = np.random.default_rng(seed)
    prompts = _prompts(rng, 8)
    ref, _ = _serve(params, prompts, "reference", share=share,
                    kv_dtype=kv_dtype, **kw)
    ker, eng = _serve(params, prompts, "kernel", share=share,
                      kv_dtype=kv_dtype, **kw)
    return ref, ker, eng


# ---------------------------------------------------------------------------
# Cross-tier token parity
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_kernel_parity_prefix_hit_f32(params):
    """THE graduation pin: kernel-tier tokens == reference-tier tokens
    on a prefix-shared workload (real sampling config), and the
    reference tier is bitwise vs the no-sharing oracle — so the kernel
    tier is transitively oracle-equal."""
    ref, ker, eng = _tier_pair(params, seed=3)
    assert ker == ref
    # the oracle chain: reference with sharing OFF serves the same
    # tokens (PR 11's bitwise contract), closing kernel == oracle
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, 8)
    oracle, _ = _serve(params, prompts, "reference", share=False)
    assert oracle == ref
    # the kernel tier actually ran the work-unit planner
    assert eng.unit_stats["prefill_units"] > 0
    assert eng.unit_stats["decode_pages_real"] > 0


@pytest.mark.quick
def test_kernel_parity_prefix_hit_int8_kv(params):
    ref, ker, _ = _tier_pair(params, seed=5, kv_dtype=jnp.int8)
    assert ker == ref


def test_kernel_parity_miss(params):
    """Prefix cache disabled (every request a miss, one group per
    request): the cascade degenerates but tokens must not move."""
    ref, ker, _ = _tier_pair(params, seed=7, share=False)
    assert ker == ref


def test_kernel_parity_mixed_chunked_prefill_rungs(params):
    """Long prompts against a tiny prefill budget: every step mixes
    decode lanes with prefill chunks, chunks straddle the cascade
    split boundary (negative-qpos0 level-1 rows + partial level-0 mask
    windows), and the session walks multiple rungs."""
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, 6, shared_len=33, suffix_hi=9)
    kw = dict(num_pages=96, prefill_budget_tokens=12, max_new=5)
    ref, eref = _serve(params, prompts, "reference", **kw)
    ker, eker = _serve(params, prompts, "kernel", **kw)
    assert ker == ref
    assert len(eker._rung_traced) >= 2  # the mix actually spans rungs
    assert eker.num_traces == eref.num_traces


def test_kernel_parity_preemption_resume(params):
    """Preemption-by-eviction with recompute-on-resume on the KERNEL
    tier: the preempted small-pool run serves the never-preempted
    big-pool tokens, and both match the reference tier."""
    rng = np.random.default_rng(23)
    pA = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    pB = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]

    def run(backend, npages):
        eng = _mk_engine(params, backend, num_pages=npages, max_batch=2,
                         max_seq_tokens=48)
        eng.submit(EngineRequest("A", list(pA), max_new_tokens=8,
                                 priority=5))
        for _ in range(6):
            eng.step()  # A is mid-decode when B arrives
        eng.submit(EngineRequest("B", list(pB), max_new_tokens=4,
                                 priority=0))
        return eng.run(), eng

    small_k, es = run("kernel", 7)   # 6 usable pages: B preempts A
    big_k, eb = run("kernel", 32)
    small_r, _ = run("reference", 7)
    assert es._finished["A"].preemptions == 1
    assert eb._finished["A"].preemptions == 0
    assert small_k == big_k          # resume is reproducible in-tier
    assert small_k == small_r        # and cross-tier


# ---------------------------------------------------------------------------
# Compile-once / retrace budget
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_kernel_retrace_budget_and_steady_state(params):
    """The kernel tier keeps the rung-ladder contract: traces == rungs
    exercised (<= 9), every plan-array shape a rung static, and a
    second wave of fresh requests compiles NOTHING."""
    rng = np.random.default_rng(17)
    eng = _mk_engine(params, "kernel")
    for i, p in enumerate(_prompts(rng, 6)):
        eng.submit(EngineRequest(f"a{i}", list(p), max_new_tokens=3))
    facts = []
    while eng.has_work():
        facts.append(eng.step())
    first_wave = eng.num_traces
    assert first_wave == len(eng._rung_traced) <= 9
    assert all(n == 1 for n in eng._rung_traced.values())
    # the prefill launches' unit count is a VALUE of the step's plan: the
    # steps of one rung ran different counts (all of them real units) on
    # that rung's one trace
    by_rung = {}
    for f in facts:
        assert f["prefill_units_launched"] == f["prefill_units"] >= 2
        by_rung.setdefault(f["rung"], set()).add(f["prefill_units_launched"])
    assert max(len(counts) for counts in by_rung.values()) >= 3, by_rung
    for i, p in enumerate(_prompts(rng, 6)):
        eng.submit(EngineRequest(f"b{i}", list(p), max_new_tokens=3))
    eng.run()
    assert eng.num_traces == first_wave


# ---------------------------------------------------------------------------
# Planner geometry
# ---------------------------------------------------------------------------


def _geom(rung=16, ppr=8, max_batch=4, ps=8):
    return EngineKernelGeom.build(
        page_size=ps, pages_per_req=ppr, max_batch=max_batch,
        max_rung=rung, num_kv_heads=CFG.num_kv_heads,
        head_dim=CFG.head_dim, kv_itemsize=4)


@pytest.mark.quick
def test_planner_rung_stable_shapes():
    """Two very different schedules at ONE rung must produce plan
    bundles with IDENTICAL array shapes — the compile-once contract
    the engine's jit relies on."""
    g = _geom()

    def shapes(segs):
        plans = build_engine_work_units(segs, rung=16, geom=g)
        return {
            lvl: {k: np.asarray(v).shape
                  for k, v in plans[lvl].items()
                  if isinstance(v, np.ndarray)}
            for lvl in ("prefill0", "prefill1", "decode")
        }

    # one decoding request past its prompt vs a mixed 3-request step
    a = [SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=16, kv_after=20,
                  decoding=True, slot=0, group=0)]
    b = [SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=16, kv_after=19,
                  decoding=True, slot=0, group=0),
         SchedSeg(row0=1, n=1, pages=(1, 2, 4), split=16, kv_after=21,
                  decoding=True, slot=1, group=0),
         SchedSeg(row0=2, n=9, pages=(5, 6, 7), split=16, kv_after=9,
                  decoding=False, slot=2, group=1)]
    sa, sb = shapes(a), shapes(b)
    assert sa == sb
    # and the level-0 mask operand is ALWAYS present (a mask-less step
    # would otherwise flip the jit pytree structure and retrace)
    assert "mask_bytes" in sa["prefill0"]


@pytest.mark.quick
def test_planner_unit_cap_overflow_raises():
    from flashinfer_tpu.ops.paged_prefill import build_prefill_work_units

    with pytest.raises(ValueError, match="num_units_pad"):
        build_prefill_work_units(
            np.asarray([0, 64], np.int64), np.asarray([0, 8], np.int64),
            np.arange(8, dtype=np.int64), np.asarray([64], np.int64),
            16, 2, 8, causal=True, num_units_pad=1)


def test_planner_covers_every_rung_row():
    """Padding rows beyond the scheduled total ride kv_len=0 segments:
    both prefill plans must span [0, rung) so no output row is ever
    uninitialized HBM."""
    g = _geom()
    segs = [SchedSeg(row0=0, n=3, pages=(1, 2), split=8, kv_after=7,
                     decoding=False, slot=0, group=0)]
    plans = build_engine_work_units(segs, rung=16, geom=g)
    for lvl in ("prefill0", "prefill1"):
        p = plans[lvl]
        real = p["stats"]["units"]
        bq = p["block_q"]
        covered = set()
        for u in range(real):
            if p["wout"][u]:  # the tile write-back covers the block
                qs = int(p["qstart"][u])
                covered |= set(range(qs, qs + bq))
        assert covered >= set(range(16)), (lvl, sorted(covered))


# one schedule per kind of unit the two prefill-level launches can meet
# (rung 16, page 8; _geom() gives block_q 16, 8 pages a chunk)
_UNIT_SCHEDULES = {
    # decode lanes and prefill chunks in one step, no shared prefix
    "mixed": [
        SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=0, kv_after=20,
                 decoding=True, slot=0, group=0),
        SchedSeg(row0=1, n=1, pages=(4, 5), split=0, kv_after=11,
                 decoding=True, slot=1, group=1),
        SchedSeg(row0=2, n=6, pages=(6, 7, 8), split=0, kv_after=19,
                 decoding=False, slot=2, group=2),
        SchedSeg(row0=8, n=5, pages=(9,), split=0, kv_after=5,
                 decoding=False, slot=3, group=3)],
    # two requests on one 16-token prefix; the second's chunk starts
    # inside it, so level 0 has real units whose mask cuts
    "shared_prefix": [
        SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=16, kv_after=21,
                 decoding=True, slot=0, group=0),
        SchedSeg(row0=1, n=7, pages=(1, 2, 4), split=16, kv_after=19,
                 decoding=False, slot=1, group=0),
        SchedSeg(row0=8, n=3, pages=(5,), split=0, kv_after=3,
                 decoding=False, slot=2, group=1)],
    # one decode lane with no prefix: neither level has KV to attend,
    # both plans are their tile's one zero-write fallback unit
    "fallback_only": [
        SchedSeg(row0=0, n=1, pages=(1, 2), split=0, kv_after=9,
                 decoding=True, slot=0, group=0)],
}

# three lanes past their prompts, two of them riding one prefix (adjacent,
# as the engine's group sort leaves them): level 0 is ONE two-row unit over
# the shared run, no mask cuts; the third lane is alone in its group and has
# no level-0 unit; level 1 is its tile's fallback unit.  Kept out of
# _UNIT_SCHEDULES: its unit count equals "fallback_only"'s.
_DECODE_ONLY = [
    SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=16, kv_after=21,
             decoding=True, slot=0, group=0),
    SchedSeg(row0=1, n=1, pages=(1, 2, 6), split=16, kv_after=18,
             decoding=True, slot=3, group=0),
    SchedSeg(row0=2, n=1, pages=(4, 5), split=8, kv_after=12,
             decoding=True, slot=2, group=1)]
_LOWERED_SCHEDULES = dict(_UNIT_SCHEDULES, decode_only=_DECODE_ONLY)

# What the planner gives for these schedules at rung 16 since ISSUE 40 (the
# units of PR 30's planner less the fallback units of a tile that holds
# another unit, and less a lone lane's level-0 units): each level's REAL
# units in order, one row a unit:
# (qstart, rowlo, rowhi, qpos0, kvstart, kvlen, first, wout, qslot, code,
# the unit's pages before the zeros).
_UNIT_KEYS = ("qstart", "rowlo", "rowhi", "qpos0", "kvstart", "kvlen",
              "first", "wout", "qslot", "code")
_PLAN_UNITS = {
    # both lanes are alone in their groups and no chunk has a prefix:
    # level 0 attends nothing, its one tile keeps one write-back unit
    ("mixed", "prefill0"): [
        (0, 0, 0, -1, 0, 0, 1, 1, 0, 1, ())],
    ("mixed", "prefill1"): [
        (0, 2, 8, 11, 0, 19, 1, 0, 0, 1, (6, 7, 8)),
        (0, 8, 13, -8, 0, 5, 0, 1, 0, 1, (9,))],
    # the lane rides the run WITH the chunk: level 0 keeps both rows
    ("shared_prefix", "prefill0"): [
        (0, 0, 8, 8, 0, 16, 1, 1, 0, 2, (1, 2))],
    ("shared_prefix", "prefill1"): [
        (0, 1, 8, -5, 0, 3, 1, 0, 0, 1, (4,)),
        (0, 8, 11, -8, 0, 3, 0, 1, 0, 1, (5,))],
    ("fallback_only", "prefill0"): [
        (0, 0, 0, -1, 0, 0, 1, 1, 0, 1, ())],
    ("fallback_only", "prefill1"): [
        (0, 0, 0, -1, 0, 0, 1, 1, 0, 1, ())],
    ("decode_only", "prefill0"): [
        (0, 0, 2, 14, 0, 16, 1, 1, 0, 1, (1, 2))],
    ("decode_only", "prefill1"): [
        (0, 0, 0, -1, 0, 0, 1, 1, 0, 1, ())],
}


def _lone(segs, e):
    """A decoding lane whose group holds no other segment this step."""
    return e.decoding and sum(o.group == e.group for o in segs) == 1


def test_planner_counts_the_units_it_launches():
    """``n_units`` is the plan's count of real units, at least one per
    level, and what the step's stats call launched is those units."""
    from flashinfer_tpu.ops.paged_prefill import build_prefill_work_units

    g = _geom()
    chunk = g.prefill_ppc * g.page_size
    seen = {}
    for name, segs in _UNIT_SCHEDULES.items():
        plans = build_engine_work_units(segs, rung=16, geom=g)
        n = 0
        for lvl in ("prefill0", "prefill1"):
            p = plans[lvl]
            assert p["n_units"].dtype == np.int32 and p["n_units"].shape == ()
            assert p["n_units"] == p["stats"]["units"] >= 1
            assert p["qstart"].shape == (g.prefill_unit_cap(16),)
            n += int(p["n_units"])
        st = plans["stats"]
        assert st["prefill_units_launched"] == st["prefill_units"] == n
        assert st["prefill_cells_launched"] == n * g.block_q * chunk
        assert st["prefill_rows_launched"] == n * chunk
        assert st["prefill_cells_valid"] <= st["prefill_cells_launched"]
        assert st["prefill_fallback_units"] == sum(
            int((plans[lvl]["kvlen"][:int(plans[lvl]["n_units"])] == 0).sum())
            for lvl in ("prefill0", "prefill1"))
        assert st["decode_lanes_whole"] == sum(_lone(segs, e) for e in segs)
        seen[name] = (n, st["prefill_fallback_units"],
                      st["decode_lanes_whole"])
    # other counts at one shape; a fallback unit only in a tile of no other
    assert seen == {"mixed": (3, 1, 2), "shared_prefix": (3, 0, 0),
                    "fallback_only": (2, 2, 1)}
    # a plan padded to a cap with no unit at all would launch an empty grid
    with pytest.raises(AssertionError, match="real unit"):
        build_prefill_work_units(
            np.asarray([0, 0], np.int64), np.asarray([0, 0], np.int64),
            np.zeros(0, np.int64), np.asarray([0], np.int64),
            16, 2, 8, causal=True, num_units_pad=8)


@pytest.mark.parametrize("schedule", sorted(_UNIT_SCHEDULES))
def test_dynamic_unit_bound_is_bitwise_the_static_grid(schedule):
    """Both prefill-level launches with the plan's ``n_units`` as the
    trip count of the unit axis against the same launches over the whole
    padded grid (the plan without that leaf): ``o`` and ``lse`` bit for
    bit, and no further trace for another count at the same shapes."""
    from flashinfer_tpu.ops.paged_prefill import (CODE_PARTIAL_MASK,
                                                  fused_paged_prefill)
    from flashinfer_tpu.serve.engine_kernels import PREFILL_ARRAY_KEYS

    g = _geom()
    HQ, HKV, D = 4, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (16, HQ, D), jnp.float32)
    kc = jax.random.normal(kk, (12, HKV, g.page_size, D), jnp.float32)
    vc = jax.random.normal(kv, (12, HKV, g.page_size, D), jnp.float32)

    def launch(plans, lvl, dynamic):
        p = {k: jnp.asarray(plans[lvl][k]) for k in PREFILL_ARRAY_KEYS
             if k in plans[lvl]}
        if not dynamic:
            p = {k: v for k, v in p.items() if k != "n_units"}
        return fused_paged_prefill(
            q, kc, vc, p, num_units=p["qstart"].shape[0],
            block_q=g.block_q, pages_per_chunk=g.prefill_ppc,
            sm_scale=D ** -0.5, causal=lvl == "prefill1", return_lse=True)

    plans = build_engine_work_units(_UNIT_SCHEDULES[schedule], rung=16,
                                    geom=g)
    p0, p1 = plans["prefill0"], plans["prefill1"]
    real0 = p0["code"][:int(p0["n_units"])]
    assert (schedule == "shared_prefix") == bool(
        (real0 == CODE_PARTIAL_MASK).any())
    if schedule == "fallback_only":
        for p in (p0, p1):  # the lane's row and the rung's padding: one tile
            assert not p["kvlen"].any() and p["n_units"] == 1
    for lvl in ("prefill0", "prefill1"):
        assert plans[lvl]["n_units"] < plans[lvl]["qstart"].shape[0]
        o_dyn, lse_dyn = launch(plans, lvl, True)
        o_st, lse_st = launch(plans, lvl, False)
        np.testing.assert_array_equal(np.asarray(o_dyn), np.asarray(o_st))
        np.testing.assert_array_equal(np.asarray(lse_dyn),
                                      np.asarray(lse_st))
        assert np.isfinite(np.asarray(o_dyn)).all()
    traced = fused_paged_prefill._cache_size()
    for other in sorted(set(_UNIT_SCHEDULES) - {schedule}):
        launch(build_engine_work_units(_UNIT_SCHEDULES[other], rung=16,
                                       geom=g), "prefill0", True)
    assert fused_paged_prefill._cache_size() == traced


def _real(plan):
    """A plan's real units as ``{key: leading n_units entries}``."""
    n = int(plan["n_units"])
    return {k: plan[k][:n] for k in _UNIT_KEYS}


def test_a_lone_lane_rides_decode_units_over_its_whole_context():
    """ISSUE 40: a decoding lane alone in its group this step has no
    level-0 unit and no mask part; its decode row starts at page 0 with
    ``kvlen = kv_after``.  Lanes that ride one cached run together, and a
    chunk prefilled inside the span beside a rider of it, keep level 0 and
    its masks; the same lane turns lone when its sharer is not in the
    step."""
    from flashinfer_tpu.ops.paged_prefill import CODE_PARTIAL_MASK

    g = _geom()
    ps = g.page_size
    lane = SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=16, kv_after=21,
                    decoding=True, slot=1, group=0)
    lone = build_engine_work_units([lane], rung=16, geom=g)
    for lvl in ("prefill0", "prefill1"):   # one write-back unit a tile
        assert lone[lvl]["n_units"] == 1
        assert not _real(lone[lvl])["kvlen"].any()
    assert not lone["prefill0"]["mask_bytes"].any()
    d = lone["decode"]
    np.testing.assert_array_equal(d["pages"][1, :3], [1, 2, 3])
    assert d["kvlen"][1] == d["wu_kvlen"][1] == 21 and d["wu_page0"][1] == 0
    assert lone["dec_rows"][1] == 0
    st = lone["stats"]
    assert (st["decode_lanes_whole"], st["prefill_fallback_units"],
            st["mask_units"]) == (1, 2, 0)
    assert st["decode_pages_real"] == -(-21 // ps)
    assert st["decode_cells_valid"] == 21.0 and st["prefill_cells_valid"] == 0

    # the same lane beside a rider of its run: level 0 as it was (one
    # two-row unit over the run), both decode rows over their suffixes
    rider = SchedSeg(row0=1, n=1, pages=(1, 2, 4), split=16, kv_after=18,
                     decoding=True, slot=2, group=0)
    both = build_engine_work_units([lane, rider], rung=16, geom=g)
    u0 = _real(both["prefill0"])
    assert both["prefill0"]["n_units"] == 1
    assert (u0["rowlo"][0], u0["rowhi"][0], u0["kvlen"][0]) == (0, 2, 16)
    np.testing.assert_array_equal(both["prefill0"]["pages"][:2], [1, 2])
    d = both["decode"]
    assert list(d["kvlen"][[1, 2]]) == [5, 2]
    assert d["pages"][1, 0] == 3 and d["pages"][2, 0] == 4
    assert both["stats"]["decode_lanes_whole"] == 0
    assert both["stats"]["prefill_fallback_units"] == 1   # level 1's tile

    # a chunk inside its span BESIDE a rider of the run (a group of two):
    # level 0 over both rows' spans with a mask that cuts, and no lane moves
    chunk = SchedSeg(row0=0, n=6, pages=(1, 2, 3), split=16, kv_after=14,
                     decoding=False, slot=0, group=0)
    rider = SchedSeg(row0=6, n=1, pages=(1, 2, 4), split=16, kv_after=18,
                     decoding=True, slot=2, group=0)
    own = build_engine_work_units([chunk, rider], rung=16, geom=g)
    u0 = _real(own["prefill0"])
    assert (u0["rowlo"][0], u0["rowhi"][0], u0["kvlen"][0]) == (0, 7, 16)
    assert u0["code"][0] == CODE_PARTIAL_MASK
    assert own["prefill0"]["mask_bytes"][0].any()
    assert own["stats"]["mask_units"] == 1
    assert own["stats"]["decode_lanes_whole"] == 0
    assert own["stats"]["prefill_tokens_whole"] == 0
    assert list(own["decode"]["kvlen"]) == [0, 0, 2, 0]

    # a lone lane beside those two: their units stay, the lane adds none at
    # either level, and the counts are the plans'
    far = SchedSeg(row0=7, n=1, pages=(5, 6, 7, 8), split=24, kv_after=30,
                   decoding=True, slot=3, group=1)
    mixed = build_engine_work_units([chunk, rider, far], rung=16, geom=g)
    for lvl in ("prefill0", "prefill1"):
        for k in _UNIT_KEYS:
            np.testing.assert_array_equal(mixed[lvl][k], own[lvl][k])
        np.testing.assert_array_equal(mixed[lvl]["pages"], own[lvl]["pages"])
    np.testing.assert_array_equal(mixed["prefill0"]["mask_bytes"],
                                  own["prefill0"]["mask_bytes"])
    d = mixed["decode"]
    np.testing.assert_array_equal(d["pages"][3, :4], [5, 6, 7, 8])
    assert d["kvlen"][3] == 30 and mixed["dec_rows"][3] == 7
    st = mixed["stats"]
    assert st["decode_lanes_whole"] == 1
    # (level 1: the chunk lies inside its span and the lanes ride decode
    # units, so its tile attends nothing)
    assert st["prefill_fallback_units"] == 1 == \
        own["stats"]["prefill_fallback_units"]
    assert st["prefill_units"] == st["prefill_units_launched"] == sum(
        int(mixed[lvl]["n_units"]) for lvl in ("prefill0", "prefill1"))


def test_lone_and_shared_lanes_attend_the_same_context():
    """``engine_kernel_attention`` over a lone lane's plan (decode units
    over the whole context) and over the plan of the same lane beside a
    rider (level 0 over the run merged with decode units over the suffix):
    the lane's row agrees to rounding, and both are the dense softmax."""
    from flashinfer_tpu.serve.engine_kernels import (engine_kernel_attention,
                                                     plans_to_device)

    g = _geom()
    hq, hkv, d, ps = 4, 2, 32, g.page_size
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (16, hq, d), jnp.float32)
    kc = jax.random.normal(kk, (12, hkv, ps, d), jnp.float32)
    vc = jax.random.normal(kv, (12, hkv, ps, d), jnp.float32)
    lane = SchedSeg(row0=0, n=1, pages=(1, 2, 3), split=16, kv_after=21,
                    decoding=True, slot=1, group=0)
    rider = SchedSeg(row0=1, n=1, pages=(1, 2, 4), split=16, kv_after=18,
                     decoding=True, slot=2, group=0)

    def attend(segs):
        plans = build_engine_work_units(segs, rung=16, geom=g)
        return np.asarray(engine_kernel_attention(
            q, kc, vc, plans_to_device(plans), geom=g, sm_scale=d ** -0.5))

    alone, shared = attend([lane]), attend([lane, rider])
    rows = jnp.swapaxes(kc[jnp.asarray(lane.pages)], 1, 2).reshape(-1, hkv, d)
    vals = jnp.swapaxes(vc[jnp.asarray(lane.pages)], 1, 2).reshape(-1, hkv, d)
    qg = q[0].reshape(hkv, hq // hkv, d)
    p = jax.nn.softmax(
        jnp.einsum("ngd,snd->ngs", qg, rows[:21]) * d ** -0.5, -1)
    want = jnp.einsum("ngs,snd->ngd", p, vals[:21]).reshape(hq, d)
    np.testing.assert_allclose(alone[0], want, atol=2e-5)
    np.testing.assert_allclose(shared[0], want, atol=2e-5)
    assert not alone[1:].any()        # every other row attends nothing


def test_a_lone_chunk_rides_level_1_over_its_whole_context(
        sharers_of_no_rows):
    """ISSUE 44: a prompt chunk alone in its group has no level-0 unit, no
    ``pos`` list and no mask part for its rows inside the span; level 1
    attends their whole context from page 0 under the planner's causal rule
    on global positions.  The rows past the split of the chunk that ends
    its prompt keep level 0 (every column of the run, no mask unit) and the
    suffix at level 1.  The same chunk with a sharer in its group (here one
    of no rows: the planner as it was) keeps level 0 and its mask."""
    from flashinfer_tpu.ops.paged_prefill import (CODE_PARTIAL,
                                                  CODE_PARTIAL_MASK,
                                                  mask_lane_bytes)

    g = _geom()
    U, chunk_tokens = g.prefill_unit_cap(16), g.prefill_ppc * g.page_size
    chunk = SchedSeg(row0=0, n=6, pages=(1, 2, 3), split=16, kv_after=14,
                     decoding=False, slot=0, group=0)
    lone = build_engine_work_units([chunk], rung=16, geom=g)
    p0 = lone["prefill0"]
    assert p0["n_units"] == 1 and not _real(p0)["kvlen"].any()
    assert p0["mask_bytes"].dtype == np.uint8 and p0["mask_bytes"].shape == (
        U, g.block_q, mask_lane_bytes(chunk_tokens))
    assert not p0["mask_bytes"].any()
    # (no unit expands a bitmap: one zero byte in that shape, none allocated)
    assert p0["mask_bytes"].strides == (0, 0, 0)
    u1 = _real(lone["prefill1"])
    assert [int(u1[k][0]) for k in _UNIT_KEYS] == [
        0, 0, 6, 8, 0, 14, 1, 1, 0, CODE_PARTIAL]
    np.testing.assert_array_equal(lone["prefill1"]["pages"][:3], [1, 2, 0])
    st = lone["stats"]
    assert (st["prefill_tokens_whole"], st["mask_units"],
            st["decode_lanes_whole"], st["prefill_fallback_units"]) \
        == (6, 0, 0, 1)
    assert st["prefill_cells_valid"] == 6 * 14

    # the planner as it was: the chunk's units at level 0, its mask cutting
    kept = build_engine_work_units(sharers_of_no_rows([chunk]), rung=16,
                                   geom=g)
    u0 = _real(kept["prefill0"])
    assert (u0["rowlo"][0], u0["rowhi"][0], u0["kvlen"][0], u0["code"][0]) \
        == (0, 6, 16, CODE_PARTIAL_MASK)
    assert not _real(kept["prefill1"])["kvlen"].any()
    assert kept["stats"]["mask_units"] == 1
    assert kept["prefill0"]["mask_bytes"].flags.c_contiguous
    assert kept["prefill0"]["mask_bytes"][0].any()
    assert kept["stats"]["prefill_tokens_whole"] == 0
    assert kept["stats"]["prefill_cells_valid"] == 6 * 16   # masked or not

    # the chunk that ends its prompt (positions 12..20: four rows inside
    # the span, five past the split), a lone lane and a short prompt of no
    # full page in one step.  Rows inside the span: nothing at level 0, the
    # run from page 0 at level 1.  Rows past the split: the run at level 0
    # with no mask to expand, the suffix at level 1, as the cascade has them
    last = SchedSeg(row0=0, n=9, pages=(1, 2, 3), split=16, kv_after=21,
                    decoding=False, slot=0, group=0)
    lane = SchedSeg(row0=9, n=1, pages=(4, 5), split=8, kv_after=12,
                    decoding=True, slot=1, group=1)
    short = SchedSeg(row0=10, n=3, pages=(6,), split=0, kv_after=3,
                     decoding=False, slot=2, group=2)
    step = build_engine_work_units([last, lane, short], rung=16, geom=g)
    u0 = _real(step["prefill0"])
    assert list(zip(u0["rowlo"], u0["rowhi"], u0["kvlen"], u0["code"])) \
        == [(4, 9, 16, CODE_PARTIAL)]
    np.testing.assert_array_equal(step["prefill0"]["pages"][:3], [1, 2, 0])
    assert not step["prefill0"]["mask_bytes"].any()
    u1 = _real(step["prefill1"])
    assert list(zip(u1["rowlo"], u1["rowhi"], u1["qpos0"], u1["kvlen"])) \
        == [(0, 4, 12, 16), (4, 9, -4, 5), (10, 13, -10, 3)]
    np.testing.assert_array_equal(
        step["prefill1"]["pages"].reshape(-1, g.prefill_ppc)[:3, :3],
        [[1, 2, 0], [3, 0, 0], [6, 0, 0]])
    assert step["stats"]["prefill_tokens_whole"] == 4 + 3
    assert step["stats"]["decode_lanes_whole"] == 1
    assert step["stats"]["mask_units"] == 0
    # a chunk that lies past its split altogether (a resumed request
    # re-reading what it generated) is the cascade's plan, rows and all
    tail = SchedSeg(row0=0, n=3, pages=(1, 2, 3), split=16, kv_after=21,
                    decoding=False, slot=0, group=0)
    both = [build_engine_work_units(s, rung=16, geom=g)
            for s in ([tail], sharers_of_no_rows([tail]))]
    for lvl in ("prefill0", "prefill1"):
        for k in _UNIT_KEYS:
            np.testing.assert_array_equal(both[0][lvl][k], both[1][lvl][k])
    assert both[0]["stats"]["prefill_tokens_whole"] == 0

    # a longer context: level 1's units walk [0, kv_after) a chunk at a time
    wide = _geom(ppr=40)
    far = SchedSeg(row0=0, n=12, pages=tuple(range(1, 39)), split=296,
                   kv_after=280, decoding=False, slot=0, group=0)
    p1 = build_engine_work_units([far], rung=16, geom=wide)["prefill1"]
    u1 = _real(p1)
    assert list(u1["kvstart"]) == [0, 128, 256] and set(u1["kvlen"]) == {280}
    assert set(u1["qpos0"]) == {268}
    np.testing.assert_array_equal(
        p1["pages"][:3 * wide.prefill_ppc].reshape(3, -1)[:, 0], [1, 17, 33])
    # a window plan never had a level 0 to leave
    wplan = build_engine_work_units(_window_segs(), rung=16, geom=_geom(ppr=5),
                                    window_left=11)
    assert wplan["stats"]["prefill_tokens_whole"] == 0


@pytest.mark.parametrize("max_batch, rung, ppr, ps", [
    (4, 16, 64, 2), (8, 64, 512, 4), (64, 128, 256, 1)])
def test_unit_cap_holds_when_every_slot_ends_its_prompt(max_batch, rung, ppr,
                                                        ps):
    """The most plan segments a step can have: every slot a lone chunk at
    the request's longest context whose rows lie on both sides of its
    split, so each is two segments at both levels.  Both plans fit the
    rung's unit cap (the planner raises when one does not)."""
    g = EngineKernelGeom.build(
        page_size=ps, pages_per_req=ppr, max_batch=max_batch, max_rung=rung,
        num_kv_heads=2, head_dim=32, kv_itemsize=4)
    n, ctx = rung // max_batch, ppr * ps
    segs = [SchedSeg(row0=i * n, n=n,
                     pages=tuple(range(1 + i * ppr, 1 + (i + 1) * ppr)),
                     split=ctx - ps, kv_after=ctx, decoding=False, slot=i,
                     group=i) for i in range(max_batch)]
    plans = build_engine_work_units(segs, rung=rung, geom=g)
    chunks, cap = g.max_prefill_chunks, g.prefill_unit_cap(rung)
    # level 0: the rows past the split over the run; level 1: the rows
    # inside the span over the run, and one suffix unit a chunk
    assert plans["prefill0"]["n_units"] == max_batch * chunks <= cap
    assert plans["prefill1"]["n_units"] == max_batch * (chunks + 1) <= cap
    assert plans["stats"]["prefill_tokens_whole"] == rung - max_batch * ps
    assert plans["stats"]["mask_units"] == 0


def _attending(stats):
    return stats["prefill_units"] - stats["prefill_fallback_units"]


def _lone_chunk_steps():
    """Steps whose prompt chunk is alone in its group, as ``(geom, heads
    (hq, hkv, d), segs, cache pages)``: page 8 and 128-token KV chunks but
    for the last, page 16 and 256."""
    wide = _geom(ppr=40)
    pages = tuple(range(1, 39))

    def seg(row0, n, kv_after, **kw):
        return SchedSeg(row0=row0, n=n, pages=pages, split=296,
                        kv_after=kv_after, decoding=False, slot=0, group=0,
                        **kw)

    long_geom = EngineKernelGeom.build(
        page_size=16, pages_per_req=1040, max_batch=2, max_rung=16,
        num_kv_heads=1, head_dim=32, kv_itemsize=4)
    return {
        # every row inside the span, the diagonal in the second KV chunk
        "inside_span": (wide, (4, 2, 32), [seg(0, 12, 200)], 48),
        # the prompt's last chunk: 8 rows inside the span, 4 past the split
        "ends_prompt": (wide, (4, 2, 32), [seg(0, 12, 300)], 48),
        # in one q tile with two lone lanes and a prompt of no full page
        "packed_with_lanes": (wide, (4, 2, 32), [
            SchedSeg(row0=0, n=1, pages=(40, 41, 42), split=16, kv_after=21,
                     decoding=True, slot=1, group=1),
            dataclasses.replace(seg(1, 9, 150), group=2),
            SchedSeg(row0=10, n=1, pages=(43, 44), split=8, kv_after=12,
                     decoding=True, slot=2, group=3),
            SchedSeg(row0=11, n=4, pages=(45,), split=0, kv_after=4,
                     decoding=False, slot=3, group=4)], 48),
        # trinity-mini.longctx's depth at one K/V head: 64 KV chunks
        "ctx_16k": (long_geom, (2, 1, 32), [
            SchedSeg(row0=0, n=16, pages=tuple(range(1, 1026)), split=16384,
                     kv_after=16380, decoding=False, slot=0, group=0)], 1026),
    }


@pytest.mark.parametrize("step", ["inside_span", "ends_prompt",
                                  "packed_with_lanes", "ctx_16k"])
def test_lone_chunk_attends_what_the_parents_plan_attends(
        step, sharers_of_no_rows):
    """``engine_kernel_attention`` over a lone chunk's plan (rows inside
    the span at level 1 from page 0, level 0 empty for them) against the
    plan of the planner as it was (level 0 under the host-built mask, the
    suffix at level 1): the same KV chunks in the same order for every row,
    and the rows past the split still two states merged, so the two agree
    bit for bit.  Both are the dense causal softmax."""
    from flashinfer_tpu.serve.engine_kernels import (engine_kernel_attention,
                                                     plans_to_device)

    g, (hq, hkv, d), segs, n_pages = _lone_chunk_steps()[step]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (16, hq, d), jnp.float32)
    kc = jax.random.normal(kk, (n_pages, hkv, g.page_size, d), jnp.float32)
    vc = jax.random.normal(kv, (n_pages, hkv, g.page_size, d), jnp.float32)

    def attend(segs):
        plans = build_engine_work_units(segs, rung=16, geom=g)
        return plans["stats"], np.asarray(engine_kernel_attention(
            q, kc, vc, plans_to_device(plans), geom=g, sm_scale=d ** -0.5))

    st, alone = attend(segs)
    st0, kept = attend(sharers_of_no_rows(segs))
    chunks = [e for e in segs if not e.decoding]
    # rows past the split of a run, which stay at level 0
    past = sum(min(e.kv_after - e.split, e.n) for e in chunks
               if 0 < e.split < e.kv_after)
    assert past == (4 if step == "ends_prompt" else 0)
    assert st["mask_units"] == 0 < st0["mask_units"]
    assert st["prefill_tokens_whole"] == sum(e.n for e in chunks) - past
    assert st0["prefill_tokens_whole"] == 0
    # the same units that attend, in another launch; where rows past the
    # split share a q tile with rows inside the span, the tile's walk over
    # the run is launched at both levels (3 KV chunks here)
    assert _attending(st) == _attending(st0) + (3 if past else 0)
    np.testing.assert_array_equal(alone, kept)
    for e in segs:
        ids = jnp.asarray(e.pages[:-(-e.kv_after // g.page_size)])
        rows = jnp.swapaxes(kc[ids], 1, 2).reshape(-1, hkv, d)
        vals = jnp.swapaxes(vc[ids], 1, 2).reshape(-1, hkv, d)
        for i in range(e.n):
            pos = e.kv_after - e.n + i
            qg = q[e.row0 + i].reshape(hkv, hq // hkv, d)
            p = jax.nn.softmax(jnp.einsum(
                "ngd,snd->ngs", qg, rows[:pos + 1]) * d ** -0.5, -1)
            want = jnp.einsum("ngs,snd->ngd", p, vals[:pos + 1]).reshape(hq, d)
            np.testing.assert_allclose(alone[e.row0 + i], want, atol=2e-5)
    total = segs[-1].row0 + segs[-1].n
    assert not alone[total:].any()        # rung padding attends nothing


def _assert_plans_are_the_parents(name, plans, g, rung=16):
    """Every plan array against the planner's rule (PR 31's, with ISSUE
    40's units): the real units first, then the pad unit (zeros, code
    CODE_PARTIAL) up to the rung's cap; a bitmap for the units whose mask
    cuts and zeros for every other; a decode row over the lane's suffix,
    or over its whole context where the lane is alone in its group."""
    from flashinfer_tpu.ops.paged_prefill import (CODE_PARTIAL,
                                                  CODE_PARTIAL_MASK)

    segs, U, ps = _LOWERED_SCHEDULES[name], g.prefill_unit_cap(rung), \
        g.page_size
    for lvl in ("prefill0", "prefill1"):
        p, units = plans[lvl], _PLAN_UNITS[name, lvl]
        assert p["n_units"] == len(units) and p["n_units"].dtype == np.int32
        for i, k in enumerate(_UNIT_KEYS):
            want = np.full(U, CODE_PARTIAL if k == "code" else 0, np.int32)
            want[:len(units)] = [u[i] for u in units]
            assert p[k].dtype == np.int32, (lvl, k)
            np.testing.assert_array_equal(p[k], want, err_msg=f"{lvl} {k}")
        pages = np.zeros((U, g.prefill_ppc), np.int32)
        for i, u in enumerate(units):
            pages[i, :len(u[-1])] = u[-1]
        assert p["pages"].dtype == np.int32
        np.testing.assert_array_equal(p["pages"], pages.reshape(-1))
    assert "mask_bytes" not in plans["prefill1"]
    # level 0's bitmap: causal by GLOBAL position over the group's run,
    # ``kv_row <= pos``, for the rows of a unit whose mask cuts
    pos = np.zeros(rung, np.int64)
    for e in segs:
        pos[e.row0:e.row0 + e.n] = np.arange(e.kv_after - e.n, e.kv_after)
    p0 = plans["prefill0"]
    chunk = g.prefill_ppc * ps
    want = np.zeros((U, g.block_q, chunk // 8), np.uint8)
    for u in np.flatnonzero(p0["code"] == CODE_PARTIAL_MASK):
        bits = np.zeros((g.block_q, chunk), bool)
        lo, hi = p0["rowlo"][u], p0["rowhi"][u]
        cols = p0["kvstart"][u] + np.arange(
            min(chunk, p0["kvlen"][u] - p0["kvstart"][u]))
        bits[lo:hi, :len(cols)] = cols[None, :] <= pos[
            p0["qstart"][u] + lo:p0["qstart"][u] + hi, None]
        want[u] = np.packbits(bits, axis=-1, bitorder="little")
    mb = p0["mask_bytes"]
    assert mb.dtype == np.uint8 and mb.shape[:2] == want.shape[:2]
    np.testing.assert_array_equal(mb[..., :chunk // 8], want)
    assert not mb[..., chunk // 8:].any()
    assert plans["stats"]["mask_units"] == np.count_nonzero(
        p0["code"] == CODE_PARTIAL_MASK) == (name == "shared_prefix")
    # the decode lanes: each slot's pages and length (from its split on
    # for a lane that shares its run this step, from page 0 for a lone
    # one), one unit a slot (num_splits 1), the lane's flat row (the rung
    # where idle)
    d = plans["decode"]
    table = np.zeros((g.max_batch, g.dec_width), np.int32)
    lens = np.zeros(g.max_batch, np.int32)
    rows = np.full(g.max_batch, rung, np.int32)
    for e in segs:
        if e.decoding:
            lo = 0 if _lone(segs, e) else e.split
            sfx = e.pages[lo // ps:-(-e.kv_after // ps)]
            table[e.slot, :len(sfx)] = sfx
            lens[e.slot], rows[e.slot] = e.kv_after - lo, e.row0
    for k, want in (("pages", table), ("kvlen", lens), ("wu_kvlen", lens),
                    ("wu_req", np.arange(g.max_batch, dtype=np.int32)),
                    ("wu_page0", np.zeros(g.max_batch, np.int32))):
        assert d[k].dtype == np.int32, k
        np.testing.assert_array_equal(d[k], want, err_msg=f"decode {k}")
    assert plans["dec_rows"].dtype == np.int32
    np.testing.assert_array_equal(plans["dec_rows"], rows)


def _leaves_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("after_larger", [False, True],
                         ids=["fresh", "after_larger_step"])
@pytest.mark.parametrize("schedule", sorted(_LOWERED_SCHEDULES))
def test_lowering_builds_and_sends_what_the_launches_read(schedule,
                                                          after_larger):
    """ISSUE 31: the plans a step is lowered to are the parent's, element
    for element, but for bitmap rows no launch reads; the bitmap spread on
    the device from the rows that crossed is the host's whole array; a
    step with more mask units than the cap sends the whole array and the
    step's leaves are the same - and none of it depends on what the rung
    lowered before (a larger step first: no stale tail)."""
    from flashinfer_tpu.serve import handover
    from flashinfer_tpu.serve.engine_kernels import (
        plans_on_device, plans_to_device, plans_to_host,
        step_inputs_on_device)

    g = _geom()

    def lower(segs, cap):
        plans = build_engine_work_units(segs, rung=16, geom=g)
        host = plans_to_host(plans, cap)
        return plans, host, plans_on_device(jax.device_put(host))

    if after_larger:
        lower(_UNIT_SCHEDULES["shared_prefix"], g.mask_unit_cap(16))
        lower(_UNIT_SCHEDULES["mixed"], g.mask_unit_cap(16))
    plans, host, dev = lower(_LOWERED_SCHEDULES[schedule],
                             g.mask_unit_cap(16))
    _assert_plans_are_the_parents(schedule, plans, g)
    # the rows crossed by themselves ...
    h0 = host["prefill0"]
    assert "mask_bytes" not in h0
    assert h0["mask_rows"].shape == (g.mask_unit_cap(16),) \
        + plans["prefill0"]["mask_bytes"].shape[1:]
    n = plans["stats"]["mask_units"]
    assert (h0["mask_idx"][n:] == g.prefill_unit_cap(16)).all()
    assert not h0["mask_rows"][n:].any()
    # ... and the step's leaves are what the whole array would have given
    whole = plans_to_device(plans)          # the parent's way: no cap
    assert "mask_bytes" in plans_to_host(plans)["prefill0"]
    _leaves_equal(dev, whole)
    np.testing.assert_array_equal(np.asarray(dev["prefill0"]["mask_bytes"]),
                                  plans["prefill0"]["mask_bytes"])
    # over the cap: the whole array, the same leaves
    _, host0, dev0 = lower(_LOWERED_SCHEDULES[schedule], 0)
    assert ("mask_bytes" in host0["prefill0"]) == (n > 0)
    _leaves_equal(dev0, whole)
    # the way step() sends them: one int32 buffer and the bitmap rows,
    # cut into the step's leaves by one program; the whole bitmap a
    # buffer a leaf and no program
    inputs = dict(sched=(np.arange(16, dtype=np.int32),
                         np.arange(32, dtype=np.int32).reshape(4, 8)),
                  kplans=host, mplan={"n_tokens": np.int32(11)})
    got, sent, kib = handover.to_device(inputs,
                                        finish=step_inputs_on_device)
    cuts = handover.cut._cache_size()
    leaves = jax.tree_util.tree_leaves(inputs)
    assert sent == 2 and kib == sum(x.nbytes for x in leaves) / 1024
    _leaves_equal(got, dict(jax.device_put(inputs), kplans=whole))
    inputs0 = dict(inputs, kplans=plans_to_host(plans))
    got0, sent0, _ = handover.to_device(
        inputs0, finish=step_inputs_on_device, packed=False)
    assert sent0 == len(jax.tree_util.tree_leaves(inputs0)) == len(leaves) - 1
    _leaves_equal(got0, got)
    handover.to_device(inputs, finish=step_inputs_on_device)
    assert handover.cut._cache_size() == cuts   # one program a shape


def test_step_over_the_mask_cap_serves_the_same_tokens(params, monkeypatch,
                                                       level0_kept):
    """A step with more mask units than ``mask_unit_cap`` ships the whole
    bitmap (``mask_full_uploads``) and serves the tokens the capped path
    serves; with the rung's real cap no step of this run overflows.  The
    engine's own schedules make no mask unit since a lone chunk rides level
    1 (no whole bitmap then, whatever the cap); with its chunks kept at
    level 0 they make them as before."""
    rng = np.random.default_rng(31)
    prompts = [[int(t) for t in rng.integers(1, CFG.vocab_size, n)]
               for n in (27, 21, 9, 30)]     # chunks inside their own span

    def run():
        eng = _mk_engine(params, "kernel")
        for i, p in enumerate(prompts):
            eng.submit(EngineRequest(f"r{i}", list(p), max_new_tokens=3))
        facts = []
        while eng.has_work():
            facts.append(eng.step())
        return {r: q.out_tokens for r, q in eng._finished.items()}, facts

    capped, facts = run()
    assert (sum(f["mask_units"] for f in facts) > 0) == level0_kept
    # (of each prompt, the rows past its split stay at level 0: page 8)
    assert sum(f["prefill_tokens"] - f["prefill_tokens_whole"]
               for f in facts) == (
        87 if level0_kept else sum(n % 8 for n in (27, 21, 9, 30)))
    assert not any(f["mask_full_uploads"] for f in facts)
    monkeypatch.setattr(EngineKernelGeom, "mask_unit_cap",
                        lambda self, rung: 0)
    whole, facts0 = run()
    assert whole == capped
    assert [f["mask_full_uploads"] for f in facts0] == [
        int(f["mask_units"] > 0) for f in facts]
    for f, f0 in zip(facts, facts0):   # two buffers, or a buffer a leaf
        assert f["upload_transfers"] == 2
        assert (f0["upload_transfers"] > 2) == bool(f0["mask_full_uploads"])


def test_schedule_gap_raises():
    g = _geom()
    segs = [SchedSeg(row0=1, n=1, pages=(1,), split=0, kv_after=3,
                     decoding=True, slot=0, group=0)]
    with pytest.raises(ValueError, match="contiguously"):
        build_engine_work_units(segs, rung=16, geom=g)


def test_fused_prefill_return_lse_matches_oracle():
    """The new ``return_lse`` prefill output against the dense oracle
    (the cascade composition consumes these states, so a wrong lse
    silently skews every merged logit)."""
    from flashinfer_tpu.ops.paged_prefill import (build_prefill_work_units,
                                                  fused_paged_prefill)

    rng = np.random.default_rng(0)
    HQ, HKV, D, PS = 4, 2, 64, 8
    qo_lens, kv_lens = [5, 1, 0, 7], [24, 16, 8, 7]
    qo_indptr = np.concatenate([[0], np.cumsum(qo_lens)]).astype(np.int64)
    pages_per = [-(-l // PS) for l in kv_lens]
    pindptr = np.concatenate([[0], np.cumsum(pages_per)]).astype(np.int64)
    npages = int(pindptr[-1])
    pidx = rng.permutation(npages).astype(np.int64)
    q = jax.random.normal(jax.random.PRNGKey(1),
                          (int(qo_indptr[-1]), HQ, D), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(2), (npages, HKV, PS, D),
                           jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(3), (npages, HKV, PS, D),
                           jnp.float32)
    plan_np = build_prefill_work_units(
        qo_indptr, pindptr, pidx, np.asarray(kv_lens, np.int64),
        16, 2, PS, causal=True)
    statics = dict(num_units=plan_np.pop("num_units"),
                   block_q=plan_np.pop("block_q"),
                   pages_per_chunk=plan_np.pop("pages_per_chunk"))
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    out, lse = fused_paged_prefill(q, kc, vc, plan, sm_scale=D ** -0.5,
                                   causal=True, return_lse=True,
                                   **statics)
    # dense oracle per request (bottom-right causal alignment)
    for r in range(len(qo_lens)):
        qs, qe = int(qo_indptr[r]), int(qo_indptr[r + 1])
        if qe <= qs:
            continue
        pages = pidx[pindptr[r]:pindptr[r + 1]]
        kr = np.asarray(kc)[pages].transpose(0, 2, 1, 3).reshape(
            -1, HKV, D)[:kv_lens[r]]
        vr = np.asarray(vc)[pages].transpose(0, 2, 1, 3).reshape(
            -1, HKV, D)[:kv_lens[r]]
        kg = np.repeat(kr, HQ // HKV, axis=1)
        vg = np.repeat(vr, HQ // HKV, axis=1)
        qr = np.asarray(q)[qs:qe]
        qpos = kv_lens[r] - (qe - qs) + np.arange(qe - qs)
        s = np.einsum("qhd,khd->qhk", qr, kg) * (D ** -0.5)
        valid = np.arange(kv_lens[r])[None, :] <= qpos[:, None]
        s = np.where(valid[:, None, :], s, -np.inf)
        mx = s.max(-1, keepdims=True)
        has = np.isfinite(mx)
        p = np.where(valid[:, None, :],
                     np.exp(s - np.where(has, mx, 0.0)), 0.0)
        l = p.sum(-1, keepdims=True)
        o_ref = np.einsum("qhk,khd->qhd", p / np.where(l > 0, l, 1.0), vg)
        lse_ref = np.where(l[..., 0] > 0,
                           mx[..., 0] + np.log(np.maximum(l[..., 0],
                                                          1e-30)),
                           -1e30)
        np.testing.assert_allclose(np.asarray(out)[qs:qe], o_ref,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse)[qs:qe], lse_ref,
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Knob + config surface
# ---------------------------------------------------------------------------


@pytest.mark.quick
def test_attention_backend_knob_registered(params):
    from flashinfer_tpu.autotuner import KNOWN_KNOBS, validate_tactic

    spec = KNOWN_KNOBS["engine.attention_backend"]
    assert spec.kind == "str"
    assert set(spec.choices) == {"reference", "kernel"}
    assert validate_tactic("engine.attention_backend", "kernel") is None
    assert validate_tactic("engine.attention_backend", "cuda") is not None
    # EngineConfig.from_knobs resolves it (default: the oracle tier)
    cfg = EngineConfig.from_knobs(CFG, num_pages=64)
    assert cfg.attention_backend in ("reference", "kernel")
    with pytest.raises(ValueError, match="attention_backend"):
        ServingEngine(CFG, params, EngineConfig(
            num_pages=64, page_size=8, attention_backend="vulkan"))


def test_kernel_tier_cost_is_launched_vs_effective(params):
    """The kernel tier's aggregate cost prices launched work from the
    REAL unit stats (padded grids included) with the exact attended
    pairs as flops_effective — never equal unless padding was zero."""
    rng = np.random.default_rng(29)
    prompts = _prompts(rng, 6)
    _, eng = _serve(params, prompts, "kernel")
    cost = eng.aggregate_cost()
    assert cost.flops_effective is not None
    assert cost.flops > cost.flops_effective
    us = eng.unit_stats
    assert us["kv_pairs_launched"] >= us["prefill_cells_valid"]
    # the reference tier keeps the launched == effective convention
    _, ref_eng = _serve(params, prompts, "reference")
    assert ref_eng.aggregate_cost().flops_effective is None


# ---------------------------------------------------------------------------
# The facts step() returns (ISSUE 24): each step's own counts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_step_facts_add_up_and_queue_wait_counts_once(params, backend):
    """Every step's facts are that step's own values: over a run they
    add up to the engine's running totals (``tokens_total``,
    ``sampled_total``, ``unit_stats``; the unit facts are 0 on the
    reference tier), prompt and decode tokens split the tokens
    scheduled, and a request's queue wait is counted in the step that
    ran its FIRST prompt chunk only — a preempted request that is
    admitted again does not count twice."""
    rng = np.random.default_rng(23)
    pA = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    pB = [int(t) for t in rng.integers(1, CFG.vocab_size, 20)]
    eng = _mk_engine(params, backend, num_pages=7, max_batch=2,
                     max_seq_tokens=48)   # 6 usable pages: B preempts A
    a = EngineRequest("A", list(pA), max_new_tokens=8, priority=5)
    eng.submit(a)
    facts = [eng.step() for _ in range(6)]  # A is mid-decode ...
    b = EngineRequest("B", list(pB), max_new_tokens=4, priority=0)
    eng.submit(b)                           # ... when B arrives
    while eng.has_work():
        facts.append(eng.step())
    assert a.preemptions == 1 and b.preemptions == 0
    busy = [f for f in facts if f["tokens"]]
    assert [f["step"] for f in busy] == list(range(len(busy)))
    assert sum(f["tokens"] for f in busy) == eng.tokens_total
    assert sum(f["sampled"] for f in busy) == eng.sampled_total
    assert sum(f["finished"] for f in busy) == 2
    for f in busy:
        assert f["prefill_tokens"] + f["decode_tokens"] == f["tokens"]
    # A's rows are computed again on resume, less what the trie kept
    assert sum(f["prefill_tokens"] for f in busy) > len(pA) + len(pB)
    for k in ServingEngine._UNIT_FACTS:
        assert sum(f[k] for f in busy) == pytest.approx(eng.unit_stats[k])
        assert (eng.unit_stats[k] > 0) == (backend == "kernel"), k
    # two requests, two first chunks, whatever happened to them later
    assert sum(f["first_scheduled"] for f in busy) == 2
    firsts = [f for f in busy if f["first_scheduled"]]
    assert len(firsts) == 2 and firsts[0]["step"] == 0
    assert a.first_sched_t is not None and b.first_sched_t is not None
    assert sum(f["queue_wait_s"] for f in busy) == pytest.approx(
        (a.first_sched_t - a.enqueue_t) + (b.first_sched_t - b.enqueue_t))
    assert all(f["queue_wait_s"] == 0.0 for f in busy
               if not f["first_scheduled"])


# ---------------------------------------------------------------------------
# The window layers' plan (ISSUE 37): the same planner once more, under the
# window rule, over a request's window page list
# ---------------------------------------------------------------------------


def _window_segs():
    """A 12-row chunk whose request holds 5 pages from its window's first
    one (positions counted from there: 28 before the chunk, 40 after), and
    a decode lane holding 3 pages (21 positions)."""
    return [SchedSeg(row0=0, n=12, pages=(3, 4, 5, 6, 7), split=0,
                     kv_after=40, decoding=False, slot=0, group=0),
            SchedSeg(row0=12, n=1, pages=(9, 10, 11), split=0, kv_after=21,
                     decoding=True, slot=2, group=1)]


def test_window_plan_is_level_1_under_the_window_rule_and_no_level_0():
    from flashinfer_tpu.ops.paged_prefill import build_prefill_work_units

    g = _geom(ppr=5)
    segs = _window_segs()
    plans = build_engine_work_units(segs, rung=16, geom=g, window_left=11)
    assert set(plans) == {"prefill1", "decode", "dec_rows", "stats"}
    assert plans["stats"]["mask_units"] == 0
    # level 1 is the planner's own plan of the chunk under causal + window
    U = g.prefill_unit_cap(16)
    want = build_prefill_work_units(
        np.asarray([0, 12, 13, 16]), np.asarray([0, 5, 5, 5]),
        np.asarray([3, 4, 5, 6, 7]), np.asarray([40, 0, 0]), g.block_q,
        g.prefill_ppc, 8, causal=True, window_left=11, pack_tiles=True,
        prune=True, num_units_pad=U)
    for k in ("qstart", "rowlo", "rowhi", "qpos0", "kvstart", "kvlen",
              "code", "pages", "n_units"):
        np.testing.assert_array_equal(plans["prefill1"][k], want[k])
    # chunks wholly behind the first query's window are pruned: a request
    # that still held 300 positions launches one unit under the window rule
    # and one a chunk without it
    wide = _geom(ppr=40)
    long = [SchedSeg(row0=0, n=12, pages=tuple(range(1, 39)), split=0,
                     kv_after=300, decoding=False, slot=0, group=0)]
    assert wide.prefill_ppc * 8 == 128     # 300 positions: three chunks
    # (the rung's padding rows share the chunk's tile: no unit of their own)
    assert build_engine_work_units(long, rung=16, geom=wide)[
        "prefill1"]["n_units"] == 3
    assert build_engine_work_units(long, rung=16, geom=wide, window_left=11)[
        "prefill1"]["n_units"] == 1
    # the decode lane: its held pages, its length counted from the first
    d = plans["decode"]
    np.testing.assert_array_equal(d["pages"][2, :3], [9, 10, 11])
    assert d["kvlen"][2] == 21 and plans["dec_rows"][2] == 12
    # a window plan has no shared level
    shared = [SchedSeg(row0=0, n=4, pages=(1, 2), split=8, kv_after=12,
                       decoding=False, slot=0, group=0)]
    with pytest.raises(ValueError, match="no shared level"):
        build_engine_work_units(shared, rung=16, geom=g, window_left=11)


@pytest.mark.parametrize("schedule", sorted(_LOWERED_SCHEDULES))
def test_a_plan_without_a_window_is_the_plan_it_was(schedule):
    """``window_left`` left out or -1: the three plans, leaf for leaf (what
    ``_assert_plans_are_the_parents`` holds to the parent's rule)."""
    from flashinfer_tpu.serve.engine_kernels import plans_to_host

    g = _geom()
    segs = _LOWERED_SCHEDULES[schedule]
    a = build_engine_work_units(segs, rung=16, geom=g)
    b = build_engine_work_units(segs, rung=16, geom=g, window_left=-1)
    assert list(a) == ["prefill1", "decode", "dec_rows", "stats",
                       "prefill0"] and a["stats"] == b["stats"]
    _leaves_equal(plans_to_host(a, None), plans_to_host(b, None))
    _assert_plans_are_the_parents(schedule, a, g)


def test_window_attention_is_dense_attention_under_the_window():
    """``engine_window_attention`` over the plan above against a dense
    softmax over each row's last 12 positions."""
    from flashinfer_tpu.serve.engine_kernels import (engine_window_attention,
                                                     window_plans_to_host)

    g = _geom(ppr=5)
    segs = _window_segs()
    plans = window_plans_to_host(
        build_engine_work_units(segs, rung=16, geom=g, window_left=11))
    hq, hkv, d, ps = 4, 2, 32, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (16, hq, d), jnp.float32)
    kc = jax.random.normal(kk, (12, hkv, ps, d), jnp.float32)
    vc = jax.random.normal(kv, (12, hkv, ps, d), jnp.float32)
    out = np.asarray(engine_window_attention(
        q, kc, vc, jax.tree.map(jnp.asarray, plans), geom=g,
        sm_scale=d ** -0.5, window_left=11))
    for e in segs:
        rows = jnp.swapaxes(kc[jnp.asarray(e.pages)], 1, 2).reshape(-1, hkv, d)
        vals = jnp.swapaxes(vc[jnp.asarray(e.pages)], 1, 2).reshape(-1, hkv, d)
        for i in range(e.n):
            pos = e.kv_after - e.n + i
            lo = max(pos - 11, 0)
            kw, vw = rows[lo:pos + 1], vals[lo:pos + 1]
            qg = q[e.row0 + i].reshape(hkv, hq // hkv, d)
            p = jax.nn.softmax(
                jnp.einsum("ngd,snd->ngs", qg, kw) * d ** -0.5, -1)
            want = jnp.einsum("ngs,snd->ngd", p, vw).reshape(hq, d)
            np.testing.assert_allclose(out[e.row0 + i], want, atol=2e-5)
    assert not out[13:].any()      # rung padding: attention over nothing
