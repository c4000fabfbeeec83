"""granite-4.0-h's block on ``ServingEngine`` (ISSUE 28): a model that brings
its own layer through the engine's seam, recurrent state slots beside K/V
pages, Mamba-2 over the flat batch, and an expert layer that holds a share.

Everything is compared with the benchmark's plain float32 reference
(``perfbench/families/granite_hybrid_reference.py``, which imports nothing
of the program) on the family's seeded weights, at tiny sizes on the CPU.
The program runs in float32 here, so two float32 computations in another
order are compared (a chunked scan against a sequential one, a grouped matmul
against a dense one): 1e-6 of the logits' spread, but for a router choice at
a near tie, which that noise can flip and which then moves a logit by 1e-4
of the spread (seen under the 8-device CPU configuration of the whole suite);
the tolerance is 1e-3, and a wrong state, segment or expert moves them by 1.  LOGITS are compared, not sampled tokens, through the
``return_logits`` hook (off in every timed run).
"""

import dataclasses
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "perfbench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from families import granite_hybrid as fam  # noqa: E402
from families import granite_hybrid_reference as ref  # noqa: E402
from flashinfer_tpu.serve import (EngineConfig, EngineRequest,  # noqa: E402
                                  SamplingConfig, ServingEngine)

SEED = 3
M = dict(fam.TINY, embedding_multiplier=12, residual_multiplier=0.22,
         logits_scaling=16, rms_norm_eps=1e-5)
TOL = 1e-3   # of the logits' spread (see above)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        fam.make_params(SEED, M))


def _mcfg(**over):
    return dataclasses.replace(fam.model_config(M), dtype=jnp.float32, **over)


def _engine(params, *, budget=16, backend="reference", **over):
    kw = dict(num_pages=64, page_size=8, max_batch=4,
              prefill_budget_tokens=budget, max_seq_tokens=64,
              sampling=SamplingConfig(top_k=1), attention_backend=backend,
              return_logits=True)
    kw.update(over)
    return ServingEngine(_mcfg(), params, EngineConfig(**kw))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, M["vocab_size"], n).tolist() for n in lengths]


def _serve(eng, reqs, on_step=None):
    """Run to the end; {rid: [logits of each served token]}."""
    for r in reqs:
        eng.submit(r)
    got = {}
    while eng.has_work():
        facts = eng.step()
        for rid, lg in eng.last_logits.items():
            got.setdefault(rid, []).append(lg)
        eng.last_logits = {}
        if on_step is not None:
            on_step(facts)
    return {rid: np.stack(rows) for rid, rows in got.items()}


def _reference_logits(req, on_rows=lambda *a: None):
    seq = req.prompt + req.out_tokens[:-1]
    rows = [len(req.prompt) - 1 + j for j in range(len(req.out_tokens))]
    return np.asarray(fam.reference_forward(
        [seq], M, SEED, on_rows=on_rows, precision="float32",
        logit_rows=[rows])[0])


def _close(got, want, tol=TOL):
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * np.std(want), (
        np.max(np.abs(got - want)), np.std(want))


# ---------------------------------------------------------------------------
# the engine against the reference's full forward pass
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "kernel"])
def test_prefill_then_decode_matches_the_reference(params, backend):
    """Prompts longer and shorter than the prefill budget, packed with each
    other's decode lanes: pages (attention) and slots (Mamba) carry the
    state across chunks and steps; every served token's logits are the
    reference's at that position."""
    eng = _engine(params, budget=8, backend=backend)
    reqs = [EngineRequest(f"r{i}", p, max_new_tokens=6)
            for i, p in enumerate(_prompts((21, 5, 13)))]
    seen = []
    got = _serve(eng, reqs, seen.append)
    for r in reqs:
        assert len(r.out_tokens) == 6
        _close(got[r.rid], _reference_logits(r))
    # both paths of the scan ran: segments of two rows or more, and lanes
    assert sum(f["ssm_prefill_tokens"] for f in seen) >= 21 + 5 + 13 - 3
    assert max(f["ssm_decode_lanes"] for f in seen) == 3
    assert max(f["state_slots_used"] for f in seen) == 3
    assert eng.pool.used_pages == 0 and eng._slots == [None] * 4


def test_the_engine_holds_the_reference_state(params):
    """What the family reads out of the engine for a request in flight (K/V
    rows through the page list, the convolution's tail and ``S`` at the
    slot) is what the reference holds after the same tokens, mid-prompt and
    mid-generation; the reference learns where the padded sequence ends
    from ``state_rows``."""
    eng = _engine(params, budget=8)
    reqs = [EngineRequest("long", _prompts((27,))[0], max_new_tokens=4),
            EngineRequest("short", _prompts((6,), seed=1)[0],
                          max_new_tokens=9)]
    for r in reqs:
        eng.submit(r)
    for stop in (2, 5):           # 'long' mid-prompt, then both generating
        while eng.steps < stop:
            eng.step()
        for r in reqs:
            n = r.kv_len
            assert n or (r.rid, stop) == ("short", 2)   # still queued behind
            if not n:
                continue
            rows = fam.state_rows(eng, r, n)
            seq = (r.seq()[:n] + [0] * 64)[:64]       # padded, as check.py
            worst = {}

            def on_rows(si, unit, want):
                for kind, w in want.items():
                    g = rows[kind][unit]
                    w = np.asarray(w)[:n] if kind in "kv" else np.asarray(w)
                    worst[unit, kind] = float(
                        np.linalg.norm(g - w) / np.linalg.norm(w))

            fam.reference_forward([seq], M, SEED, on_rows=on_rows,
                                  precision="float32", logit_rows=[[0]])
            assert sorted(k for _, k in worst) == ["conv", "conv", "k",
                                                   "ssm", "ssm", "v"]
            assert max(worst.values()) < TOL, (r.rid, n, worst)


@pytest.mark.parametrize("budget", [4, 16, 64])
def test_chunking_and_packing_leave_the_logits_alone(params, budget):
    """The same prompt served alone in one chunk, and in chunks of ``budget``
    tokens packed with two other requests: the tail and ``S`` cross chunk
    and step boundaries, and segments share one flat batch."""
    prompt = _prompts((29,), seed=5)[0]
    alone = _engine(params, budget=64)
    want = _serve(alone, [EngineRequest("a", prompt, max_new_tokens=5)])["a"]
    eng = _engine(params, budget=budget)
    others = [EngineRequest(f"o{i}", p, max_new_tokens=7)
              for i, p in enumerate(_prompts((3, 11), seed=6))]
    got = _serve(eng, others + [EngineRequest("a", prompt,
                                              max_new_tokens=5)])
    _close(got["a"], want)


def test_a_reused_slot_starts_from_zero(params):
    """A slot freed at release is handed to the next admission with the
    last holder's state still in it: the step starts the newcomer from
    zero, so it is served what a fresh engine serves it."""
    eng = _engine(params, max_batch=1)
    first = EngineRequest("first", _prompts((19,))[0], max_new_tokens=3)
    _serve(eng, [first])
    assert float(jnp.abs(eng.caches[0][1]).max()) > 0   # slot 0 is dirty
    prompt = _prompts((9,), seed=8)[0]
    got = _serve(eng, [EngineRequest("next", prompt, max_new_tokens=4)])
    fresh = _serve(_engine(params, max_batch=1),
                   [EngineRequest("next", prompt, max_new_tokens=4)])
    np.testing.assert_array_equal(got["next"], fresh["next"])


def test_preemption_recomputes_from_a_zeroed_slot(params):
    """A preempted request folds its tokens into the prompt and re-prefills
    (recompute): its slot is taken meanwhile and its state starts from
    zero on re-admission, and its later tokens' logits are the reference's."""
    eng = _engine(params, max_batch=1, num_pages=8, page_size=8)
    low = EngineRequest("low", _prompts((10,))[0], max_new_tokens=8,
                        priority=5)
    eng.submit(low)
    for _ in range(4):
        eng.step()
    assert low.state == "running" and len(low.out_tokens) >= 2
    got = {}
    high = EngineRequest("high", _prompts((12,), seed=2)[0],
                         max_new_tokens=3, priority=0)
    eng.submit(high)
    while eng.has_work():
        eng.step()
        for rid, lg in eng.last_logits.items():
            got.setdefault(rid, []).append(lg)
        eng.last_logits = {}
    assert low.preemptions == 1 and len(low.out_tokens) == 8
    # the reference over low's whole sequence: the logits served after the
    # resume are the last ones
    full = EngineRequest("low", low.prompt[:10], max_new_tokens=8)
    full.out_tokens = list(low.out_tokens)
    want = _reference_logits(full)
    after = np.stack(got["low"])
    _close(after, want[-len(after):])
    _close(np.stack(got["high"]), _reference_logits(high))


def test_one_trace_per_rung(params):
    eng = _engine(params, budget=16, max_batch=4)
    reqs = [EngineRequest(f"r{i}", p, max_new_tokens=5)
            for i, p in enumerate(_prompts((2, 17, 9, 30, 4, 12)))]
    rungs = set()
    _serve(eng, reqs, lambda f: rungs.add(f["rung"]))
    assert len(rungs) >= 3
    assert eng.num_traces == len(rungs)
    assert all(n == 1 for n in eng._rung_traced.values())


# ---------------------------------------------------------------------------
# what a recurrent model refuses, and what the Llama block keeps
# ---------------------------------------------------------------------------


def test_prefix_lookups_are_refused_and_counted(params):
    eng = _engine(params)
    shared = _prompts((24,))[0]
    reqs = [EngineRequest(f"r{i}", shared + [7 + i], max_new_tokens=2)
            for i in range(3)]
    _serve(eng, reqs[:1])
    _serve(eng, reqs[1:])
    assert eng.prefix_lookups_refused == 3
    assert all(r.hit_tokens == 0 for r in reqs)
    assert eng.prefix_cache.num_pages == 0


@pytest.mark.parametrize("over, what", [
    (dict(kv_offload="host"), "host spill"),
    (dict(role="prefill"), "migration"),
    (dict(role="decode"), "migration"),
    (dict(kv_dtype="int8"), "int8 K/V"),
])
def test_spill_and_migration_raise_by_name_at_construction(params, over, what):
    with pytest.raises(NotImplementedError, match=what):
        _engine(params, **over)


def test_spill_migrate_and_offload_raise_by_name(params):
    from flashinfer_tpu.serve import kv_tier

    eng = _engine(params)
    r = EngineRequest("r", _prompts((9,))[0], max_new_tokens=4)
    eng.submit(r)
    eng.step()
    with pytest.raises(NotImplementedError, match="host spill"):
        eng.offload_idle("r")
    with pytest.raises(NotImplementedError, match="host spill"):
        kv_tier.spill_request(eng, r)
    with pytest.raises(NotImplementedError, match="migration"):
        kv_tier.migrate_request(eng, eng, r)
    with pytest.raises(NotImplementedError, match="migration"):
        eng.adopt_migrated(r, None)


def test_the_llama_block_keeps_its_prefix_hits_and_pays_nothing():
    """The gate sits in ``PrefixCache``'s caller: a Llama engine still
    hits, allocates no state arrays and gets no plan."""
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    eng = ServingEngine(cfg, init_llama_params(jax.random.PRNGKey(0), cfg),
                        EngineConfig(num_pages=64, page_size=8, max_batch=4,
                                     prefill_budget_tokens=16,
                                     max_seq_tokens=64,
                                     sampling=SamplingConfig(top_k=1)))
    assert not eng._recurrent and eng._block is None
    assert all(len(c) == 2 and c[0].shape[0] == 64 for c in eng.caches)
    shared = _prompts((24,))[0]
    for i in range(2):
        eng.submit(EngineRequest(f"r{i}", shared + [7 + i],
                                 max_new_tokens=2))
        facts = [eng.step() for _ in range(4)]
    assert eng._finished["r1"].hit_tokens == 24
    assert eng.prefix_lookups_refused == 0
    assert "state_slots_used" not in facts[0]


def test_fused_ingest_is_inert_without_rotation(params):
    eng = _engine(params, backend="kernel", fused_ingest="on")
    assert eng._ingest is False
    r = EngineRequest("r", _prompts((16,))[0], max_new_tokens=2)
    _close(_serve(eng, [r])["r"], _reference_logits(r))


_LIGHT = """
import sys
import jax, jax.numpy as jnp
import flashinfer_tpu.serve as serve
from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
cfg = LlamaConfig.tiny(num_layers=2)
eng = serve.ServingEngine(cfg, init_llama_params(jax.random.PRNGKey(0), cfg),
                          serve.EngineConfig(num_pages=16))
eng.submit(serve.EngineRequest("r", [1, 2, 3], max_new_tokens=2))
eng.run()
# (the package's own __init__ has always imported mamba and fused_moe)
heavy = [m for m in sys.modules if m.startswith("flashinfer_tpu") and
         m.rsplit(".", 1)[-1] in ("mamba_kernel", "moe_gmm",
                                  "granite_hybrid", "recurrent",
                                  "block_step")]
assert not heavy, heavy
assert all(len(c) == 2 and c[0].ndim == 4 and c[0].shape[0] == 16
           for c in eng.caches)
print("light")
"""


def test_serving_llama_imports_none_of_the_new_family():
    """``import flashinfer_tpu.serve``, building a ``ServingEngine`` on the
    tiny Llama config and serving a request pull in none of what the new
    family needs, and allocate no slot arrays (a fresh interpreter: this
    process has imported them all)."""
    p = subprocess.run([sys.executable, "-c", _LIGHT], capture_output=True,
                       text=True, timeout=600, cwd=ROOT,
                       env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
                            "PATH": "/usr/bin:/bin", "HOME": str(ROOT)})
    assert p.stdout.strip().endswith("light"), p.stderr[-2000:]


# ---------------------------------------------------------------------------
# the layer alone
# ---------------------------------------------------------------------------


def _layer_ctx(mcfg, t, segments, slots):
    """The context a layer gets for a flat batch of ``t`` rows holding
    ``segments`` ((row0, n, slot, fresh)), no attention."""
    from flashinfer_tpu.serve import recurrent
    from flashinfer_tpu.serve.block_step import StepContext

    total = sum(n for _, n, _, _ in segments)
    plan, _ = recurrent.build_plan(segments, tokens=total, rung=t,
                                   max_batch=slots, chunk=mcfg.scan_chunk)
    tok_req = np.zeros(t, np.int32)
    for row0, n, slot, _ in segments:
        tok_req[row0:row0 + n] = slot
    return StepContext(positions=jnp.zeros(t, jnp.int32),
                       tok_req=jnp.asarray(tok_req), attend=None,
                       plan={k: jnp.asarray(v) for k, v in plan.items()},
                       aux={})


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 and 4-7 as two shares of one Mamba layer: what each adds,
    with the mixer and the shared expert (which every chip computes alike)
    counted once, sums to the reference's uncut layer."""
    from flashinfer_tpu.models import granite_hybrid as gh

    whole = dict(M, num_local_experts=8)
    layer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         fam._layer(jax.random.PRNGKey(11),
                                    fam._shapes(whole, "mamba")))
    t = 16
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(12), (t, 128), jnp.float32)
    want, _, _, _ = ref.mamba_layer(
        x, jnp.int32(t), layer, n_heads=8, d_head=16, d_state=16, n_groups=1,
        eps=1e-5, res=0.22, top_k=3, first=0)
    outs, rows = [], []
    for first in (0, 4):
        mcfg = _mcfg(local_experts=(first, 4))
        share = dict(layer, w_gate_up=layer["w_gate_up"][first:first + 4],
                     w_down=layer["w_down"][first:first + 4])
        ctx = _layer_ctx(mcfg, t, [(0, t, 0, True)], slots=2)
        mixed, _ = gh._mamba_mixer(mcfg, x, share,
                                   mcfg.new_recurrent_state(2), ctx)
        out, n_rows = gh._expert_layer(mcfg, mixed, share, ctx)
        outs.append(out)
        rows.append(np.asarray(n_rows))
    # every token's 3 routes land in one share or the other
    assert rows[0].sum() + rows[1].sum() == 3 * t and min(map(sum, rows)) > 0
    u = ref.rmsnorm(mixed, layer["post_norm"], 1e-5)
    shared_once = mixed + 0.22 * ref.swiglu(
        u, layer["shared_gate_up"], layer["shared_down"], "float32")
    total = outs[0] + outs[1] - shared_once
    assert float(jnp.max(jnp.abs(total - want))) < TOL * float(jnp.std(want))


def test_the_scan_respects_segments_and_slots():
    """One flat batch: a lane from slot 2's state, a fresh 11-row segment on
    slot 0 and a continuing 5-row one on slot 1 (units of 4 rows).  Each
    segment's outputs and final state are those of the reference run over
    that request's whole sequence alone."""
    from flashinfer_tpu.models import granite_hybrid as gh

    mcfg = _mcfg(scan_chunk=4)
    layer = jax.tree.map(lambda a: a.astype(jnp.float32),
                         fam._layer(jax.random.PRNGKey(21),
                                    fam._shapes(M, "mamba")))
    xs = {s: 0.3 * jax.random.normal(jax.random.PRNGKey(30 + s), (n, 128),
                                     jnp.float32)
          for s, n in ((0, 11), (1, 9), (2, 7))}

    def alone(x):
        out, tail, s_n, _ = ref.mamba_layer(
            x, jnp.int32(len(x)), layer, n_heads=8, d_head=16, d_state=16,
            n_groups=1, eps=1e-5, res=0.22, top_k=3, first=0)
        return out, tail, s_n

    # step 1 brings slots 1 and 2 to where step 2 picks them up
    state = mcfg.new_recurrent_state(3)
    ctx = _layer_ctx(mcfg, 16, [(0, 4, 1, True), (4, 6, 2, True)], slots=3)
    x1 = jnp.zeros((16, 128)).at[0:4].set(xs[1][:4]).at[4:10].set(xs[2][:6])
    _, state = gh._mamba_mixer(mcfg, x1, layer, state, ctx)
    ctx = _layer_ctx(mcfg, 32, [(0, 1, 2, False), (1, 11, 0, True),
                                (12, 5, 1, False)], slots=3)
    x2 = jnp.zeros((32, 128)).at[0:1].set(xs[2][6:]).at[1:12].set(xs[0]) \
        .at[12:17].set(xs[1][4:])
    mixed, (conv, ssm) = gh._mamba_mixer(mcfg, x2, layer, state, ctx)
    got = {0: mixed[1:12], 1: mixed[12:17], 2: mixed[0:1]}
    for slot in (0, 1, 2):
        out, tail, s_n = alone(xs[slot])
        # the reference's layer goes on to the expert layer; the mixer's
        # part of it is recomputed here from its own pieces
        want = gh._mamba_mixer(
            mcfg, jnp.zeros((16, 128)).at[:len(xs[slot])].set(xs[slot]),
            layer, mcfg.new_recurrent_state(1),
            _layer_ctx(mcfg, 16, [(0, len(xs[slot]), 0, True)], slots=1))
        np.testing.assert_allclose(got[slot],
                                   want[0][:len(xs[slot])][-len(got[slot]):],
                                   atol=2e-6)
        np.testing.assert_allclose(conv[slot], tail, atol=1e-6)
        np.testing.assert_allclose(ssm[slot].reshape(s_n.shape), s_n,
                                   rtol=1e-4, atol=1e-6)


def _scan_alone(state, segs, x, b, c, delta, a_head):
    """The recurrence a token at a time, float32, one scheduled segment at
    a time (numpy; shares nothing with the kernel): ``{slot: S}`` and
    ``y`` with zero rows where no segment lies.  ``b`` / ``c`` ``[t, groups,
    d_state]``."""
    nh, (g, ds) = a_head.shape[0], b.shape[1:]
    b, c = (np.repeat(v, nh // g, 1)[:, :, None] for v in (b, c))
    y = np.zeros(x.shape, np.float32)
    out = {}
    for row0, n, slot, fresh in segs:
        s = np.zeros((nh, x.shape[1] // nh, ds), np.float32) if fresh \
            else state[slot].reshape(nh, -1, ds).copy()
        for r in range(row0, row0 + n):
            s = np.exp(delta[r] * a_head)[:, None, None] * s + (
                delta[r][:, None] * x[r].reshape(nh, -1))[..., None] * b[r]
            y[r] = (s * c[r]).sum(-1).reshape(-1)
        out[slot] = s.reshape(state.shape[1:])
    return y, out


_MIXED = [(0, 7, 3, False), (7, 1, 0, False), (8, 5, 1, False)]


@pytest.mark.parametrize("segs, groups", [
    pytest.param([(3, 1, 1, False), (4, 6, 3, False)], 1,
                 id="unnamed_slots"),
    pytest.param([(0, 1, 0, True), (1, 9, 2, True)], 1,
                 id="fresh_lane_and_fresh_units"),
    pytest.param([(0, 1, 4, False), (1, 1, 0, True), (2, 1, 2, False)], 1,
                 id="lanes_only_n_work_0"),
    pytest.param([(0, 24, 2, False)], 1, id="one_long_segment"),
    pytest.param([(2, 8, 1, False), (10, 12, 4, True)], 1, id="units_only"),
    pytest.param(_MIXED, 1, id="short_last_unit"),
    pytest.param(_MIXED, 2, id="two_groups"),
    pytest.param([], 1, id="nothing_scheduled"),
])
def test_the_slot_kernel_touches_what_the_step_schedules(segs, groups):
    """``ops/ssm_slot_kernel.py`` at 8 heads x 16 x 16 a B/C group, units of
    4 rows, 5 slots, in interpret mode: every scheduled slot and every row
    of a segment is what the recurrence gives a token at a time; a ``fresh``
    segment starts from zero whatever the slot held (NaN here); a slot no
    item names is bit for bit what it was; rows of no segment, those past
    the step's tokens among them, are zero."""
    from flashinfer_tpu.ops.ssm_slot_kernel import ssm_slot_update
    from flashinfer_tpu.serve import recurrent

    nh, dh, ds, slots, t, chunk = 8 * groups, 16, 16, 5, 32, 4
    total = sum(n for _, n, _, _ in segs)
    rng = np.random.default_rng(len(segs) + total)
    state = rng.standard_normal((slots, nh * dh, ds)).astype(np.float32)
    for _, _, slot, fresh in segs:
        if fresh:
            state[slot] = np.nan
    x = rng.standard_normal((t, nh * dh)).astype(np.float32)
    b, c = rng.standard_normal((2, t, groups, ds)).astype(np.float32)
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (t, nh))
                   ).astype(np.float32)
    a_head = -rng.uniform(1, 16, nh).astype(np.float32)
    plan, _ = recurrent.build_plan(segs, tokens=total, rung=t,
                                   max_batch=slots, chunk=chunk)
    y, new = ssm_slot_update(
        jnp.asarray(state), *(jnp.asarray(plan[k]) for k in
                              ("seg", "work", "n_work")),
        *map(jnp.asarray, (x, b.reshape(t, -1), c.reshape(t, -1), delta,
                           a_head)),
        heads=nh, d_head=dh, d_state=ds, groups=groups, chunk=chunk)
    want_y, want = _scan_alone(state, segs, x, b, c, delta, a_head)
    np.testing.assert_allclose(y, want_y, rtol=2e-5, atol=2e-5)
    held = np.zeros(t, bool)
    for row0, n, _, _ in segs:
        held[row0:row0 + n] = True
    assert not np.asarray(y)[~held].any()
    assert int(plan["n_work"]) == sum(-(-n // chunk) for _, n, _, _ in segs
                                      if n > 1)
    for slot in range(slots):
        if slot in want:
            np.testing.assert_allclose(new[slot], want[slot], rtol=2e-5,
                                       atol=2e-6)
        else:
            np.testing.assert_array_equal(new[slot], state[slot])


def test_the_slot_kernel_refuses_a_block_across_groups():
    """A grid step's heads share one row of B and C, so a block lies inside
    one group; where no whole 128-column tile fits in a group the launch
    raises and does not read another group's rows."""
    from flashinfer_tpu.ops.ssm_slot_kernel import head_block

    assert head_block(128, 64, 128, 128) == 16     # granite-4.0-h-small
    assert head_block(128, 64, 128, 16) == 16      # eight groups
    assert head_block(8, 16, 16, 8) == 8           # tiny: all the heads
    with pytest.raises(ValueError, match="inside a B/C group"):
        head_block(8, 16, 16, 4)


def test_the_plan_of_a_step():
    from flashinfer_tpu.serve import recurrent

    plan, facts = recurrent.build_plan(
        [(0, 1, 3, False), (1, 1, 0, True), (2, 9, 1, True),
         (11, 4, 2, False)], tokens=15, rung=16, max_batch=4, chunk=4)
    assert int(plan["n_tokens"]) == 15 and int(plan["n_work"]) == 4
    assert plan["seg"].tolist() == [[1, 1, 1], [2, 9, 1], [11, 4, 0],
                                    [0, 1, 0]]
    assert plan["work"][:4].tolist() == [
        [2, 4, 1, 1, 0, 1], [6, 4, 1, 0, 0, 1], [10, 1, 1, 0, 1, 1],
        [11, 4, 2, 1, 1, 0]]
    assert plan["work"].shape == (recurrent.work_units(16, 4, 4), 6)
    assert facts == {"ssm_prefill_tokens": 13, "ssm_decode_lanes": 2}
    assert recurrent.build_plan([], tokens=3, rung=4, max_batch=4,
                                chunk=0) == ({"n_tokens": 3}, {})


def test_the_familys_weights_are_the_programs_layout():
    """The benchmark makes its own weights; their names and shapes are the
    program's (``models/granite_hybrid.layer_shapes``)."""
    from flashinfer_tpu.models import granite_hybrid as gh

    mcfg = fam.model_config(M)
    for kind in ("mamba", "attention"):
        assert fam._shapes(M, kind) == gh.layer_shapes(mcfg, kind)
    tree = gh.init_granite_hybrid_params(jax.random.PRNGKey(0), mcfg)
    mine = fam.make_params(SEED, M)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), tree) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), mine)
    one = fam.layer_params(SEED, M, 2)
    assert all(bool(jnp.array_equal(one[k], mine["layers"][2][k]))
               for k in one)
