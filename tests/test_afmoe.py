"""Trinity-Mini's block (``afmoe``) on ``ServingEngine`` (ISSUE 37): window
layers in a pool sized by the window beside the full layers' pages, rotation
on the window layers only, gated QK-normed GQA, sandwich norms, and a
sigmoid-routed expert layer that holds a share.

Everything is compared with the benchmark's plain float32 reference
(``perfbench/families/afmoe_reference.py``, which imports nothing of the
program and attends a block of query rows at a time under a mask) on the
family's seeded weights, at toy widths on the CPU.  The program runs in
float32 here, so two float32 computations in another order are compared:
1e-6 of the logits' spread, but for a router choice at a near tie, which that
noise can flip; the tolerance is 1e-3, and a wrong row, position, window edge,
rotation or expert moves a logit by 0.1 to 1 (the controls at the end show
it).  LOGITS are compared, through the ``return_logits`` hook (off in every
timed run).
"""

import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT, ROOT / "perfbench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from families import afmoe as fam  # noqa: E402
from families import afmoe_reference as ref  # noqa: E402
from flashinfer_tpu.models import afmoe  # noqa: E402
from flashinfer_tpu.serve import (EngineConfig, EngineRequest,  # noqa: E402
                                  SamplingConfig, ServingEngine)
from flashinfer_tpu.serve.block_step import StepContext  # noqa: E402
from flashinfer_tpu.serve.window_pool import pages_cap  # noqa: E402

SEED = 7
with open(ROOT / "perfbench" / "configs" / "trinity-mini.json") as f:
    M = dict(json.load(f), **fam.TINY)
M["sliding_window"] = 12     # prompts below are several times as long
TOL = 1e-3   # of the logits' spread (see above)
PAGE, BUDGET = 8, 12
CAP = pages_cap(M["sliding_window"], BUDGET, PAGE)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        fam.make_params(SEED, M))


def _mcfg(**over):
    return dataclasses.replace(fam.model_config(M), dtype=jnp.float32, **over)


def _engine(params, **over):
    kw = dict(num_pages=64, page_size=PAGE, max_batch=4,
              prefill_budget_tokens=BUDGET, max_seq_tokens=64, ladder=(4, 16),
              sampling=SamplingConfig(top_k=1), return_logits=True,
              # the kernel tier (interpreted here, 12 s a rung to compile)
              # where a test is about its plans; the scheduler's and the
              # pools' tests run the reference tier
              attention_backend="reference")
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


def _reference_logits(req, on_rows=lambda *a: None, prompt_len=None,
                      precision="float32", m=M):
    plen = prompt_len or len(req.prompt)
    seq = (req.prompt[:plen] + req.out_tokens)[:-1]
    rows = [plen - 1 + j for j in range(len(req.out_tokens))]
    return np.asarray(fam.reference_forward(
        [seq + [0] * (-len(seq) % 32)], m, SEED, on_rows=on_rows,
        precision=precision, logit_rows=[rows])[0])


def _close(got, want, tol=TOL):
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * np.std(want), (
        np.max(np.abs(got - want)), np.std(want))


# ---------------------------------------------------------------------------
# (a) the engine against the reference's full forward pass
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["kernel", "reference"])
def test_prefill_then_decode_matches_the_reference(params, backend):
    """Prompts several times the window and longer than the prefill budget,
    chunked, packed with each other's decode lanes, then decoded through
    both pools: the window layers attend the last 12 positions out of pages
    counted from the first one still held, rotated; the full layers attend
    everything, unrotated; every served token's logits are the reference's at
    that position.  What the family reads out of the engine for a request in
    flight is what the reference holds, with zeros where pages are
    released."""
    eng = _engine(params, attention_backend=backend)
    reqs = [EngineRequest(f"r{i}", p, max_new_tokens=5)
            for i, p in enumerate(_prompts((40, 9, 23)))]
    seen, held = [], {}

    def on_step(facts):
        seen.append(facts)
        # never more than the cap a request, before or after the release
        assert all(len(r.wpages) <= CAP for r in reqs)
        if eng.steps == 5:      # r0 past the window, in flight
            r = reqs[0]
            held[r.rid] = (r.kv_len, r.wfirst,
                           fam.state_rows(eng, r, r.kv_len))

    got = _serve(eng, reqs, on_step)
    for r in reqs:
        assert len(r.out_tokens) == 5
        _close(got[r.rid], _reference_logits(r))
    n, wfirst, rows = held["r0"]
    assert n > 3 * M["sliding_window"] and wfirst > 0
    assert set(rows) == {"k", "v"} and rows["k"].shape == (4, n, 2 * 32)
    worst, zeroed = [], []

    def on_rows(si, li, want):
        for kind, w in want.items():
            w = np.asarray(w)[:n]
            worst.append(np.max(np.abs(rows[kind][li] - w)))
            zeroed.append(int((np.abs(w).sum(-1) == 0).sum()))

    _reference_logits(reqs[0], on_rows)
    assert len(worst) == 8 and max(worst) < 1e-4
    # the three window layers hold zeros below page_floor(n - window), the
    # full layer (the fourth) none
    gone = (n - M["sliding_window"]) // PAGE * PAGE
    assert zeroed == [gone] * 6 + [0] * 2 and gone == wfirst * PAGE
    # the counters: windows cut pairs, pages are released as contexts grow,
    # the window pool's share peaks under 100, both plans are lowered
    assert sum(f["attn_pairs_window"] for f in seen) \
        < 0.6 * sum(f["attn_pairs_causal"] for f in seen)
    assert all(f["attn_pairs_full"] == f["attn_pairs_causal"] for f in seen)
    assert sum(f["window_pages_released"] for f in seen) \
        == eng._wpages.released_total > 0
    assert 0 < max(f["window_pages_share"] for f in seen) <= 100
    assert all(f["window_pages_pool"] == 4 * CAP for f in seen)
    assert all(0 < f["moe_routes_local"] < f["moe_routes"] for f in seen)
    assert all(f["plans_lowered"] == (backend == "kernel") for f in seen)
    # both pools are empty after the last request ended
    assert eng.pool.used_pages == 0 and eng._slots == [None] * 4
    assert eng._wpages.pool.used_pages == 0 and eng._wpages.reserved == 0
    assert eng.num_traces == len({f["rung"] for f in seen})


def test_preemption_recomputes_both_pools(params):
    """A preempted request folds its tokens into the prompt and re-prefills:
    its pages of BOTH pools are freed and written anew, and its later tokens'
    logits are the reference's."""
    eng = _engine(params, max_batch=1, num_pages=10)
    low = EngineRequest("low", _prompts((30,))[0], max_new_tokens=6,
                        priority=5)
    eng.submit(low)
    for _ in range(5):
        eng.step()
    assert low.state == "running" and len(low.out_tokens) >= 2
    assert low.wfirst > 0 and low.wpages
    eng.last_logits = {}
    high = EngineRequest("high", _prompts((14,), seed=2)[0],
                         max_new_tokens=3, priority=0)
    eng.submit(high)
    eng.step()
    assert low.preemptions == 1 and not low.wpages and low.wfirst == 0
    assert eng._wpages.reserved == high.wreserved
    assert eng._wpages.pool.used_pages == len(high.wpages)
    got = _serve(eng, [])
    assert len(low.out_tokens) == 6
    want = _reference_logits(low, prompt_len=30)
    _close(got["low"], want[-len(got["low"]):])
    assert eng.pool.used_pages == 0 and eng._wpages.pool.used_pages == 0
    assert eng._wpages.reserved == 0


def test_a_window_that_never_binds_is_full_attention(params):
    """With the window as long as the context nothing is released, and a
    window layer differs from a full one only by its rotation."""
    wide = dict(M, sliding_window=64)
    eng = ServingEngine(
        dataclasses.replace(fam.model_config(wide), dtype=jnp.float32),
        params, EngineConfig(
            num_pages=32, page_size=PAGE, max_batch=2,
            prefill_budget_tokens=BUDGET, max_seq_tokens=64, ladder=(4, 16),
            sampling=SamplingConfig(top_k=1), attention_backend="kernel",
            return_logits=True))
    r = EngineRequest("r", _prompts((33,))[0], max_new_tokens=4)
    got = _serve(eng, [r])
    _close(got["r"], _reference_logits(r, m=wide))
    assert eng._wpages.released_total == 0 and eng._wpages.cap == 64 // PAGE


# ---------------------------------------------------------------------------
# (b) the controls: what the tolerance would catch
# ---------------------------------------------------------------------------


def test_window_off_and_rotation_moved_are_far_outside_the_tolerance(params):
    eng = _engine(params)
    r = EngineRequest("r", _prompts((40,))[0], max_new_tokens=4)
    got = _serve(eng, [r])["r"]
    want = _reference_logits(r)
    _close(got, want)
    spread = np.std(want)
    # the sliding layers attend the whole context
    off = _reference_logits(r, precision="float32-nowindow")
    assert np.max(np.abs(off - want)) > 100 * TOL * spread
    # the full layer rotated too
    moved = dict(M, layer_types=["sliding_attention"] * 4,
                 sliding_window=64)
    rot = _reference_logits(r, m=moved)
    assert np.max(np.abs(rot - off)) > 100 * TOL * spread


# ---------------------------------------------------------------------------
# (c) the share
# ---------------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of 4 shares of 2 experts each, plus the shared expert
    once, are the uncut reference's layer.  The layer's post norm is not
    linear, so the parts are added before it: a share's routed part comes
    from ``held_experts`` under the model's routing, exactly as
    ``_expert_layer`` calls them, and is tied to the layer's output first."""
    from flashinfer_tpu.fused_moe.routing import route_deepseek_v3
    from flashinfer_tpu.models.expert_share import held_experts

    whole = dict(M, num_experts=8)
    layer = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        fam._layer(jax.random.PRNGKey(11), fam._shapes(whole, False)))
    t = 16
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(12), (t, 128), jnp.float32)
    kw = dict(top_k=3, scale=M["route_scale"], eps=1e-5)
    ctx = StepContext(positions=None, tok_req=None, attend=None,
                      plan={"n_tokens": jnp.int32(t)}, aux={})
    u = ref.rmsnorm(x, layer["pre_mlp_norm"], 1e-5)
    shared = ref.swiglu(u, layer["shared_gate_up"], layer["shared_down"],
                        "float32")
    weights, ids = route_deepseek_v3(
        u @ layer["router"], layer["e_bias"], 3, 1, 1, M["route_scale"])
    total, rows = shared, []
    for first in (0, 2, 4, 6):
        mcfg = _mcfg(local_experts=(first, 2))
        share = dict(layer, w_gate_up=layer["w_gate_up"][first:first + 2],
                     w_down=layer["w_down"][first:first + 2])
        out, n_rows = afmoe._expert_layer(mcfg, x, share, ctx)
        rows.append(int(n_rows.sum()))
        # a share alone is what the reference gives for the same share
        np.testing.assert_allclose(
            out, ref.expert_ffn(x, share, first=first, **kw)[0], atol=1e-5)
        routed, _ = held_experts(u, weights, ids, share["w_gate_up"],
                                 share["w_down"], first=first,
                                 n_tokens=jnp.int32(t))
        np.testing.assert_allclose(
            out, x + ref.rmsnorm(routed + shared, layer["post_mlp_norm"],
                                 1e-5), atol=1e-5)
        total = total + routed
    # every token's 3 routes land in exactly one share
    assert sum(rows) == 3 * t and min(rows) > 0
    want, _ = ref.expert_ffn(x, layer, first=0, **kw)
    np.testing.assert_allclose(
        x + ref.rmsnorm(total, layer["post_mlp_norm"], 1e-5), want,
        atol=2e-5)


# ---------------------------------------------------------------------------
# (d) what a model with window layers refuses, by name
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
    (dict(kv_offload="host", spill_policy="spill"), "kv_offload='host'"),
    (dict(role="decode"), "role='decode'"),
    (dict(kv_dtype=jnp.int8), "kv_dtype=int8"),
])
def test_spill_migration_and_int8_pages_raise_by_name(params, over, what):
    with pytest.raises(NotImplementedError, match="window layers") as e:
        _engine(params, **over)
    assert what in str(e.value)


def test_offload_and_adoption_raise_by_name(params):
    from flashinfer_tpu.serve import kv_tier

    eng = _engine(params)
    r = EngineRequest("r", _prompts((9,))[0], max_new_tokens=2)
    eng.submit(r)
    eng.step()
    for call in (lambda: eng.offload_idle("r"),
                 lambda: eng.adopt_migrated(r, None),
                 lambda: kv_tier.spill_request(eng, r)):
        with pytest.raises(NotImplementedError, match="window layers"):
            call()


def test_the_window_pool_refuses_what_it_cannot_hold(params):
    """Admission counts both pools: with the window pool's pages all
    promised, a request waits as it does for pages of the full pool, and is
    admitted once a request ends."""
    eng = _engine(params, max_batch=2)
    first = EngineRequest("first", _prompts((10,))[0], max_new_tokens=3)
    eng.submit(first)
    eng.step()
    spare = eng._wpages.usable - eng._wpages.reserved
    eng._wpages.reserved += spare - 1            # room for one page
    r = EngineRequest("r", _prompts((20,))[0], max_new_tokens=2)
    eng.submit(r)
    eng.step()
    assert r.state == "waiting" and not r.pages and r.wreserved == 0
    eng._wpages.reserved -= spare - 1
    _serve(eng, [])
    assert len(r.out_tokens) == 2 and eng._wpages.reserved == 0


# ---------------------------------------------------------------------------
# (e) the family and the program agree on the layout
# ---------------------------------------------------------------------------


def test_the_familys_weights_are_the_programs_layout():
    mcfg = fam.model_config(M)
    for dense in (True, False):
        assert fam._shapes(M, dense) == afmoe.layer_shapes(mcfg, dense)
    assert mcfg.layer_kinds() == ("window",) * 3 + ("attention",)
    assert mcfg.attention_rope == {"window": True, "attention": False}
    assert mcfg.attention_window == 12 and mcfg.num_dense_layers == 1
    full = fam.model_config(json.load(open(
        ROOT / "perfbench" / "configs" / "trinity-mini.json")))
    assert full == dataclasses.replace(afmoe.AfmoeConfig(),
                                       local_experts=(0, 16))
    assert full.layer_kinds().count("window") == 24
