"""MiniCPM-SALA's block on ``ServingEngine`` (ISSUE 45): lightning
linear-attention layers in recurrent slots beside InfLLM-v2 block-sparse GQA
layers over K/V pages with a compressed-key cache.

Everything is compared with the benchmark's plain float32 reference
(``perfbench/families/minicpm_sala_reference.py``, which imports nothing of
the program, computes the recurrence in chunks of 256 as algebra and the
sparse layer from the full compressed-score matrix and a block mask) on the
family's seeded weights, at toy widths on the CPU.  The program runs in
float32 here, so two float32 computations in another order are compared:
LOGITS, through the ``return_logits`` hook (off in every timed run), within
1e-4 of the logits' spread.  The sound difference reads 1e-6 of it; the
program in bfloat16 reads 2e-2 (``test_bfloat16_is_told_apart``), a wrong
row, position, block or state moves a logit by its whole spread.  The
selection has no near tie on these seeds (a swapped block would read 1e-2).
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

from families import minicpm_sala as fam  # noqa: E402
from families import minicpm_sala_reference as ref  # noqa: E402
from flashinfer_tpu.models import minicpm_sala as sala  # noqa: E402
from flashinfer_tpu.ops import block_sparse_paged as bsp  # noqa: E402
from flashinfer_tpu.serve import (EngineConfig, EngineRequest,  # noqa: E402
                                  SamplingConfig, ServingEngine, recurrent)
from flashinfer_tpu.serve.block_step import StepContext  # noqa: E402

SEED = 5
PS = 4            # page = the compressed keys' stride; a block is 4 pages
with open(ROOT / "perfbench" / "configs" / "minicpm-sala.json") as f:
    M = dict(json.load(f), **fam.TINY)
M.update(head_dim=16, lightning_head_dim=16,
         sparse_config={"kernel_size": 2 * PS, "kernel_stride": PS,
                        "block_size": 4 * PS, "topk": 4, "init_blocks": 1,
                        "window_size": 8 * PS})     # 2 newest blocks forced
TOL = 1e-4   # of the logits' spread (see above)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        fam.make_params(SEED, M))


def _mcfg(dtype=jnp.float32, **over):
    kw = dict(dtype=dtype, scan_chunk=8, tile_tokens=4, chunk_blocks=2)
    kw.update(over)
    return dataclasses.replace(fam.model_config(M), **kw)


def _engine(params, mcfg=None, **over):
    kw = dict(num_pages=128, page_size=PS, max_batch=4,
              prefill_budget_tokens=21, max_seq_tokens=160, ladder=(4, 24),
              sampling=SamplingConfig(top_k=1), attention_backend="kernel",
              return_logits=True)
    kw.update(over)
    return ServingEngine(mcfg or _mcfg(), params, EngineConfig(**kw))


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


def _reference_logits(req, on_rows=lambda *a: None, prompt_len=None):
    plen = prompt_len or len(req.prompt)
    seq = (req.prompt[:plen] + req.out_tokens)[:-1]
    rows = [plen - 1 + j for j in range(len(req.out_tokens))]
    return np.asarray(fam.reference_forward(
        [seq + [0] * (-len(seq) % 32)], M, SEED, on_rows=on_rows,
        precision="float32", logit_rows=[rows])[0])


def _worst(got, want):
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want)) / np.std(want))


# ---------------------------------------------------------------------------
# (a) the engine against the reference's full forward pass
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(params):
    """One engine for the tests that serve to the end and leave it empty
    (a second engine of the same sizes traces and lowers both rungs again).
    Its prefix cache is ON: the lookups are refused and counted, which
    changes nothing else."""
    return _engine(params, enable_prefix_cache=True)


@pytest.fixture(scope="module")
def served(engine):
    """Three prompts through one engine: 131 tokens (9 blocks: a query past
    64 drops blocks; chunks of 21 split blocks, pages and the compressed
    keys' windows), 6 and 77, chunked, packed with each other's decode
    lanes, then decoded through pages and slots."""
    eng = engine
    reqs = [EngineRequest(f"r{i}", p, max_new_tokens=5)
            for i, p in enumerate(_prompts((131, 6, 77)))]
    seen, held = [], {}

    def on_step(facts):
        seen.append(facts)
        if eng.steps == 5:      # r0 mid-prompt, in flight
            r = reqs[0]
            held[r.rid] = (r.kv_len, fam.state_rows(eng, r, r.kv_len))

    got = _serve(eng, reqs, on_step)
    return eng, reqs, got, seen, held


def test_prefill_then_decode_matches_the_reference(served):
    eng, reqs, got, seen, _ = served
    for r in reqs:
        assert len(r.out_tokens) == 5
        assert _worst(got[r.rid], _reference_logits(r)) < TOL
    assert eng.pool.used_pages == 0 and eng._slots == [None] * 4
    # no GQA work unit was planned: the sparse layers attend by themselves
    assert not eng._kernel_backend and eng._geom is None
    assert all(f["plans_lowered"] == 0 for f in seen)
    assert eng.num_traces == len({f["rung"] for f in seen})


def test_what_the_family_reads_is_what_the_reference_holds(served):
    """K, V and compressed-key rows of the sparse layer and the matrix
    states of the lightning layers, for a request in flight mid-prompt: a
    compressed key is there exactly when its window's last position is
    cached (the rows after it are zero on both sides)."""
    eng, reqs, _, _, held = served
    n, rows = held["r0"]
    assert 64 < n < 131 and set(rows) == {"k", "v", "kc", "state"}
    done = (n - 2 * PS) // PS + 1
    kc = rows["kc"][1]
    assert np.all(kc[done:] == 0) and np.all(np.abs(kc[:done]).sum(-1) > 0)
    seq = reqs[0].prompt[:n]
    worst = []

    def on_rows(si, li, want):
        for kind, w in want.items():
            g, w = rows[kind][li], np.asarray(w)[:n]
            worst.append(np.max(np.abs(g - w)) / np.max(np.abs(w)))

    fam.reference_forward([seq + [0] * (-n % 32)], M, SEED, on_rows=on_rows,
                          precision="float32", logit_rows=[[n - 1]])
    assert len(worst) == 1 + 3 + 1 and max(worst) < 1e-5


def test_the_counters(served):
    eng, _, _, seen, _ = served
    sp = M["sparse_config"]
    assert sum(f["attn_pairs_selected"] for f in seen) \
        < sum(f["attn_pairs_causal"] for f in seen)
    for f in seen:
        # a tile reads at least what its rows selected, K/V rows by blocks
        assert f["attn_blocks_read"] >= f["attn_blocks_selected"] > 0
        assert f["kv_rows_read"] == f["attn_blocks_read"] * sp["block_size"]
        assert f["state_slots_used"] >= f["ssm_decode_lanes"]
        assert f["ssm_prefill_tokens"] + f["ssm_decode_lanes"] == f["tokens"]
    # every compressed key of the 131 + 6 + 77 prompt tokens and their 4
    # decoded positions each was written once
    want = sum(max((n + 4 - sp["kernel_size"]) // PS + 1, 0)
               for n in (131, 6, 77))
    assert sum(f["kc_rows_written"] for f in seen) == want
    lanes = [f for f in seen if f["prefill_tokens"] == 0]
    assert lanes and all(
        f["attn_blocks_read"] == f["attn_blocks_selected"] for f in lanes)


def test_bfloat16_is_told_apart(params):
    """The tolerance is tight enough: the same engine in bfloat16 (weights,
    activations, pages) fails it by two orders."""
    eng = _engine(jax.tree.map(lambda a: a.astype(jnp.bfloat16), params),
                  _mcfg(jnp.bfloat16))
    req = EngineRequest("b", _prompts((77,))[0], max_new_tokens=2)
    got = _serve(eng, [req])
    assert 100 * TOL < _worst(got["b"], _reference_logits(req)) < 0.2


def test_whole_and_chunked_prefill_leave_the_same_pages(params):
    """One prompt prefilled whole and in chunks of 21: the same K/V rows and
    the same compressed keys (a chunk reads its request's previous page),
    the same states, the same first token's logits."""
    prompt = _prompts((93,), seed=3)[0]
    out = []
    for budget, ladder in ((21, (4, 24)), (96, (4, 96))):
        eng = _engine(params, prefill_budget_tokens=budget, ladder=ladder)
        req = EngineRequest("w", prompt, max_new_tokens=2)
        eng.submit(req)
        while req.kv_len < len(prompt):
            eng.step()
        out.append((fam.state_rows(eng, req, len(prompt)),
                    eng.last_logits["w"]))
    (a, la), (b, lb) = out
    for kind in a:
        for li in a[kind]:
            np.testing.assert_allclose(a[kind][li], b[kind][li], atol=1e-5)
    np.testing.assert_allclose(la, lb, atol=1e-5)


# ---------------------------------------------------------------------------
# (b) the lightning unit body against the token-by-token recurrence
# ---------------------------------------------------------------------------


def _recurrence(q, k, v, lam, s):
    """``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``, one token at
    a time, float64."""
    out = []
    for qt, kt, vt in zip(q, k, v):
        s = lam[:, None, None] * s + kt[:, :, None] * vt[:, None, :]
        out.append(np.einsum("hd,hde->he", qt, s))
    return np.stack(out), s


def _lightning_inputs(mcfg, layer, x, pos):
    """(q / sqrt(d), k, v, gate) as the mixer makes them, float64."""
    from flashinfer_tpu.norm import qk_rmsnorm, rmsnorm
    from flashinfer_tpu.rope import apply_rope_pos_ids

    t, nh, d = x.shape[0], mcfg.lightning_nh, mcfg.lightning_head_dim
    h = rmsnorm(x, layer["input_norm"], mcfg.rms_eps)
    q, k, v, g = jnp.split(h @ layer["qkvg"], 4, -1)
    q, k = qk_rmsnorm(q.reshape(t, nh, d), k.reshape(t, nh, d),
                      layer["q_norm"], layer["k_norm"], mcfg.rms_eps)
    q, k = apply_rope_pos_ids(q, k, pos, rope_theta=mcfg.rope_theta)
    return (np.asarray(q, np.float64) / d ** 0.5, np.asarray(k, np.float64),
            np.asarray(v.reshape(t, nh, d), np.float64), g)


def test_lightning_units_carry_the_state_as_the_recurrence_does():
    """One lightning layer over two requests across three steps: request A's
    30-token chunk (four units of 8, the last of 6) from a FRESH slot whose
    array holds another request's leftovers, then 11 more tokens RESUMED
    from the slot beside request B's first chunk, then one lane each: the
    mixer's pre-norm output equals the token-by-token recurrence and the
    slots hold its states; an unscheduled slot is not touched."""
    mcfg = _mcfg()
    li = 0
    layer = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        fam._layer(jax.random.PRNGKey(7), fam._shapes(M, "lightning-attn")))
    nh, d, hid = mcfg.lightning_nh, mcfg.lightning_head_dim, M["hidden_size"]
    lam = np.exp(np.asarray(mcfg.log_decay(li), np.float64))
    xa = jax.random.normal(jax.random.PRNGKey(1), (42, hid), jnp.float32)
    xb = jax.random.normal(jax.random.PRNGKey(2), (10, hid), jnp.float32)
    junk = jax.random.normal(jax.random.PRNGKey(3), (4, nh, d, d))
    state = (junk.astype(jnp.float32),)

    @jax.jit
    def run(x, state, positions, tok_req, plan):
        ctx = StepContext(positions=positions, tok_req=tok_req, attend=None,
                          plan=plan, aux={})
        # the residual is taken off again: x_out = x + r W_o(gated)
        return sala._lightning_mixer(mcfg, li, x, layer, state, ctx)

    def step(x, state, positions, tok_req, segs, rung=32):
        # one rung for all three steps (one compile): the rows past the
        # step's tokens are padding no segment names
        t = len(positions)
        plan, _ = recurrent.build_plan(segs, tokens=t, rung=rung, max_batch=4,
                                       chunk=mcfg.scan_chunk)
        pad = lambda a: jnp.pad(jnp.asarray(a), ((0, rung - t),)
                                + ((0, 0),) * (jnp.ndim(a) - 1))
        y, state = run(pad(x), state, pad(jnp.asarray(positions, jnp.int32)),
                       pad(jnp.asarray(tok_req, jnp.int32)),
                       jax.tree.map(jnp.asarray, plan))
        return y[:t], state

    def want(x, pos, s):
        from flashinfer_tpu.norm import rmsnorm

        q, k, v, g = _lightning_inputs(mcfg, layer, x, jnp.asarray(pos))
        o, s = _recurrence(q, k, v, lam, s)
        o = rmsnorm(jnp.asarray(o.reshape(len(pos), -1), jnp.float32),
                    layer["o_norm"], mcfg.rms_eps)
        o = o * jax.nn.sigmoid(g)
        return np.asarray(x + mcfg.residual_scale * (o @ layer["o_proj"])), s

    zero = np.zeros((nh, d, d))
    # step 1: A's rows 0-29, slot 2, fresh
    y, state = step(xa[:30], state, range(30), [2] * 30, [(0, 30, 2, True)])
    wa, sa = want(xa[:30], range(30), zero)
    np.testing.assert_allclose(y, wa, atol=2e-5)
    np.testing.assert_allclose(state[0][2], sa, atol=2e-5)
    np.testing.assert_array_equal(state[0][0], junk[0])     # not scheduled
    # step 2: B's rows 0-8 (slot 0, fresh) before A's rows 30-40 (resumed)
    x2 = jnp.concatenate([xb[:9], xa[30:41]])
    y, state = step(x2, state, list(range(9)) + list(range(30, 41)),
                    [0] * 9 + [2] * 11, [(0, 9, 0, True), (9, 11, 2, False)])
    wb, sb = want(xb[:9], range(9), zero)
    wa, sa = want(xa[30:41], range(30, 41), sa)
    np.testing.assert_allclose(y, np.concatenate([wb, wa]), atol=2e-5)
    # step 3: a lane each
    x3 = jnp.stack([xa[41], xb[9]])
    y, state = step(x3, state, [41, 9], [2, 0],
                    [(0, 1, 2, False), (1, 1, 0, False)])
    wa, sa = want(xa[41:], [41], sa)
    wb, sb = want(xb[9:], [9], sb)
    np.testing.assert_allclose(y, np.concatenate([wa, wb]), atol=2e-5)
    np.testing.assert_allclose(state[0][2], sa, atol=2e-5)
    np.testing.assert_allclose(state[0][0], sb, atol=2e-5)
    np.testing.assert_array_equal(state[0][1], junk[1])
    # and the reference's chunked algebra is that recurrence
    q, k, v, _ = _lightning_inputs(mcfg, layer, xa, jnp.arange(42))
    o, s = ref.linear_attention(
        *(jnp.asarray(np.pad(a, ((0, 22), (0, 0), (0, 0))), jnp.float32)
          for a in (q, k, v)), jnp.arange(64) < 42,
        jnp.asarray(np.log(lam), jnp.float32),
        jnp.zeros((nh, d, d), jnp.float32))
    wo, ws = _recurrence(q, k, v, lam, zero)
    np.testing.assert_allclose(o[:42], wo, atol=1e-5)
    np.testing.assert_allclose(s, ws, atol=1e-5)


# ---------------------------------------------------------------------------
# (c) the selection
# ---------------------------------------------------------------------------

_SEL = dict(stride=4, kernel_size=8, block_size=16)


@pytest.mark.parametrize("pos, topk, init, local, want", [
    # 3 blocks, top 4: a row with at most topk blocks keeps all
    (40, 4, 1, 2, {0, 1, 2}),
    # 8 blocks, top 4: init block 0 and the 2 newest forced, 1 free pick
    (120, 4, 1, 2, {0, 6, 7, "best"}),
    # forced alone fill the selection
    (120, 3, 1, 2, {0, 6, 7}),
    # no forced block but the newest: the 3 best of the others
    (120, 4, 0, 1, {7, "best3"}),
])
def test_block_selection_forces_and_keeps(pos, topk, init, local, want):
    kq, kk = jax.random.split(jax.random.PRNGKey(pos + topk))
    q = jax.random.normal(kq, (1, 4, 16))
    kc = jax.random.normal(kk, (32, 2, 16))
    scores = bsp.compressed_block_scores(
        q, kc, jnp.asarray([pos]), init_blocks=init, local_blocks=local,
        sm_scale=0.25, **_SEL)
    mask = np.asarray(bsp.select_blocks(scores, topk))[0]      # [Hkv, 8]
    own = pos // 16
    sc = np.asarray(scores)[0]
    assert np.all(np.isneginf(sc[:, own + 1:])) and not mask[:, own + 1:].any()
    for g in range(2):
        got = set(np.flatnonzero(mask[g]))
        free = [b for b in range(own + 1) if np.isfinite(sc[g, b])]
        ranked = sorted(free, key=lambda b: -sc[g, b])
        exp = {w for w in want if isinstance(w, int)}
        if "best" in want:
            exp.add(ranked[0])
        if "best3" in want:
            exp.update(ranked[:3])
        assert got == exp
    # the reference's selection, from the same inputs
    theirs = np.asarray(ref.block_selection(
        q, kc, jnp.asarray([pos]), topk=topk, init_blocks=init,
        local_blocks=local, blocks=8, **_SEL))[0]
    np.testing.assert_array_equal(mask, theirs)


def test_block_scores_pool_the_compressed_softmax():
    """A block's score is the largest, over the compressed keys that touch
    it (``4b - 1 .. 4b + 3``) and are complete at the query's position, of
    the group's summed softmax over the complete keys."""
    kq, kk = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (1, 4, 16))
    kc = jax.random.normal(kk, (32, 2, 16))
    pos = 75                       # keys c with 4c + 7 <= 75: c <= 17
    sc = np.asarray(bsp.compressed_block_scores(
        q, kc, jnp.asarray([pos]), init_blocks=0, local_blocks=0,
        sm_scale=0.25, **_SEL))[0]
    s = np.einsum("ngd,cnd->ngc", np.asarray(q[0]).reshape(2, 2, 16),
                  np.asarray(kc)[:18]) * 0.25
    p = np.exp(s - s.max(-1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True)).sum(1)                  # [Hkv, 18]
    for b in range(5):
        cs = [c for c in range(4 * b - 1, 4 * b + 4) if 0 <= c <= 17]
        np.testing.assert_allclose(sc[:, b], p[:, cs].max(-1), rtol=1e-5)
    assert np.all(np.isneginf(sc[:, 5:]))


def test_ties_go_to_the_lower_block():
    scores = jnp.asarray([[1.0, 2.0, 2.0, 2.0, 0.5, 2.0, -jnp.inf, -jnp.inf]])
    np.testing.assert_array_equal(
        np.asarray(bsp.select_blocks(scores, 3))[0],
        [False, True, True, True, False, False, False, False])
    order = jnp.argsort(-scores, -1, stable=True)
    np.testing.assert_array_equal(np.asarray(order)[0, :3], [1, 2, 3])


def test_a_compressed_key_appears_when_its_window_is_cached(served):
    """``kc_c`` is written in the step that caches position ``4c + 7`` and
    not before (what an earlier holder of the page left stays until then):
    a request fed 21, then 21, then 1 token at a time; what is written is
    the mean of the 8 cached K rows."""
    eng = served[0]
    req = EngineRequest("k", _prompts((47,), seed=9)[0], max_new_tokens=3)
    eng.submit(req)
    li = list(eng.cfg.mixer_types).index("minicpm4")
    d = eng.cfg.head_dim

    def held():
        return np.asarray(eng.caches[li][1][jnp.asarray(req.pages)])

    prev, before, facts = None, 0, eng.step()     # admits: pages are fixed
    while req.state == "running":
        n, now = req.kv_len, held()
        done = max((n - 8) // 4 + 1, 0)
        assert facts["kc_rows_written"] == done - before
        if prev is not None:
            moved = np.any(now != prev, axis=(1, 2))
            assert moved[before:done].all() and not moved[done:].any() \
                and not moved[:before].any(), (n, moved)
        kv = np.asarray(eng.caches[li][0][jnp.asarray(req.pages)])
        k = kv[..., :d].transpose(0, 2, 1, 3).reshape(-1, kv.shape[1], d)
        for c in range(done):
            np.testing.assert_allclose(now[c], k[4 * c:4 * c + 8].mean(0),
                                       atol=1e-6)
        prev, before, facts = now, done, eng.step()
    while eng.has_work():
        eng.step()
    eng.last_logits = {}


# ---------------------------------------------------------------------------
# (d) the paged kernel against dense attention under the same block mask
# ---------------------------------------------------------------------------


def _dense(q, k, v, mask, pos, block):
    """softmax over the positions ``j <= pos`` of the blocks ``mask`` admits
    (``[Q, Hkv, NB]``), float64: ``q`` ``[Q, Hq, D]``, ``k`` / ``v`` ``[T,
    Hkv, D]``."""
    n_q, hq, d = q.shape
    hkv = k.shape[1]
    ok = np.repeat(mask, block, -1)[..., :k.shape[0]] \
        & (np.arange(k.shape[0])[None] <= np.asarray(pos)[:, None])[:, None]
    s = np.einsum("qngd,jnd->qngj", q.reshape(n_q, hkv, hq // hkv, d), k) \
        / d ** 0.5
    s = np.where(ok[:, :, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("qngj,jnd->qngd", p, v).reshape(n_q, hq, d)


@pytest.mark.parametrize("case, pos, live, tile", [
    ("a_unit_of_two_tiles", range(100, 108), 8, 4),
    ("a_padded_unit", list(range(61, 66)) + [0, 0, 0], 5, 4),
    ("a_lane", [117], 1, 1),
    ("rows_that_share_every_block", range(40, 44), 4, 4),
])
def test_the_paged_kernel_reads_its_tiles_unions(case, pos, live, tile):
    """``block_sparse_paged_attention`` (interpret mode) through a scattered
    page list, for tiles whose rows selected different blocks: the union is
    the ascending list of blocks some live row selected and nothing else, a
    row attends its own blocks of it up to its position, dead rows give
    zeros; equal to dense attention under each row's own block mask."""
    hq, hkv, d, block, per, topk = 4, 2, 16, 16, 4, 4
    pos = np.asarray(list(pos))
    n_u, t = len(pos), 128
    rng = np.random.default_rng(len(case))
    k = rng.standard_normal((t, hkv, d)).astype(np.float32)
    v = rng.standard_normal((t, hkv, d)).astype(np.float32)
    q = rng.standard_normal((n_u, hq, d)).astype(np.float32)
    pages = rng.permutation(np.arange(1, 40))[:t // PS]        # scattered
    pool = np.zeros((40, hkv, PS, 2 * d), np.float32)
    kv = np.concatenate([k, v], -1).reshape(t // PS, PS, hkv, 2 * d)
    pool[pages] = kv.transpose(0, 2, 1, 3)
    # each row: its own block, block 0 and two random others below its own
    mask = np.zeros((n_u, hkv, t // block), bool)
    for r, p in enumerate(pos):
        for g in range(hkv):
            own = p // block
            mask[r, g, rng.permutation(own + 1)[:topk - 2]] = True
            mask[r, g, [0, own]] = True
    alive = np.arange(n_u) < live
    cap = bsp.union_cap(tile, topk=topk, init_blocks=1, local_blocks=1,
                        blocks=t // block, chunk_blocks=2)
    union, count, hit = bsp.tile_unions(
        jnp.asarray(mask), jnp.asarray(alive), tile=tile, cap=cap)
    for g in range(hkv):
        for i in range(n_u // tile):
            rows = slice(i * tile, (i + 1) * tile)
            want = np.flatnonzero((mask[rows, g] & alive[rows, None]).any(0))
            c = int(count[g, i])
            np.testing.assert_array_equal(np.asarray(union[g, i, :c]), want)
            np.testing.assert_array_equal(
                np.asarray(hit[g, i, :, :c]),
                mask[rows, g][:, want] & alive[rows, None])
            assert not np.asarray(hit[g, i, :, c:]).any()
    bias = bsp.selection_bias(union, hit, jnp.asarray(pos).reshape(-1, tile),
                              block_size=block)
    qt = jnp.transpose(
        jnp.asarray(q).reshape(n_u // tile, tile, hkv, hq // hkv, d),
        (2, 0, 1, 3, 4)).reshape(hkv, n_u // tile, -1, d)
    out = bsp.block_sparse_paged_attention(
        qt, jnp.asarray(pool), jnp.asarray(pages)[None],
        jnp.zeros(count.shape, jnp.int32), union, count, bias,
        block_pages=per, chunk_blocks=2, sm_scale=d ** -0.5)
    out = np.asarray(out).reshape(hkv, n_u // tile, tile, hq // hkv, d)
    out = out.transpose(1, 2, 0, 3, 4).reshape(n_u, hq, d)
    want = _dense(q.astype(np.float64), k, v, mask, pos, block)
    np.testing.assert_allclose(out[:live], want[:live], atol=2e-6)
    assert np.all(out[live:] == 0)


# ---------------------------------------------------------------------------
# (e) preemption, and what stays refused
# ---------------------------------------------------------------------------


def test_preemption_recomputes_pages_and_states(params):
    """A preempted request folds its tokens into the prompt and re-prefills
    from a zeroed slot: pages and compressed keys are written anew, and its
    later tokens' logits are the reference's."""
    eng = _engine(params, max_batch=1, num_pages=24)
    low = EngineRequest("low", _prompts((41,))[0], max_new_tokens=6,
                        priority=5)
    eng.submit(low)
    for _ in range(5):
        eng.step()
    assert low.state == "running" and len(low.out_tokens) >= 2
    eng.last_logits = {}
    high = EngineRequest("high", _prompts((30,), seed=2)[0],
                         max_new_tokens=3, priority=0)
    got = _serve(eng, [high])
    assert low.preemptions == 1 and len(low.out_tokens) == 6
    want = _reference_logits(low, prompt_len=41)
    assert _worst(got["low"], want[-len(got["low"]):]) < TOL
    assert _worst(got["high"], _reference_logits(high)) < TOL


def test_prefix_lookups_are_refused_and_counted(served):
    """Two requests with one prompt: nothing is shared (a hit would skip
    tokens whose matrix states nobody kept), each lookup is counted, and
    both serve the reference's logits."""
    eng = served[0]
    assert eng.config.enable_prefix_cache
    before = eng.prefix_lookups_refused
    prompt = _prompts((37,), seed=4)[0]
    reqs = [EngineRequest(f"p{i}", list(prompt), max_new_tokens=2)
            for i in range(2)]
    eng.last_logits = {}
    got = _serve(eng, reqs)
    assert eng.prefix_lookups_refused == before + 2
    assert all(r.hit_tokens == 0 for r in reqs)
    want = _reference_logits(reqs[0])
    for r in reqs:
        assert _worst(got[r.rid], want) < TOL


@pytest.mark.parametrize("what, over", [
    ("host spill", dict(kv_offload="host", spill_policy="spill")),
    ("migration", dict(role="prefill")),
    ("int8 pages", dict(kv_dtype=jnp.int8)),
])
def test_spill_migration_and_int8_pages_are_refused_by_name(params, what,
                                                            over):
    with pytest.raises(NotImplementedError, match="recurrent"):
        _engine(params, **over)


def test_a_page_that_is_not_the_stride_is_refused(params):
    with pytest.raises(ValueError, match="stride"):
        _engine(params, page_size=8)
