"""GLM-5's block on ``ServingEngine`` (ISSUE 35): latent pages with the
indexer's key pages beside them, exact top-k selection per query token,
sparse absorbed MLA, and a sigmoid-routed expert layer that holds a share.

Everything is compared with the benchmark's plain float32 reference
(``perfbench/families/glm_dsa_reference.py``, which imports nothing of the
program and runs the EXPANDED attention over a full score matrix and a mask)
on the family's seeded weights, at toy widths on the CPU.  The program runs
in float32 here, so two float32 computations in another order are compared:
1e-6 of the logits' spread, but for a selection or a router choice at a near
tie, which that noise can flip; the tolerance is 1e-3, and a wrong row,
position, selected set or expert moves a logit by 1.  LOGITS are compared,
through the ``return_logits`` hook (off in every timed run).
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

from families import glm_dsa as fam  # noqa: E402
from families import glm_dsa_reference as ref  # noqa: E402
from flashinfer_tpu.models import glm_dsa  # noqa: E402
from flashinfer_tpu.serve import (EngineConfig, EngineRequest,  # noqa: E402
                                  SamplingConfig, ServingEngine)
from flashinfer_tpu.serve.block_step import StepContext  # noqa: E402

SEED = 5
with open(ROOT / "perfbench" / "configs" / "glm-5.json") as f:
    M = dict(json.load(f), **fam.TINY)
M["index_topk"] = 12     # prompts below are longer, so selection drops rows
TOL = 1e-3   # of the logits' spread (see above)


@pytest.fixture(scope="module")
def params():
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        fam.make_params(SEED, M))


def _mcfg(**over):
    return dataclasses.replace(fam.model_config(M), dtype=jnp.float32,
                               scan_chunk=4, **over)


def _engine(params, **over):
    kw = dict(num_pages=64, page_size=8, max_batch=4,
              prefill_budget_tokens=12, max_seq_tokens=64, ladder=(4, 16),
              sampling=SamplingConfig(top_k=1), attention_backend="kernel",
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


def _reference_logits(req, on_rows=lambda *a: None, prompt_len=None):
    plen = prompt_len or len(req.prompt)
    seq = (req.prompt[:plen] + req.out_tokens)[:-1]
    rows = [plen - 1 + j for j in range(len(req.out_tokens))]
    return np.asarray(fam.reference_forward(
        [seq + [0] * (-len(seq) % 32)], M, SEED, on_rows=on_rows,
        precision="float32", logit_rows=[rows])[0])


def _close(got, want, tol=TOL):
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * np.std(want), (
        np.max(np.abs(got - want)), np.std(want))


# ---------------------------------------------------------------------------
# (a) the engine against the reference's full forward pass
# ---------------------------------------------------------------------------


def test_prefill_then_decode_matches_the_reference(params):
    """Prompts longer than the prefill budget and than ``index_topk``,
    chunked, packed with each other's decode lanes, then decoded through the
    cache: both arrays of a layer carry every position across chunks and
    steps, each query token selects its own rows, and every served token's
    logits are the reference's at that position.  What the family reads out
    of the engine for a request in flight is what the reference holds."""
    eng = _engine(params)
    reqs = [EngineRequest(f"r{i}", p, max_new_tokens=5)
            for i, p in enumerate(_prompts((31, 6, 19)))]
    seen, held = [], {}

    def on_step(facts):
        seen.append(facts)
        if eng.steps == 4:      # r0 mid-generation or mid-prompt, in flight
            r = reqs[0]
            held[r.rid] = (r.kv_len, fam.state_rows(eng, r, r.kv_len))

    got = _serve(eng, reqs, on_step)
    for r in reqs:
        assert len(r.out_tokens) == 5
        _close(got[r.rid], _reference_logits(r))
    n, rows = held["r0"]
    assert n > M["index_topk"] and set(rows) == {"ckv", "kpe", "kidx"}
    worst = []

    def on_rows(si, li, want):
        for kind, w in want.items():
            worst.append(np.max(np.abs(rows[kind][li] - np.asarray(w)[:n])))

    _reference_logits(reqs[0], on_rows)
    assert len(worst) == 6 and max(worst) < 1e-4
    # the counters: selection dropped rows, every page written is counted,
    # a decode-only step scored its lanes' whole contexts
    assert sum(f["attn_pairs_selected"] for f in seen) \
        < sum(f["attn_pairs_causal"] for f in seen)
    assert all(f["latent_pages_written"] == f["kv_pages_written"]
               for f in seen)
    last = [f for f in seen if f["prefill_tokens"] == 0][0]
    assert last["index_rows_scored"] == last["attn_pairs_causal"]
    assert last["latent_rows_read"] <= last["tokens"] * M["index_topk"]
    assert all(0 < f["moe_routes_local"] < f["moe_routes"] for f in seen)
    assert eng.pool.used_pages == 0 and eng._slots == [None] * 4
    # no GQA work unit was planned for a model without an attention layer
    assert not eng._kernel_backend and eng._geom is None
    assert all(f["plans_lowered"] == 0 for f in seen)
    assert eng.num_traces == len({f["rung"] for f in seen})


def test_preemption_recomputes_both_arrays(params):
    """A preempted request folds its tokens into the prompt and re-prefills:
    its pages are freed and written anew, and its later tokens' logits are
    the reference's."""
    eng = _engine(params, max_batch=1, num_pages=8)
    low = EngineRequest("low", _prompts((18,))[0], max_new_tokens=6,
                        priority=5)
    eng.submit(low)
    for _ in range(4):
        eng.step()
    assert low.state == "running" and len(low.out_tokens) >= 2
    eng.last_logits = {}
    high = EngineRequest("high", _prompts((14,), seed=2)[0],
                         max_new_tokens=3, priority=0)
    got = _serve(eng, [high])
    assert low.preemptions == 1 and len(low.out_tokens) == 6
    want = _reference_logits(low, prompt_len=18)
    _close(got["low"], want[-len(got["low"]):])
    _close(got["high"], _reference_logits(high))


# ---------------------------------------------------------------------------
# (b) selection, (c) absorbed against expanded, on one layer
# ---------------------------------------------------------------------------


def _attend_layer(mcfg, layer, pages=4, page_size=8):
    """``(x, cache, positions, plan) -> (x_out, cache)``: one layer's
    attention for rows of slot 0 at ``positions``, jitted (one compile a row
    count), with the appends done by plain indexing."""
    table = jnp.zeros((2, pages), jnp.int32).at[0].set(
        jnp.arange(1, pages + 1))

    @jax.jit
    def run(x, cache, positions, plan):
        def append(pool, rows):
            flat = pool.reshape(-1, pool.shape[-1])
            at = table[0][positions // page_size] * page_size \
                + positions % page_size
            return flat.at[at].set(rows.astype(pool.dtype)).reshape(
                pool.shape)

        ctx = StepContext(
            positions=positions, tok_req=jnp.zeros_like(positions),
            attend=None, plan=plan, aux={}, page_table=table,
            page_size=page_size, append=append)
        return glm_dsa._latent_attention(mcfg, x, layer, cache, ctx)

    def call(x, cache, positions, segs):
        from flashinfer_tpu.serve import recurrent

        t = len(positions)
        plan, _ = recurrent.build_plan(
            [(r0, n, 0, False) for r0, n in segs], tokens=t, rung=t,
            max_batch=2, chunk=mcfg.scan_chunk)
        return run(x, cache, jnp.asarray(positions, jnp.int32),
                   jax.tree.map(jnp.asarray, plan))

    return call


def _attention_kw():
    return dict(
        heads=M["num_attention_heads"], nope=M["qk_nope_head_dim"],
        rope_dim=M["qk_rope_head_dim"], ckv=M["kv_lora_rank"],
        v_dim=M["v_head_dim"], idx_heads=M["index_n_heads"],
        idx_dim=M["index_head_dim"], topk=M["index_topk"],
        theta=float(M["rope_parameters"]["rope_theta"]),
        eps=M["rms_norm_eps"], idx_eps=M["index_norm_eps"])


def test_a_chunk_selects_and_attends_as_its_tokens_do_one_at_a_time():
    """One layer's attention over 32 positions of one request, (i) as one
    segment cut into units, (ii) a first chunk and then every further token
    as a lane of its own: the absorbed program equals the expanded
    reference in both, so a chunk's rows select the sets the same tokens
    select one at a time, and those are the reference's sets (inputs
    without near ties: a swapped row would move the output by far more
    than the tolerance)."""
    mcfg = _mcfg()
    layer = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        fam._layer(jax.random.PRNGKey(3), fam._shapes(M, "dense")))
    # index weights scaled up: index scores spread over ~1
    layer = dict(layer, idx_wq=8 * layer["idx_wq"], idx_wp=8 * layer["idx_wp"])
    t = 32
    x = jax.random.normal(jax.random.PRNGKey(4), (t, 128), jnp.float32)
    want, lat, kidx, close = ref.attention(x, jnp.int32(t), layer,
                                           **_attention_kw())
    # rows past index_topk drop positions; a row counts its k-th score and
    # those exactly equal to it (all four index heads negative: a score of 0,
    # where both sides give the lower position the place)
    c = np.asarray(close)
    assert np.all(c[:M["index_topk"]] == 0) and c[M["index_topk"]:].min() >= 1
    cache = mcfg.new_latent_pages(6, 8)
    cache = tuple(c.astype(jnp.float32) for c in cache)

    attend = _attend_layer(mcfg, layer)
    whole, held = attend(x, cache, range(t), [(0, t)])
    np.testing.assert_allclose(whole, want, atol=2e-5)
    np.testing.assert_allclose(held[0][1:5].reshape(t, -1), lat, atol=1e-5)
    np.testing.assert_allclose(held[1][1:5].reshape(t, -1), kidx, atol=1e-5)

    first, c = attend(x[:24], cache, range(24), [(0, 24)])
    rest = []
    for p in range(24, t):
        y, c = attend(x[p:p + 1], c, [p], [(0, 1)])
        rest.append(y)
    np.testing.assert_allclose(jnp.concatenate([first] + rest), want,
                               atol=2e-5)


def test_selection_is_the_references_mask():
    """``select_topk`` over ``index_scores``: the selected positions of each
    query row are the k highest of its causal scores, ties (exact zeros,
    with four index heads) to the lower position, and a row with no more
    than k cached positions keeps them all."""
    from flashinfer_tpu.ops.sparse_mla import index_scores, select_topk

    k, t = 6, 24
    kq, kk, kw = jax.random.split(jax.random.PRNGKey(0), 3)
    q_i = jax.random.normal(kq, (t, 4, 16))
    k_i = jax.random.normal(kk, (t, 16))
    w_i = jax.random.normal(kw, (t, 4))
    pos = jnp.arange(t)
    scores = index_scores(q_i, w_i, k_i, pos)
    idx, valid = select_topk(scores, k)
    dense = np.asarray(jnp.einsum(
        "qjs,qj->qs", jax.nn.relu(jnp.einsum("qjd,sd->qjs", q_i, k_i)), w_i))
    for row in range(t):
        causal = dense[row, :row + 1]
        want = set(np.lexsort((np.arange(row + 1), -causal))[:k])
        got = set(np.asarray(idx[row])[np.asarray(valid[row])])
        assert got == want and len(got) == min(k, row + 1)


def _unit_scores(pos, width, ties, seed):
    """Masked index scores ``[Q, width]`` of query rows at ``pos``; with
    ``ties`` on a grid of halves, so many are exactly equal in float32."""
    sc = jax.random.normal(jax.random.PRNGKey(seed), (len(pos), width))
    if ties:
        sc = jnp.round(2 * sc) / 2
    return jnp.where(jnp.arange(width) <= jnp.asarray(pos)[:, None], sc,
                     -jnp.inf)


@pytest.mark.parametrize("case, pos, live, ties", [
    ("all_below_topk", range(0, 20), 20, False),
    ("context_is_topk", range(4, 24), 20, False),
    ("past_topk_two_blocks", range(600, 620), 20, False),
    ("past_topk_all_blocks", range(1280, 1300), 20, False),
    ("padded_unit", list(range(700, 705)) + list(range(15)), 5, False),
    ("ties_at_the_kth", range(900, 920), 20, True),
])
def test_a_unit_streams_what_it_would_gather(case, pos, live, ties):
    """The streamed form (``selection_mask`` + the ``sparse_mla_stream``
    kernel, interpret mode) equals ``sparse_mla_attend`` over ``win_l[idx]``
    for a unit of 20 rows (two query tiles, the second padded) against a
    window of 1,300 positions (three blocks, those past the unit's last
    position skipped): the same softmax over the same rows, a re-ordered
    float32 sum apart.  The mask holds exactly the list's positions: all
    causal ones while there are no more than ``k``, else exactly ``k``, and
    among scores exactly equal to the ``k``-th the lower positions."""
    from flashinfer_tpu.ops.sparse_mla import (
        select_topk, selection_mask, sparse_mla_attend, sparse_mla_stream)

    k, heads, width, d, d_v = 24, 4, 1300, 128, 32
    pos = list(pos)
    kq, kwin = jax.random.split(jax.random.PRNGKey(len(case)))
    q = jax.random.normal(kq, (len(pos), heads, d))
    win = jax.random.normal(kwin, (width, d))
    scores = _unit_scores(pos, width, ties, seed=live)
    idx, valid = select_topk(scores, k)
    mask = np.asarray(selection_mask(scores, idx, valid))
    sc = np.asarray(scores)
    tied = 0
    for row, p in enumerate(pos):
        want = np.lexsort((np.arange(p + 1), -sc[row, :p + 1]))[:k]
        assert set(np.nonzero(mask[row])[0]) == set(want), (case, row)
        assert mask[row].sum() == min(k, p + 1)
        kth = sc[row, want[-1]]
        tied += int((sc[row, :p + 1] == kth).sum() > (sc[row, want] == kth).sum())
    assert (tied > 0) == ties     # some tie at the k-th score was cut
    kw = dict(d_v=d_v, sm_scale=0.2)
    want = sparse_mla_attend(q, win[idx], valid, **kw)
    got = sparse_mla_stream(q, win, jnp.asarray(mask), pos[live - 1], **kw)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got[:live], want[:live], atol=5e-6)


_SEARCH_K = 64
_SEARCH_CASES = {   # name: (values, unmasked positions of each of 16 rows, k)
    "random": ("normal", range(200, 216), _SEARCH_K),
    "ties_straddle_the_kth": ("halves", range(300, 316), _SEARCH_K),
    "only_zeros_of_both_signs": ("zeros", [416] * 16, _SEARCH_K),
    "zeros_of_both_signs_at_the_kth": ("zeros_between", [416] * 16, _SEARCH_K),
    "fewer_than_k": ("normal", range(1, 17), _SEARCH_K),
    "exactly_k": ("halves", [_SEARCH_K] * 16, _SEARCH_K),
    "k_plus_one": ("halves", [_SEARCH_K + 1] * 16, _SEARCH_K),
    "some_rows_all_masked": ("normal", [0, 0, 100, 0] * 4, _SEARCH_K),
    "k_over_the_width": ("halves", range(400, 416), 2048),
}


def _search_scores(values, unmasked, width):
    """Float32 ``[rows, width]`` of the named kind, ``-inf`` from each row's
    ``unmasked`` count on: ``halves`` lie on a grid of halves (exact ties),
    ``zeros`` are ``0.0`` / ``-0.0`` at random, ``zeros_between`` holds ~40
    ones a row, then such zeros where the ``k``-th falls, then minus ones."""
    shape = (len(unmasked), width)
    k_normal, k_signs = jax.random.split(jax.random.PRNGKey(49))
    normal = jax.random.normal(k_normal, shape)
    zeros = jnp.where(jax.random.bernoulli(k_signs, 0.5, shape), 0.0, -0.0)
    sc = {"normal": normal, "halves": jnp.round(2 * normal) / 2,
          "zeros": zeros,
          "zeros_between": jnp.where(normal > 1.3, 1.0, jnp.where(
              normal < -1.3, -1.0, zeros))}[values]
    return jnp.where(jnp.arange(width) < jnp.asarray(unmasked)[:, None], sc,
                     -jnp.inf)


@pytest.mark.parametrize("width", [416, 384])   # 4,160 scaled down; whole tiles
@pytest.mark.parametrize("values, unmasked, k", _SEARCH_CASES.values(),
                         ids=list(_SEARCH_CASES))
def test_a_units_search_selects_what_the_sort_would(values, unmasked, k,
                                                    width):
    """A unit's mask, from the ``k``-th score found by search
    (``kth_score``, the bisection kernel in interpret mode) under the one
    rule, is bit for bit the mask from ``select_topk``'s sorted list: exact
    float32 ties at the ``k``-th score cut at the same position, ``0.0`` and
    ``-0.0`` one class, every unmasked position kept while a row has no more
    than ``k``, nothing of a row that has none (a unit's padding); at a
    width of 3.25 lane tiles, which the search pads, and at one of 3."""
    from flashinfer_tpu.ops.sparse_mla import (
        kth_score, mask_from_kth, select_topk, selection_mask)

    scores = _search_scores(values, unmasked, width)
    want = np.asarray(selection_mask(scores, *select_topk(scores, k)))
    got = np.asarray(mask_from_kth(scores, kth_score(scores, k), k))
    np.testing.assert_array_equal(got, want)
    unmasked = np.asarray(scores > -jnp.inf).sum(-1)
    np.testing.assert_array_equal(got.sum(-1), np.minimum(unmasked, k))
    assert not got[np.asarray(scores) == -np.inf].any()


def test_units_stream_through_the_engine(params, monkeypatch):
    """``test_prefill_then_decode_matches_the_reference`` again with windows
    of several blocks (a block cut to 16 positions here, so a 64-position
    window is four and a unit skips those past its last row): prompts
    chunked into units that stream, packed with decode lanes that gather,
    serve the reference's logits; the two facts count the units."""
    from flashinfer_tpu.ops import sparse_mla

    monkeypatch.setattr(sparse_mla, "STREAM_BLOCK", 16)
    eng = _engine(params)
    reqs = [EngineRequest(f"r{i}", p, max_new_tokens=4)
            for i, p in enumerate(_prompts((45, 9, 27), seed=3))]
    seen = []
    got = _serve(eng, reqs, seen.append)
    for r in reqs:
        assert len(r.out_tokens) == 4
        _close(got[r.rid], _reference_logits(r))
    units = sum(f["attn_units"] for f in seen)
    # 45 + 9 + 27 prompt tokens in units of at most 4 rows: at least 21
    assert units >= 21
    assert sum(f["attn_units_streamed"] for f in seen) == units
    lanes_only = [f for f in seen if f["prefill_tokens"] == 0]
    assert lanes_only and all(f["attn_units"] == 0 for f in lanes_only)


def test_run_sparse_takes_the_models_scale():
    """``BatchMLAPagedAttentionWrapper.run_sparse``: its default
    ``sm_scale`` is a placeholder, ``1 / sqrt(d_ckv + d_kpe)``, which is no
    model's; with the model's ``attention_scale`` it is the engine's
    function (``ops/sparse_mla.py``) over the same rows."""
    from flashinfer_tpu.mla import BatchMLAPagedAttentionWrapper
    from flashinfer_tpu.ops.sparse_mla import sparse_mla_attend

    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q_nope = jax.random.normal(ks[0], (2, 4, 32))
    q_pe = jax.random.normal(ks[1], (2, 4, 16))
    ckv = jax.random.normal(ks[2], (5, 8, 32))
    kpe = jax.random.normal(ks[3], (5, 8, 16))
    rows = jnp.asarray([[3, 17, 9, -1], [30, 2, 11, 25]], jnp.int32)
    w = BatchMLAPagedAttentionWrapper()
    scale = _mcfg().attention_scale
    assert scale == 1.0 / 32 ** 0.5
    got = w.run_sparse(q_nope, q_pe, ckv, kpe, rows, sm_scale=scale)
    r = jnp.maximum(rows, 0)
    gathered = jnp.concatenate(
        [ckv.reshape(-1, 32)[r], kpe.reshape(-1, 16)[r]], -1)
    want = sparse_mla_attend(jnp.concatenate([q_nope, q_pe], -1), gathered,
                             rows >= 0, d_v=32, sm_scale=scale)
    np.testing.assert_allclose(got, want, atol=1e-6)
    default = w.run_sparse(q_nope, q_pe, ckv, kpe, rows)
    np.testing.assert_allclose(
        default, w.run_sparse(q_nope, q_pe, ckv, kpe, rows,
                              sm_scale=1.0 / 48 ** 0.5), atol=1e-6)
    assert float(jnp.max(jnp.abs(default - got))) > 1e-3


# ---------------------------------------------------------------------------
# (d) the share adds up
# ---------------------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The expert layer's parts over 4 shares of 2 experts each, the shared
    expert counted once, equal the uncut reference's layer."""
    whole = dict(M, n_routed_experts=8)
    layer = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        fam._layer(jax.random.PRNGKey(11), fam._shapes(whole, "moe")))
    t = 16
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(12), (t, 128), jnp.float32)
    kw = dict(top_k=3, scaling=2.5, eps=1e-5)
    want, _ = ref.expert_ffn(x, layer, first=0, **kw)
    ctx = StepContext(positions=None, tok_req=None, attend=None,
                      plan={"n_tokens": jnp.int32(t)}, aux={})
    outs, rows = [], []
    for first in (0, 2, 4, 6):
        mcfg = _mcfg(local_experts=(first, 2))
        share = dict(layer, w_gate_up=layer["w_gate_up"][first:first + 2],
                     w_down=layer["w_down"][first:first + 2])
        out, n_rows = glm_dsa._expert_layer(mcfg, x, share, ctx)
        outs.append(out)
        rows.append(int(n_rows.sum()))
        # a share alone is what the reference gives for the same share
        np.testing.assert_allclose(
            out, ref.expert_ffn(x, share, first=first, **kw)[0], atol=1e-5)
    # every token's 3 routes land in exactly one share
    assert sum(rows) == 3 * t and min(rows) > 0
    u = ref.rmsnorm(x, layer["post_norm"], 1e-5)
    shared_once = x + ref.swiglu(u, layer["shared_gate_up"],
                                 layer["shared_down"], "float32")
    total = shared_once + sum(o - shared_once for o in outs)
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# (e) what a latent model refuses, by name
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
    with pytest.raises(NotImplementedError, match="latent layers") as e:
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
        with pytest.raises(NotImplementedError, match="latent layers"):
            call()


def test_the_familys_weights_are_the_programs_layout():
    mcfg = fam.model_config(M)
    for ffn in ("dense", "moe"):
        assert fam._shapes(M, ffn) == glm_dsa.layer_shapes(mcfg, ffn)
    assert mcfg.layer_kinds() == ("latent",) * 2
    assert mcfg.layer_ffn == ("dense", "moe")
