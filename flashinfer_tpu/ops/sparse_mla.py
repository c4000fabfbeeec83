"""Learned sparse attention over a latent cache (DeepSeek sparse attention, as
GLM-5 and DeepSeek-V3.2 run it): the lightning indexer's scores, the exact
top-k selection per query token, and absorbed multi-latent attention over the
selected rows only, in two forms that compute the same softmax.

- :func:`index_scores`: ``I[q, s] = sum_j w[q, j] relu(q_I[q, j] . k_I[s])``
  in float32, masked outside ``s <= pos[q]``.  Plain XLA.
- :func:`select_topk`: the ``k`` highest of a row of masked scores as a LIST
  (``lax.top_k``, which the TPU runs as a sort of the whole row), for a query
  that gathers; :func:`kth_score`: the ``k``-th score alone, by a search.
- :func:`sparse_mla_attend`, the gathered form (plain XLA): softmax over
  each query token's own gathered rows, the value being the rows' leading
  ``d_v`` columns (the absorbed form: one latent row is key and value of
  every head).  Right where a query brings few MXU rows: a decode lane, and
  ``run_sparse``.
- :func:`selection_mask` and :func:`sparse_mla_stream`, the streamed form (a
  Pallas kernel, ``name="sparse_mla_stream"``): the same selection as a mask
  over the window's positions, and a flash loop that reads a request's window
  ONCE per tile of query tokens under that mask.  Right for a unit of many
  query rows of one request: no two queries share a list, so a gather moves
  ``queries x k`` rows one at a time, where all heads of all the unit's
  tokens share the window's rows on the MXU.

``BatchMLAPagedAttentionWrapper.run_sparse`` (``mla.py``) calls the first
three; the serving engine's latent layer (``models/glm_dsa.py``) all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import use_interpret

_MASKED = -jnp.inf
_NEG_INF = -1e30        # a masked logit of the streamed form (finite)
STREAM_BLOCK = 512      # window positions a grid step of the stream reads
_STREAM_TOKENS = 16     # query tokens a tile: x heads = the MXU's rows


def index_scores(q_i, w_i, k_i, q_pos):
    """``q_i`` ``[..., Q, Hi, Di]``, ``w_i`` ``[..., Q, Hi]`` float32, ``k_i``
    ``[..., S, Di]`` (the cached keys at positions ``0 .. S - 1``), ``q_pos``
    ``[..., Q]`` -> float32 ``[..., Q, S]``, ``-inf`` where ``s > q_pos``."""
    s = jnp.einsum("...qhd,...sd->...qhs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("...qhs,...qh->...qs", jax.nn.relu(s),
                        w_i.astype(jnp.float32))
    causal = jnp.arange(k_i.shape[-2]) <= q_pos[..., None]
    return jnp.where(causal, scores, _MASKED)


def select_topk(scores, k: int):
    """The positions of the ``k`` highest scores of each row (ties to the
    lower position) and which of them are real, ``(idx [..., k] int32, valid
    [..., k])``, by a sort of the row: for a query that gathers its list.  A
    row with fewer than ``k`` unmasked scores selects them all, the rest not."""
    vals, idx = jax.lax.top_k(scores, min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), vals > _MASKED


def sparse_mla_attend(q, rows, valid, *, d_v: int, sm_scale: float,
                      return_lse: bool = False):
    """``q`` ``[..., H, D]`` (absorbed query ``[q_abs | q_pe]``), ``rows``
    ``[..., S, D]`` (each query token's gathered cache rows ``[c_kv |
    k_pe]``), ``valid`` ``[..., S]`` -> float32 ``[..., H, d_v]``: softmax of
    ``q . rows * sm_scale`` over the valid rows, times the rows' first
    ``d_v`` columns.  The second product runs over the rows' whole width and
    is cut afterwards, so the gathered block is read as it lies and never
    copied for its value part."""
    s = jnp.einsum("...hd,...sd->...hs", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    live = valid[..., None, :]
    s = jnp.where(live, s, -1e30)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.where(live, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    p = (p / jnp.where(l > 0, l, 1.0)).astype(rows.dtype)
    out = jnp.einsum("...hs,...sd->...hd", p, rows,
                     preferred_element_type=jnp.float32)[..., :d_v]
    if return_lse:
        return out, jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(l[..., 0]),
                              -1e30)
    return out


def _running_count(flags):
    """How many of ``flags`` ``[..., S]`` are set at or before each position
    -> float32 ``[..., S]``, exact.  Two small matrix products with
    triangles of ones (inside chunks of 128 positions, then over the chunks'
    totals) in place of a cumulative sum, which the TPU runs as a scan of
    many strided passes."""
    s, c = flags.shape[-1], 128
    lead = flags.shape[:-1]
    f = jnp.pad(flags, ((0, 0),) * len(lead) + ((0, -s % c),))
    f = f.astype(jnp.bfloat16).reshape(*lead, -1, c)
    n = f.shape[-2]
    inside = jnp.einsum("...ci,ij->...cj", f,
                        jnp.triu(jnp.ones((c, c), jnp.bfloat16)),
                        preferred_element_type=jnp.float32)
    # a chunk's total is at most 128: exact in bfloat16
    before = jnp.einsum("...c,cd->...d", inside[..., -1].astype(jnp.bfloat16),
                        jnp.triu(jnp.ones((n, n), jnp.bfloat16), 1),
                        preferred_element_type=jnp.float32)
    return (inside + before[..., None]).reshape(*lead, -1)[..., :s]


def selection_mask(scores, idx, valid):
    """The selection of :func:`select_topk` as a mask over the positions it
    chose from: ``scores`` ``[..., S]``, ``idx`` / ``valid`` ``[..., k]`` ->
    bool ``[..., S]`` with exactly the selected positions set.  From the
    list only its last entry is read, for the ``k``-th score; the rule
    (:func:`mask_from_kth`) is then applied to the scores themselves, so the
    order the sort left ties in does not matter: a position is selected iff
    its score is above the ``k``-th, or equal to it and among the first
    ``k - (count above)`` such positions.  A row whose list is not full (its
    last entry not valid) keeps every unmasked position.

    A caller with no use for the list (a unit that streams its window) gets
    the same mask from :func:`kth_score` and the rule, without the sort."""
    k = idx.shape[-1]
    kth = jnp.where(valid[..., -1:],
                    jnp.take_along_axis(scores, idx[..., -1:], -1), _MASKED)
    return mask_from_kth(scores, kth, k)


def _stream_kernel(last_ref, q_ref, w_ref, bias_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, sm_scale: float, d_v: int):
    # q_ref [R, D]: a tile of query tokens x heads; w_ref [block, D]: window
    # rows; bias_ref [tokens, block] float32, 0 where the token selected the
    # position and _NEG_INF elsewhere; o_ref [R, d_v] float32
    j = pl.program_id(1)
    tokens, block = bias_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # blocks wholly past the unit's last position hold no selected row
    @pl.when(j * block <= last_ref[0])
    def _block():
        s = jax.lax.dot_general(
            q_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale     # [R, block]
        # the mask is per token: one row of it serves the token's heads
        s = (s.reshape(tokens, -1, block)
             + bias_ref[...][:, None, :]).reshape(s.shape)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        # a masked logit is exactly _NEG_INF (the bias swallows the score),
        # so its p is 0 under any real maximum; while a row has met no
        # selected position its p are 1, and the first real maximum's
        # alpha = exp(_NEG_INF - m) = 0 wipes what they added
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[...][:, :1] + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(w_ref.dtype), w_ref[:, :d_v], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[...] = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)


def sparse_mla_stream(q, window, mask, last, *, d_v: int, sm_scale: float):
    """The streamed form of :func:`sparse_mla_attend` for query tokens that
    share one window: ``q`` ``[Q, H, D]`` absorbed queries, ``window``
    ``[S, D]`` the request's cached rows by position (``D`` a multiple of
    128), ``mask`` bool ``[Q, S]`` the positions each TOKEN selected
    (:func:`selection_mask`), ``last`` an int32 scalar no selected position
    of any token that counts lies past -> float32 ``[Q, H, d_v]``: per token
    and head the softmax of ``q . window * sm_scale`` over the selected
    positions, times the rows' first ``d_v`` columns.

    A flash loop, grid ``(query tiles, window blocks)``: a tile is
    ``_STREAM_TOKENS`` tokens x all heads (the MXU's rows), a block
    :data:`STREAM_BLOCK` positions; online softmax in float32, the
    probabilities cast to the rows' dtype for the value product.  The window
    is read once a tile, and blocks past ``last`` are neither fetched nor
    computed."""
    n_q, heads, d = q.shape
    n_s = window.shape[0]
    tokens = min(_STREAM_TOKENS, n_q)
    q_pad, s_pad = -n_q % tokens, -n_s % STREAM_BLOCK
    bias = jnp.where(mask, 0.0, _NEG_INF).astype(jnp.float32)
    bias = jnp.pad(bias, ((0, q_pad), (0, s_pad)), constant_values=_NEG_INF)
    q = jnp.pad(q, ((0, q_pad), (0, 0), (0, 0))).reshape(-1, d)
    window = jnp.pad(window, ((0, s_pad), (0, 0)))
    rows = tokens * heads
    last = jnp.clip(jnp.asarray(last, jnp.int32), 0, n_s - 1).reshape(1)

    def block_of(j, last_ref):      # a skipped step re-names the last block
        return jnp.minimum(j, last_ref[0] // STREAM_BLOCK)

    out = pl.pallas_call(
        functools.partial(_stream_kernel, sm_scale=sm_scale, d_v=d_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=((n_q + q_pad) // tokens, (n_s + s_pad) // STREAM_BLOCK),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i, j, last_ref: (i, 0)),
                pl.BlockSpec((STREAM_BLOCK, d),
                             lambda i, j, last_ref: (block_of(j, last_ref), 0)),
                pl.BlockSpec((tokens, STREAM_BLOCK),
                             lambda i, j, last_ref: (i, block_of(j, last_ref))),
            ],
            out_specs=pl.BlockSpec((rows, d_v), lambda i, j, last_ref: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, d_v), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(((n_q + q_pad) * heads, d_v),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="sparse_mla_stream",
        interpret=use_interpret(),
    )(last, q, window, bias)
    return out.reshape(-1, heads, d_v)[:n_q]


# score rows a grid step of the search holds in VMEM.  A grid step costs ~7 us
# however few rows it holds (33 dependent halvings), so more rows a step is
# fewer of them: 8 / 16 / 32 rows read 0.114 / 0.061 / 0.037 ms a unit of 128
# rows at a window of 4,160 and 0.191 / 0.124 / 0.091 at 16,640, the sort 0.24
# and 1.28 (one v5e; PERF.md section 6, PR 49)
_SEARCH_ROWS = 32


def kth_score(scores, k: int):
    """The ``k``-th highest score of each row, exactly and without a sort:
    ``scores`` ``[Q, S]`` float32 (``-inf`` where masked) -> ``[Q, 1]``, for
    :func:`mask_from_kth`.  The search of ``ops/sampling_kernels.py
    top_k_thresholds``: :data:`_SEARCH_ROWS` rows at a time resident in VMEM,
    33 halvings of the interval of their order-preserving int32 keys, one HBM
    read of the scores.  A row with fewer than ``k`` unmasked scores gives
    its least one (which keeps them all, as ``-inf`` would), a row with none
    ``+inf`` (which keeps nothing, as there is nothing to keep); scores at or
    under ``-1e20`` count as masked."""
    from flashinfer_tpu.ops.sampling_kernels import top_k_thresholds

    return top_k_thresholds(
        scores, jnp.full((scores.shape[0],), k, jnp.float32),
        block_rows=_SEARCH_ROWS)[:, None]


def mask_from_kth(scores, kth, k: int):
    """THE selection rule: ``scores`` ``[..., S]``, ``kth`` ``[..., 1]`` the
    ``k``-th highest score of each row (from a list's last entry or from
    :func:`kth_score`) -> bool ``[..., S]``: set where the score is above
    ``kth``, or equal to it, unmasked, and among the first ``k - (count
    above)`` such positions (ties go to the lower position, as a stable sort
    leaves them).  Float comparisons only, so ``0.0`` and ``-0.0`` tie and
    any ``kth`` under every unmasked score keeps them all."""
    above = scores > kth
    tie = (scores == kth) & (scores > _MASKED)
    room = (k - jnp.sum(above, -1, keepdims=True)).astype(jnp.float32)
    return above | (tie & (_running_count(tie) <= room))
