"""Learned sparse attention over a latent cache (DeepSeek sparse attention, as
GLM-5 and DeepSeek-V3.2 run it): the lightning indexer's scores, the exact
top-k selection per query token, and absorbed multi-latent attention over the
selected rows only.  Plain XLA; every function serves a prefill chunk's rows
and decode lanes alike, because each query token brings its own list of rows.

- :func:`index_scores`: ``I[q, s] = sum_j w[q, j] relu(q_I[q, j] . k_I[s])``
  in float32, masked outside ``s <= pos[q]``.
- :func:`select_topk`: the ``k`` highest of a row of masked scores, exactly
  (``lax.top_k``: a sort, no approximation), and which of them are real (a
  query with fewer than ``k`` cached tokens keeps them all).
- :func:`sparse_mla_attend`: softmax over the gathered rows, the value being
  the rows' leading ``d_v`` columns (the absorbed form: one latent row is
  key and value of every head).

``BatchMLAPagedAttentionWrapper.run_sparse`` (``mla.py``) and the serving
engine's latent layer (``models/glm_dsa.py``) both call these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_MASKED = -jnp.inf


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
    lower position), and which of them are real: ``(idx [..., k] int32,
    valid [..., k])``.  A row with fewer than ``k`` unmasked scores selects
    them all; the rest of its list is not valid."""
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
