"""An expert layer that holds one chip's share of the experts
(``models/granite_hybrid.py``, ``models/glm_dsa.py``).

The routing rule is the model's: it routes over ALL the published experts
and hands the chosen ``ids`` and their ``weights`` here.  This chip holds
experts ``first .. first + held - 1`` and adds what they give; a route to an
absent expert is dropped before the grouped matmul and not renormalised, and
nothing stands in for the peer chips (on one chip the layer runs without its
exchange).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _grouped(lhs, row_ids, w, group_sizes, gather: bool):
    """Rows of ``lhs`` (gathered by ``row_ids`` when ``gather``), sorted by
    expert, times each expert's weight: the Pallas grouped matmul on the
    chip (its grid covers the groups' rows only, so rows past them are
    never computed), ``ragged_dot`` where kernels are interpreted or a
    width is not 128-aligned."""
    from flashinfer_tpu.utils import use_interpret

    k, n = w.shape[1], w.shape[2]
    if use_interpret() or k % 128 or n % 128:
        return jax.lax.ragged_dot(lhs[row_ids] if gather else lhs, w,
                                  group_sizes)
    from flashinfer_tpu.fused_moe.core import _heuristic_gmm_tiles
    from flashinfer_tpu.ops.moe_gmm import gather_gmm, gmm

    tm, tn, tk = _heuristic_gmm_tiles(row_ids.shape[0], k, n,
                                      w.dtype.itemsize, lhs.dtype.itemsize)
    if gather:
        return gather_gmm(lhs, row_ids, w, group_sizes, tm=tm, tn=tn, tk=tk)
    return gmm(lhs, w, group_sizes, tm=tm, tn=tn, tk=tk)


def held_experts(u, weights, ids, w_gate_up, w_down, *, first: int,
                 n_tokens):
    """What the experts held here add for rows ``u`` ``[T, hidden]`` routed
    by ``ids`` / ``weights`` ``[T, top_k]``: float32 ``[T, hidden]``, and the
    rows each held expert got (``[held]``).  Rows past ``n_tokens`` are
    padding and route nowhere.  The sort lies under the scope ``moe.route``,
    the grouped matmuls and the weighted sum under ``moe.experts``."""
    from flashinfer_tpu.activation import silu_and_mul

    t, top_k = ids.shape
    held = w_gate_up.shape[0]
    with jax.named_scope("moe.route"):
        # a route stays when its expert is held here and its row is a
        # token; the others sort behind every group and are never computed
        local = (ids >= first) & (ids < first + held) \
            & (jnp.arange(t) < n_tokens)[:, None]
        key = jnp.where(local, ids - first, held).reshape(-1)
        order = jnp.argsort(key, stable=True)
        token_of = order // top_k
        group_sizes = jnp.bincount(key, length=held).astype(jnp.int32)
        # where route (t, k) lies among the sorted rows
        place = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
    with jax.named_scope("moe.experts"):
        h1 = _grouped(u, token_of, w_gate_up, group_sizes, True)
        h2 = _grouped(silu_and_mul(h1), token_of, w_down, group_sizes, False)
        per_route = h2[place].reshape(t, top_k, -1).astype(jnp.float32)
        routed = jnp.sum(jnp.where(local[..., None],
                                   weights[..., None] * per_route, 0.0), 1)
    return routed, group_sizes


def route_facts(rows, tokens: int, top_k: int) -> dict:
    """The expert layers' counters of one step from the rows each held
    expert got in each expert layer (``rows`` ``[layers, held]``, on the
    host)."""
    return {"moe_routes": tokens * top_k * rows.shape[0],
            "moe_routes_local": int(rows.sum()),
            "moe_expert_rows_max": int(rows.max()),
            "moe_experts_hit": int((rows > 0).sum())}
