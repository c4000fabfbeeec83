"""Segment-ids flash attention Pallas kernel (prefill/append core).

TPU-native re-design of the reference's FA2-style prefill kernels
(``include/flashinfer/attention/prefill.cuh:2448,2682``).  Instead of the
reference's per-request CTA work queue, raggedness is expressed the TPU way:
all requests are flattened onto one token axis and a *segment id* per token
keeps requests apart, so one dense grid serves single-request, ragged-batch
and (after a gather) paged-batch prefill.  Masking modes (causal with
bottom-right alignment, sliding window, custom bitmask via segment trick),
logits soft-cap, GQA head grouping, and LSE output all live in this one
kernel — they are closure specializations, the Pallas analogue of the
reference's jinja-specialized kernel instantiations.

Grid: ``(num_qo_heads, q_blocks, kv_blocks)`` with online-softmax state in
VMEM scratch carried across the innermost kv dimension.  A plan-time
block-code map hoists mask work out of the inner loop: blocks provably
all-masked are skipped (both matmuls bypassed), blocks provably all-valid
run an unmasked fast path (no segment/causal/window selects), and only
genuinely mixed blocks — the diagonal and request boundaries — pay for
in-register mask recomputation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import round_up, use_interpret

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 512
_NEG_INF = -1e30


BLOCK_COMPUTE = 0  # mixed block: recompute segment/causal/window masks
BLOCK_SKIP = 1  # provably all-masked: bypass both matmuls
BLOCK_FULL = 2  # provably all-valid: unmasked fast path (no selects)


def _flash_kernel(
    # scalar-prefetch: block-code map (+ ALiBi slopes when use_alibi)
    code_ref,  # [nq * nkv] i32: BLOCK_COMPUTE / BLOCK_SKIP / BLOCK_FULL
    *rest_all,
    sm_scale: float,
    causal: bool,
    logits_soft_cap: float,
    window_left: int,
    num_kv_blocks: int,
    return_lse: bool,
    use_alibi: bool = False,
):
    # operand order (after skip_ref): [slopes_ref?], q_ref [bq, head_dim],
    # k_ref/v_ref [bkv, head_dim], q_seg_ref [bq, 1], kv_seg_ref [1, bkv]
    # (lane-resident; 2-D because 1-D operands hit XLA-vs-Mosaic tiling
    # mismatches at large sizes), q_pos_ref [bq, 1], kv_pos_ref [1, bkv],
    # outputs (lse_ref only when return_lse), scratch
    if use_alibi:
        slopes_ref, *rest_all = rest_all
    else:
        slopes_ref = None
    (q_ref, k_ref, v_ref, q_seg_ref, kv_seg_ref, q_pos_ref, kv_pos_ref,
     *rest) = rest_all
    if return_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        lse_ref = None
    head_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    code = code_ref[q_idx * num_kv_blocks + kv_idx]

    def compute(masked: bool):
        """One online-softmax block step.  ``masked=False`` is the
        BLOCK_FULL fast path: the plan proved every (q, kv) pair of this
        block valid (one common segment, causal/window satisfied
        block-wide), so no mask is materialized and no selects run — the
        plan-time mask hoisting that keeps interior blocks MXU-bound."""
        # native-dtype (bf16) matmul on the MXU, f32 accumulation
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bkv] f32
        s = s * sm_scale
        q_pos = q_pos_ref[...]
        kv_pos = kv_pos_ref[...]
        if use_alibi:
            # reference variants.cuh:68 — bias after scale, before the
            # soft-cap transform; (1, bkv) - (bq, 1) broadcasts like the
            # causal mask compare below
            slope = slopes_ref[head_idx]
            s = s + slope * (kv_pos - q_pos).astype(jnp.float32)
        if logits_soft_cap > 0.0:
            s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
        if masked:
            q_seg = q_seg_ref[...]  # [bq, 1]
            kv_seg = kv_seg_ref[...]  # [1, bkv] — lane broadcast, free
            mask = q_seg == kv_seg
            if causal:
                mask = mask & (kv_pos <= q_pos)
            if window_left >= 0:
                mask = mask & (kv_pos >= q_pos - window_left)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows: keep exp argument finite
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_ref[...][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(code == BLOCK_COMPUTE)
    def _compute_masked():
        compute(masked=True)

    @pl.when(code == BLOCK_FULL)
    def _compute_full():
        compute(masked=False)

    @pl.when(kv_idx == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        if return_lse:
            m = m_ref[...][:, :1]
            lse = jnp.where(l > 0.0, m + jnp.log(l), _NEG_INF)
            lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "logits_soft_cap", "window_left",
        "block_q", "block_kv", "return_lse",
    ),
)
def flash_attention(
    q: jax.Array,  # [total_q, num_qo_heads, head_dim]
    k: jax.Array,  # [total_kv, num_kv_heads, head_dim]
    v: jax.Array,  # [total_kv, num_kv_heads, head_dim_vo]
    q_seg: jax.Array,  # [total_q] int32 segment ids (-1 = padding)
    kv_seg: jax.Array,  # [total_kv] int32 segment ids (-2 = padding)
    q_pos: jax.Array,  # [total_q] int32 in-request absolute positions
    kv_pos: jax.Array,  # [total_kv] int32
    *,
    causal: bool = False,
    sm_scale: float = 1.0,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    return_lse: bool = False,
    alibi_slopes: Optional[jax.Array] = None,  # [num_qo_heads] f32
):
    """Ragged flash attention over flattened token axes.

    GQA is handled by mapping each q head to its kv head (``h // group``) in
    the kv BlockSpec index map.  Padding tokens must carry distinct negative
    segment ids on the q/kv sides so they never match.  ``alibi_slopes``
    adds ``slope_h * (kv_pos - q_pos)`` to the scaled logits in-kernel
    (SMEM scalar per grid head — no dense bias tensor).
    """
    total_q, num_qo_heads, head_dim = q.shape
    total_kv, num_kv_heads, head_dim_vo = v.shape[0], v.shape[1], v.shape[2]
    assert num_qo_heads % num_kv_heads == 0
    group = num_qo_heads // num_kv_heads

    # block shapes must stay tile-aligned for Mosaic: sublane multiples of
    # 16 (bf16 tile) on the q axis, lane multiples of 128 on the kv axis
    # (kv_seg/kv_pos ride the lane dim); padding below absorbs the tail
    bq = min(block_q, round_up(total_q, 16))
    bkv = min(block_kv, round_up(total_kv, 128))
    # pad token axes to block multiples: out-of-bounds block tails would
    # otherwise read undefined memory, and the padded segment ids (-1/-2)
    # keep padding masked out of every score
    pq = round_up(total_q, bq) - total_q
    pkv = round_up(total_kv, bkv) - total_kv
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0), (0, 0)))
        q_seg = jnp.pad(q_seg, (0, pq), constant_values=-1)
        q_pos = jnp.pad(q_pos, (0, pq))
    if pkv:
        k = jnp.pad(k, ((0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pkv), (0, 0), (0, 0)))
        kv_seg = jnp.pad(kv_seg, (0, pkv), constant_values=-2)
        kv_pos = jnp.pad(kv_pos, (0, pkv))
    tq_pad, tkv_pad = total_q + pq, total_kv + pkv
    nq, nkv = tq_pad // bq, tkv_pad // bkv

    qT = jnp.swapaxes(q, 0, 1)  # [H, Tq, D]
    kT = jnp.swapaxes(k, 0, 1)  # [Hkv, Tkv, D]
    vT = jnp.swapaxes(v, 0, 1)

    q_seg2 = q_seg.astype(jnp.int32).reshape(-1, 1)
    kv_seg2 = kv_seg.astype(jnp.int32).reshape(1, -1)
    q_pos2 = q_pos.astype(jnp.int32).reshape(-1, 1)
    kv_pos2 = kv_pos.astype(jnp.int32).reshape(1, -1)

    # conservative per-(q_blk, kv_blk) block-code map, the plan-time mask
    # hoisting: blocks provably all-masked (BLOCK_SKIP) bypass both
    # matmuls — the causal/segment block-sparsity the reference gets from
    # its work-queue plan — and blocks provably all-VALID (BLOCK_FULL)
    # run the unmasked fast path with no segment/causal/window selects in
    # the inner loop.  Padding maps to distinct large sentinels so
    # pad-only blocks fall out via segment disjointness (and can never be
    # FULL: the q/kv sentinels differ).
    BIGQ, BIGK = 2**30, 2**30 + 5
    qss = jnp.where(q_seg2[:, 0] < 0, BIGQ, q_seg2[:, 0]).reshape(nq, bq)
    kss = jnp.where(kv_seg2[0] < 0, BIGK, kv_seg2[0]).reshape(nkv, bkv)
    qmin, qmax = qss.min(1), qss.max(1)
    kmin, kmax = kss.min(1), kss.max(1)
    qp = q_pos2[:, 0].reshape(nq, bq)
    kp = kv_pos2[0].reshape(nkv, bkv)
    skip = (kmin[None, :] > qmax[:, None]) | (kmax[None, :] < qmin[:, None])
    # position rules are only valid when both blocks sit in one common segment
    single_common = (
        (qmin[:, None] == qmax[:, None])
        & (kmin[None, :] == kmax[None, :])
        & (qmin[:, None] == kmin[None, :])
    )
    full = single_common
    if causal:
        skip = skip | (
            single_common & (kp.min(1)[None, :] > qp.max(1)[:, None])
        )
        # causal holds for EVERY pair iff max(kv_pos) <= min(q_pos)
        full = full & (kp.max(1)[None, :] <= qp.min(1)[:, None])
    if window_left >= 0:
        skip = skip | (
            single_common
            & (kp.max(1)[None, :] < qp.min(1)[:, None] - window_left)
        )
        # window holds for EVERY pair iff min(kv_pos) >= max(q_pos) - wl
        full = full & (
            kp.min(1)[None, :] >= qp.max(1)[:, None] - window_left
        )
    code_map = jnp.where(
        skip, BLOCK_SKIP, jnp.where(full, BLOCK_FULL, BLOCK_COMPUTE)
    ).astype(jnp.int32).reshape(-1)

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        logits_soft_cap=logits_soft_cap,
        window_left=window_left,
        num_kv_blocks=nkv,
        return_lse=return_lse,
        use_alibi=alibi_slopes is not None,
    )

    out_specs = [
        pl.BlockSpec((None, bq, head_dim_vo), lambda h, i, j, *_: (h, i, 0))
    ]
    out_shape = [jax.ShapeDtypeStruct((num_qo_heads, tq_pad, head_dim_vo), q.dtype)]
    if return_lse:
        out_specs.append(
            pl.BlockSpec((None, bq, 128), lambda h, i, j, *_: (h, i, 0))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((num_qo_heads, tq_pad, 128), jnp.float32)
        )

    prefetch = [code_map]
    if alibi_slopes is not None:
        prefetch.append(
            jnp.asarray(alibi_slopes, jnp.float32).reshape(num_qo_heads)
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(num_qo_heads, nq, nkv),
        in_specs=[
            pl.BlockSpec((None, bq, head_dim), lambda h, i, j, *_: (h, i, 0)),
            pl.BlockSpec(
                (None, bkv, head_dim), lambda h, i, j, *_: (h // group, j, 0)
            ),
            pl.BlockSpec(
                (None, bkv, head_dim_vo),
                lambda h, i, j, *_: (h // group, j, 0),
            ),
            pl.BlockSpec((bq, 1), lambda h, i, j, *_: (i, 0)),
            pl.BlockSpec((1, bkv), lambda h, i, j, *_: (0, j)),
            pl.BlockSpec((bq, 1), lambda h, i, j, *_: (i, 0)),
            pl.BlockSpec((1, bkv), lambda h, i, j, *_: (0, j)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, head_dim_vo), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    results = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # NOTE: dimension_semantics=("parallel","parallel","arbitrary") would
        # enable megacore grid partitioning on dual-core chips (v4/v5p), but
        # is a suspect in a Mosaic compile hang under investigation on v5e;
        # reintroduce once cleared.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=use_interpret(),
    )(*prefetch, qT, kT, vT, q_seg2, kv_seg2, q_pos2, kv_pos2)

    out = jnp.swapaxes(results[0], 0, 1)[:total_q]  # [Tq, H, D]
    if return_lse:
        return out, jnp.swapaxes(results[1][..., 0], 0, 1)[:total_q]
    return out
