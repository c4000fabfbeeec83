"""Block-sparse (BSR) attention Pallas kernel.

TPU re-design of the reference's block-sparse path
(``flashinfer/sparse.py:195`` BlockSparseAttentionWrapper, which reuses the
prefill kernels with sparse gather indices inside prefill.cuh).  The TPU
translation is direct and kernel-native: the BSR column-index array is a
*scalar-prefetch* operand and the KV BlockSpec's ``index_map`` reads it, so
the Pallas pipeline DMA-gathers exactly the nonzero KV blocks — sparsity
lives in the index map, not in gather ops.

Grid: ``(num_qo_heads, q_blocks, max_blocks_per_row)``; rows with fewer
nonzero blocks skip compute via the prefetched indptr.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import cdiv, use_interpret

_NEG_INF = -1e30


def _bsr_kernel(
    indptr_ref,  # [MB+1] scalar prefetch
    cols_ref,  # [MB * max_nnz] padded column ids (scalar prefetch)
    q_ref,  # [R, D]
    k_ref,  # [C, D]  (block selected by index map)
    v_ref,  # [C, D]
    o_ref,  # [R, D]
    acc_ref,
    m_ref,
    l_ref,
    *,
    max_nnz: int,
    sm_scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    row_nnz = indptr_ref[i + 1] - indptr_ref[i]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < row_nnz)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[...][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == max_nnz - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_row", "block_col", "max_nnz", "sm_scale")
)
def bsr_attention(
    q: jax.Array,  # [M, num_qo_heads, head_dim]
    k: jax.Array,  # [N, num_kv_heads, head_dim]
    v: jax.Array,
    indptr: jax.Array,  # [MB + 1] int32
    cols_padded: jax.Array,  # [MB * max_nnz] int32, padded with 0
    *,
    block_row: int,
    block_col: int,
    max_nnz: int,
    sm_scale: float = 1.0,
):
    M, H, D = q.shape
    N, HKV, _ = k.shape
    group = H // HKV
    MB = M // block_row
    qT = jnp.swapaxes(q, 0, 1)
    kT = jnp.swapaxes(k, 0, 1)
    vT = jnp.swapaxes(v, 0, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H, MB, max_nnz),
        in_specs=[
            pl.BlockSpec((None, block_row, D), lambda h, i, j, *_: (h, i, 0)),
            pl.BlockSpec(
                (None, block_col, D),
                lambda h, i, j, ip, cols: (h // group, cols[i * max_nnz + j], 0),
            ),
            pl.BlockSpec(
                (None, block_col, D),
                lambda h, i, j, ip, cols: (h // group, cols[i * max_nnz + j], 0),
            ),
        ],
        out_specs=pl.BlockSpec((None, block_row, D), lambda h, i, j, *_: (h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_row, D), jnp.float32),
            pltpu.VMEM((block_row, 128), jnp.float32),
            pltpu.VMEM((block_row, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_bsr_kernel, max_nnz=max_nnz, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, M, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=use_interpret(),
    )(indptr.astype(jnp.int32), cols_padded.astype(jnp.int32), qT, kT, vT)
    return jnp.swapaxes(out, 0, 1)


def _bsr_token_select_kernel(
    indptr_ref,  # [MB+1] scalar prefetch
    cols_ref,  # [MB * max_nnz] padded column-block ids
    q_ref,  # [R, D]
    k_ref,  # [C, D]
    v_ref,  # [C, D]
    sel_ref,  # [R, KBpad] f32 per-token block-selection bitmap
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    max_nnz: int,
    kb_pad: int,
    block_row: int,
    block_col: int,
    causal: bool,
    sm_scale: float,
):
    """BSR attention with *per-token* column-block selection (the reference
    MSA semantics, flashinfer/msa_ops/: every query token ranks KV blocks
    by proxy score and keeps its own top-k).  The kernel walks the union
    BSR structure per row-block; each tile extracts its selection column
    from the VMEM-resident bitmap with one skinny one-hot matmul, plus
    token-level causal masking for the boundary blocks."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    row_nnz = indptr_ref[i + 1] - indptr_ref[i]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < row_nnz)
    def _compute():
        c = cols_ref[i * max_nnz + j]
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [R, C]
        # sel_col[r] = bitmap[r, c]: lane-extract via one-hot matmul
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (kb_pad, 1), 0) == c
        ).astype(jnp.float32)
        sel_col = jax.lax.dot_general(
            sel_ref[...], onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R, 1]
        mask = sel_col > 0.5
        if causal:
            q_pos = i * block_row + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            kv_pos = c * block_col + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            mask = mask & (kv_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[...][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == max_nnz - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_row", "block_col", "max_nnz", "causal", "sm_scale"
    ),
)
def bsr_attention_token_select(
    q: jax.Array,  # [M, num_qo_heads, head_dim]
    k: jax.Array,  # [N, num_kv_heads, head_dim]
    v: jax.Array,
    indptr: jax.Array,  # [MB+1] int32 union-BSR structure
    cols_padded: jax.Array,  # [MB * max_nnz] int32
    sel_bitmap: jax.Array,  # [M, KBpad] f32/bool per-token block selection
    *,
    block_row: int,
    block_col: int,
    max_nnz: int,
    causal: bool = False,
    sm_scale: float = 1.0,
):
    M, H, D = q.shape
    group = H // k.shape[1]
    MB = M // block_row
    kb_pad = sel_bitmap.shape[1]
    qT = jnp.swapaxes(q, 0, 1)
    kT = jnp.swapaxes(k, 0, 1)
    vT = jnp.swapaxes(v, 0, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H, MB, max_nnz),
        in_specs=[
            pl.BlockSpec((None, block_row, D), lambda h, i, j, *_: (h, i, 0)),
            pl.BlockSpec(
                (None, block_col, D),
                lambda h, i, j, ip, cols: (h // group, cols[i * max_nnz + j], 0),
            ),
            pl.BlockSpec(
                (None, block_col, D),
                lambda h, i, j, ip, cols: (h // group, cols[i * max_nnz + j], 0),
            ),
            pl.BlockSpec((block_row, kb_pad), lambda h, i, j, *_: (i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, block_row, D), lambda h, i, j, *_: (h, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_row, D), jnp.float32),
            pltpu.VMEM((block_row, 128), jnp.float32),
            pltpu.VMEM((block_row, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _bsr_token_select_kernel,
            max_nnz=max_nnz, kb_pad=kb_pad, block_row=block_row,
            block_col=block_col, causal=causal, sm_scale=sm_scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, M, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=use_interpret(),
    )(
        indptr.astype(jnp.int32), cols_padded.astype(jnp.int32),
        qT, kT, vT, sel_bitmap.astype(jnp.float32),
    )
    return jnp.swapaxes(out, 0, 1)


def _vbsr_kernel(
    # scalar prefetch
    indptr_ref,  # [MT+1] per-q-tile nnz offsets
    cols_ref,  # [MT * max_nnz] kv-tile ids (padded)
    flags_ref,  # [MT * max_nnz] 1=fully covered tile, 2=partial (0=pad)
    rb0_ref,  # [MT] first variable row-block intersecting each q tile
    # inputs
    q_ref,  # [TR, D]
    k_ref,  # [TC, D]
    v_ref,  # [TC, D]
    rowid_ref,  # [TR, 1] variable row-block id per q token
    colid_ref,  # [1, TC] variable col-block id per kv token
    map_ref,  # [MBpad, NBpad] f32 block mask (1.0 = attend)
    # outputs + scratch
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    max_nnz: int,
    k_span: int,
    nb_pad: int,
    sm_scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    row_nnz = indptr_ref[i + 1] - indptr_ref[i]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < row_nnz)
    def _compute():
        flag = flags_ref[i * max_nnz + j]
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [TR, TC]

        # exact token mask for partial tiles, reconstructed on the MXU:
        #   mask[r, c] = map[rowid[r], colid[c]]
        # as onehot_r [TR, K] @ map[rb0:rb0+K, :] [K, NB] @ onehot_c [NB, TC]
        # (K = max row-blocks a q tile can span — tiny, so both extra
        # matmuls are noise next to the qk matmul).  Fully-covered tiles
        # (flag == 1) skip the mask by construction.
        rb0 = rb0_ref[i]
        maprows = map_ref[pl.ds(rb0, k_span), :]  # [K, NBpad]
        colid = colid_ref[...]  # [1, TC]
        iota_nb = jax.lax.broadcasted_iota(jnp.int32, (nb_pad, colid.shape[1]), 0)
        onehot_c = (iota_nb == colid).astype(jnp.float32)  # [NBpad, TC]
        t = jax.lax.dot_general(
            maprows, onehot_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [K, TC]
        rowid = rowid_ref[...]  # [TR, 1]
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (rowid.shape[0], k_span), 1)
        onehot_r = (rowid == rb0 + iota_k).astype(jnp.float32)  # [TR, K]
        maskf = jax.lax.dot_general(
            onehot_r, t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [TR, TC]
        allowed = (flag == 1) | (maskf > 0.5)
        s = jnp.where(allowed, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[...][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == max_nnz - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_row", "block_col", "max_nnz", "k_span", "sm_scale"
    ),
)
def vbsr_attention(
    q: jax.Array,  # [Mpad, num_qo_heads, head_dim]
    k: jax.Array,  # [Npad, num_kv_heads, head_dim]
    v: jax.Array,
    indptr: jax.Array,  # [MT + 1] int32 (per-q-tile nnz offsets)
    cols_padded: jax.Array,  # [MT * max_nnz] int32 kv-tile ids
    flags_padded: jax.Array,  # [MT * max_nnz] int32 (1 full / 2 partial)
    rb0: jax.Array,  # [MT] int32
    row_id: jax.Array,  # [Mpad] int32 variable row-block per q token
    col_id: jax.Array,  # [Npad] int32 variable col-block per kv token
    block_map: jax.Array,  # [MBpad, NBpad] f32
    *,
    block_row: int,
    block_col: int,
    max_nnz: int,
    k_span: int,
    sm_scale: float = 1.0,
):
    """Variable-block-size BSR attention (reference
    ``VariableBlockSparseAttentionWrapper``, flashinfer/sparse.py:1075 over
    vector-sparse prefill).  The variable structure is re-tiled onto fixed
    hardware tiles on the host; compute and KV DMA stay proportional to the
    number of overlapped tiles, and partially-covered tiles recover the
    exact token-level mask in-kernel (see ``_vbsr_kernel``)."""
    M, H, D = q.shape
    group = H // k.shape[1]
    MT = M // block_row
    mb_pad, nb_pad = block_map.shape
    qT = jnp.swapaxes(q, 0, 1)
    kT = jnp.swapaxes(k, 0, 1)
    vT = jnp.swapaxes(v, 0, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H, MT, max_nnz),
        in_specs=[
            pl.BlockSpec(
                (None, block_row, D), lambda h, i, j, *_: (h, i, 0)
            ),
            pl.BlockSpec(
                (None, block_col, D),
                lambda h, i, j, ip, cols, fl, rb: (
                    h // group, cols[i * max_nnz + j], 0
                ),
            ),
            pl.BlockSpec(
                (None, block_col, D),
                lambda h, i, j, ip, cols, fl, rb: (
                    h // group, cols[i * max_nnz + j], 0
                ),
            ),
            pl.BlockSpec((block_row, 1), lambda h, i, j, *_: (i, 0)),
            pl.BlockSpec(
                (1, block_col),
                lambda h, i, j, ip, cols, fl, rb: (
                    0, cols[i * max_nnz + j]
                ),
            ),
            pl.BlockSpec((mb_pad, nb_pad), lambda h, i, j, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, block_row, D), lambda h, i, j, *_: (h, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_row, D), jnp.float32),
            pltpu.VMEM((block_row, 128), jnp.float32),
            pltpu.VMEM((block_row, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _vbsr_kernel,
            max_nnz=max_nnz, k_span=k_span, nb_pad=nb_pad,
            sm_scale=sm_scale,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, M, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=use_interpret(),
    )(
        indptr.astype(jnp.int32), cols_padded.astype(jnp.int32),
        flags_padded.astype(jnp.int32), rb0.astype(jnp.int32),
        qT, kT, vT,
        row_id.astype(jnp.int32).reshape(-1, 1),
        col_id.astype(jnp.int32).reshape(1, -1),
        block_map.astype(jnp.float32),
    )
    return jnp.swapaxes(out, 0, 1)
