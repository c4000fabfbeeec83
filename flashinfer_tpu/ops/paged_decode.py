"""Paged GQA decode attention Pallas kernel.

TPU-native re-design of the reference batch-decode path
(``include/flashinfer/attention/decode.cuh:613`` +
``scheduler.cuh:426 DecodePlan``).  Key design departures, per SURVEY §7:

- The KV page table is a *scalar-prefetch* operand; KV pages are gathered
  HBM→VMEM with double-buffered async DMAs inside the kernel (the Pallas
  paged-attention pattern), instead of the reference's ``paged_kv_t`` global
  loads.
- GQA "use_tensor_cores" trick maps to MXU-shaped q packing: the q heads of
  one KV head are processed together as an [group_padded, head_dim] tile.
- Split-KV work partitioning (reference ``scheduler.cuh:150,426``) exists
  here as a *pipeline-shape* tool, not an SM-filling one: the default
  kernel walks a request's whole KV range sequentially with pipelined DMA
  (grid starvation doesn't exist on a TPU core), but short-context /
  large-batch shapes pay a per-request cold-start DMA stall that the
  split path removes.  ``build_decode_split_units`` partitions each
  request's page list into ``num_splits`` contiguous chunk-aligned KV
  spans at plan time (PR 3 work-unit style scalar-prefetch arrays);
  ``_decode_split_kernel_fused_heads`` writes per-unit ``(out, lse)``
  partials — when every unit is a single DMA chunk the unit stream is
  cross-unit double-buffered with zero cold start anywhere — and
  ``ops/merge.py merge_states`` reduces the partials by the
  online-softmax merge identity.  The split factor is chosen by the
  analytic cost model at plan time (``obs/costmodel.choose_decode_splits``;
  ``decode.splits`` autotune knob overrides).  On-chip proof pending;
  interpret-mode parity is pinned by tests/test_split_decode.py.  LSE
  output remains available for cascade/DCP merging on both paths.

Cache layouts: "HND" ``[num_pages, num_kv_heads, page_size, head_dim]``
(TPU-preferred: one page+head slice is a contiguous [page_size, head_dim]
DMA) or "NHD" ``[num_pages, page_size, num_kv_heads, head_dim]``
(reference default; strided DMA).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import cdiv, round_up, use_interpret

_NEG_INF = -1e30

# Plan-static cast targets: the launch knows every dtype in play at
# trace time, so the decode kernels take the cast TARGET as a static
# name selecting from this literal map.  An unsupported dtype fails at
# trace instead of lowering through an unproven Mosaic cast path — the
# enumerable, per-pair-testable set the L015 [cast] lint asks for.
_CAST_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def _decode_kernel(
    # scalar prefetch
    pages_ref,  # [B, P] int32 page table (padded with a valid page id)
    kvlen_ref,  # [B] int32
    # inputs
    q_ref,  # [Gp, D] (block of [B, Hkv, Gp, D])
    k_hbm,  # full cache in ANY/HBM
    v_hbm,
    # outputs
    o_ref,  # [Gp, D]
    lse_ref,  # [Gp, 128]
    # scratch
    k_buf,  # [2, chunk_tokens, D]
    v_buf,  # [2, chunk_tokens, D]
    sem,  # DMA sems [2, 2, ppc]
    *,
    page_size: int,
    ppc: int,  # pages per chunk
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    out_dtype: str,  # o_ref's dtype name, from _CAST_DTYPES
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    kv_len = kvlen_ref[b]
    chunk_tokens = ppc * page_size
    num_chunks = pl.cdiv(kv_len, chunk_tokens)

    def page_dmas(chunk_idx, slot):
        dmas = []
        for j in range(ppc):  # wedge-lint: ok on-chip validated round 2 at ppc=16 (banked 0.71 TB/s decode); clamp min(512//PS,16)
            page = pages_ref[b, chunk_idx * ppc + j]
            # NHD page layout: per-head strided DMA [PS, h, D]
            k_src = k_hbm.at[page, :, h, :]
            v_src = v_hbm.at[page, :, h, :]
            dst = pl.ds(j * page_size, page_size)
            dmas.append(
                pltpu.make_async_copy(k_src, k_buf.at[slot, dst, :], sem.at[slot, 0, j])
            )
            dmas.append(
                pltpu.make_async_copy(v_src, v_buf.at[slot, dst, :], sem.at[slot, 1, j])
            )
        return dmas

    def start_chunk(chunk_idx, slot):
        for dma in page_dmas(chunk_idx, slot):
            dma.start()

    def wait_chunk(chunk_idx, slot):
        for dma in page_dmas(chunk_idx, slot):
            dma.wait()

    @pl.when(num_chunks > 0)
    def _warmup():
        start_chunk(0, 0)

    q = q_ref[...].astype(jnp.float32) * sm_scale  # [Gp, D]
    gp = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < num_chunks)
        def _prefetch():
            start_chunk(i + 1, jax.lax.rem(i + 1, 2))

        wait_chunk(i, slot)
        k = k_buf[slot].astype(jnp.float32)  # [chunk_tokens, D]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Gp, chunk_tokens]
        if logits_soft_cap > 0.0:
            s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
        tok = i * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_tokens), 1
        )
        valid = tok < kv_len
        if window_left >= 0:
            valid = valid & (tok >= kv_len - 1 - window_left)
        s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((gp, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((gp, 1), jnp.float32)
    acc0 = jnp.zeros_like(q)
    m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))

    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / l_safe).astype(_CAST_DTYPES[out_dtype])
    lse = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)
    lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _decode_kernel_fused_heads(
    # scalar prefetch
    pages_ref,  # [B, P] int32 page table (padded with a valid page id)
    kvlen_ref,  # [B] int32
    # inputs
    q_ref,  # [Hkv, Gp, D] (block of [B, Hkv, Gp, D])
    k_hbm,  # [num_pages, Hkv, PS, D] in ANY/HBM
    v_hbm,
    # outputs
    o_ref,  # [Hkv, Gp, D]
    lse_ref,  # [Hkv, Gp, 128]
    # scratch
    k_buf,  # [2, ppc, Hkv, PS, D]
    v_buf,
    sem,  # DMA sems [2, 2, ppc]
    base_smem,  # [1] int32: slot parity carried across grid steps
    *,
    page_size: int,
    ppc: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    num_kv_heads: int,
    cross_step_prefetch: bool,
    compute_dtype: str,  # q's (== o's) dtype name, from _CAST_DTYPES
):
    """HND fast path: one DMA per whole page serves every KV head.

    The per-(batch, kv_head) grid of ``_decode_kernel`` re-reads each page
    once per head in 4 KB slices — 8x the DMA transactions the data needs.
    Here the grid is ``(batch,)``; each 32 KB page ``[Hkv, PS, D]`` is
    gathered once and all head groups are computed from it, with bf16 MXU
    dots (f32 accumulate) instead of VPU upcasts.  This is the TPU analogue
    of the reference's one-CTA-per-request split-KV decode kernel
    (include/flashinfer/attention/decode.cuh:613) with its per-warp head
    parallelism collapsed into the head loop of a single core.

    Cross-step pipelining (``cross_step_prefetch``): each step issues the
    *next* request's first chunk before finishing, hiding the per-request
    cold-start DMA stall (~one chunk's fetch per request; at bs=64/ctx=4k
    that stall is ~6% of the whole step).  Two variants:

    - ``True`` (dynamic): slot parity carried across grid steps in SMEM
      (chunk counts differ per request, so parity is data-dependent).
      Measured LOSING on v5e — the dynamic slot indexing it forces costs
      more than the stall it hides (0.68 vs 0.75 TB/s at bs=64/ctx=4k).
    - ``"static"``: prefetch only when the current request's chunk count
      is EVEN, so the free buffer slot is always slot 0 and every slot
      index stays a compile-time constant; odd-chunk requests simply keep
      the cold-start stall.  All conditions derive from the scalar-
      prefetched ``kvlen_ref``, so there is no carried state at all.  At
      the tuned bs=64/ctx=4k shape (16 chunks/request) every request has
      an even count and the whole stall disappears.
    """
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    kv_len = kvlen_ref[b]
    chunk_tokens = ppc * page_size
    num_chunks = pl.cdiv(kv_len, chunk_tokens)
    static_pf = cross_step_prefetch == "static"
    if cross_step_prefetch is True:
        # kv_len == 0 still walks one (fully masked) chunk: the cross-step
        # pipeline depends on every step consuming the chunk-0 DMA its
        # predecessor issued (dangling semaphore signals otherwise)
        num_chunks = jnp.maximum(num_chunks, 1)

    def page_dmas(bb, chunk_idx, slot):
        dmas = []
        for j in range(ppc):  # wedge-lint: ok ppc bounded by the 8 MiB VMEM clamp at call site; on-chip validated round 2
            page = pages_ref[bb, chunk_idx * ppc + j]
            dmas.append(
                pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[slot, j], sem.at[slot, 0, j]
                )
            )
            dmas.append(
                pltpu.make_async_copy(
                    v_hbm.at[page], v_buf.at[slot, j], sem.at[slot, 1, j]
                )
            )
        return dmas

    def start_chunk(bb, chunk_idx, slot):
        for dma in page_dmas(bb, chunk_idx, slot):
            dma.start()

    def wait_chunk(bb, chunk_idx, slot):
        for dma in page_dmas(bb, chunk_idx, slot):
            dma.wait()

    if cross_step_prefetch is True:
        base = jnp.where(b == 0, 0, base_smem[0])

        @pl.when(b == 0)
        def _warmup():
            start_chunk(b, 0, 0)
    elif static_pf:
        base = 0
        # predecessor's epilogue prefetched our chunk 0 into slot 0 iff it
        # ran chunks (nc_prev > 0), had an even count (slot 0 free), and
        # we have chunks to run (its nc_next > 0 check — same formula)
        prev_nc = pl.cdiv(kvlen_ref[jnp.maximum(b - 1, 0)], chunk_tokens)
        prev_prefetched = (
            (b > 0) & (prev_nc > 0) & (jax.lax.rem(prev_nc, 2) == 0)
        )

        @pl.when((num_chunks > 0) & ~prev_prefetched)
        def _warmup():
            start_chunk(b, 0, 0)
    else:
        base = 0

        @pl.when(num_chunks > 0)
        def _warmup():
            start_chunk(b, 0, 0)

    q = q_ref[...]  # [Hkv, Gp, D] native dtype
    gp = q.shape[1]
    head_dim = q.shape[2]
    cdt = _CAST_DTYPES[compute_dtype]  # literal cast target (== q.dtype)

    def body(i, carry):
        m, l, acc = carry  # [Hkv, Gp, 1] x2, [Hkv, Gp, D]
        slot = jax.lax.rem(base + i, 2)

        @pl.when(i + 1 < num_chunks)
        def _prefetch():
            start_chunk(b, i + 1, jax.lax.rem(base + i + 1, 2))

        wait_chunk(b, i, slot)
        tok = i * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_tokens), 1
        )
        valid = tok < kv_len
        if window_left >= 0:
            valid = valid & (tok >= kv_len - 1 - window_left)

        ss, pvs = [], []
        # wedge-lint: ok bounded by num_kv_heads (<=16 served models, 2 dots/head); on-chip validated round 2
        for h in range(num_kv_heads):
            kh = k_buf[slot, :, h, :, :].reshape(chunk_tokens, head_dim)
            if kh.dtype != q.dtype:
                # quantized (fp8/int8) KV: cache bytes cross HBM at half
                # width, dequant is an in-register cast; the scalar
                # k_scale/v_scale are folded into sm_scale / output by the
                # wrapper (reference decode.py:2004 scale folding)
                kh = kh.astype(cdt)
            s = jax.lax.dot_general(
                q[h], kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [Gp, chunk_tokens] f32
            if logits_soft_cap > 0.0:
                s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
            ss.append(jnp.where(valid, s, _NEG_INF))
        s_all = jnp.stack(ss)  # [Hkv, Gp, chunk]
        m_cur = jnp.max(s_all, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p_all = jnp.where(valid[None], jnp.exp(s_all - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p_all, axis=-1, keepdims=True)
        for h in range(num_kv_heads):  # wedge-lint: ok bounded by num_kv_heads; on-chip validated round 2
            vh = v_buf[slot, :, h, :, :].reshape(chunk_tokens, head_dim)
            if vh.dtype != q.dtype:
                vh = vh.astype(cdt)
            pvs.append(
                jax.lax.dot_general(
                    p_all[h].astype(cdt), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        pv = jnp.stack(pvs)  # [Hkv, Gp, D]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((num_kv_heads, gp, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((num_kv_heads, gp, 1), jnp.float32)
    acc0 = jnp.zeros((num_kv_heads, gp, head_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))

    if cross_step_prefetch is True:
        # hand the free slot to the next request's first chunk before the
        # epilogue so its gather overlaps the output write + step transition
        next_base = jax.lax.rem(base + num_chunks, 2)

        @pl.when(b + 1 < nb)
        def _prefetch_next_request():
            start_chunk(b + 1, 0, next_base)

        base_smem[0] = next_base
    elif static_pf:
        next_nc = pl.cdiv(kvlen_ref[jnp.minimum(b + 1, nb - 1)], chunk_tokens)

        @pl.when(
            (b + 1 < nb) & (num_chunks > 0)
            & (jax.lax.rem(num_chunks, 2) == 0) & (next_nc > 0)
        )
        def _prefetch_next_request_static():
            start_chunk(b + 1, 0, 0)

    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / l_safe).astype(cdt)
    lse = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)
    lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def decode_tactic_key(batch, max_pages, num_qo_heads, num_kv_heads,
                      head_dim, page_size, q_dtype):
    """The ONE shape key for paged-decode tactic caches
    (``paged_decode.pages_per_chunk`` / ``paged_decode.prefetch``): built
    here so every lookup site (wrapper run, model decode steps) stays in
    sync when a field is added."""
    return (batch, max_pages, num_qo_heads, num_kv_heads, head_dim,
            page_size, str(q_dtype))


def _paged_decode_hnd_launch(
    q: jax.Array,  # [batch, num_qo_heads, head_dim]
    k_cache: jax.Array,  # [num_pages, Hkv, PS, D]
    v_cache: jax.Array,
    page_table: jax.Array,  # [batch, P_padded] int32
    kv_lens: jax.Array,  # [batch] int32
    *,
    page_size: int,
    pages_per_chunk: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    cross_step_prefetch,
):
    """Head-fused HND fast path: one 32KB page DMA serves all KV heads.

    Module-level (not a branch body of ``paged_decode_attention``) so
    the ``paged_decode.pages_per_chunk`` KNOB_LAUNCHES binding can
    resolve ONE launch with a once-assigned grid spec and prove shipped
    config entries fit the double-buffered chunk-pair scratch (L009) —
    the same hoist ``paged_decode_attention_split`` already has.
    Returns the padded-group ``(out, lse)`` pair; the caller slices the
    group padding off."""
    batch, num_qo_heads, head_dim = q.shape
    _num_pages, num_kv_heads, _ps, _ = k_cache.shape
    group = num_qo_heads // num_kv_heads
    gp = round_up(group, 8)
    # [B, Hq, D] -> [B, Hkv, Gp, D] with zero padding in the group dim
    qg = q.reshape(batch, num_kv_heads, group, head_dim)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    kernel = functools.partial(
        _decode_kernel_fused_heads,
        page_size=page_size,
        ppc=pages_per_chunk,
        sm_scale=sm_scale,
        logits_soft_cap=logits_soft_cap,
        window_left=window_left,
        num_kv_heads=num_kv_heads,
        cross_step_prefetch=cross_step_prefetch,
        compute_dtype=jnp.dtype(q.dtype).name,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec(
                (None, num_kv_heads, gp, head_dim),
                lambda b, *_: (b, 0, 0, 0),
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, num_kv_heads, gp, head_dim),
                lambda b, *_: (b, 0, 0, 0),
            ),
            pl.BlockSpec(
                (None, num_kv_heads, gp, 128), lambda b, *_: (b, 0, 0, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM(
                (2, pages_per_chunk, num_kv_heads, page_size, head_dim),
                k_cache.dtype,
            ),
            pltpu.VMEM(
                (2, pages_per_chunk, num_kv_heads, page_size, head_dim),
                v_cache.dtype,
            ),
            pltpu.SemaphoreType.DMA((2, 2, pages_per_chunk)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, num_kv_heads, gp, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, num_kv_heads, gp, 128), jnp.float32),
        ],
        interpret=use_interpret(),
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32), qg, k_cache, v_cache)


def _paged_decode_nhd_launch(
    q: jax.Array,  # [batch, num_qo_heads, head_dim]
    k_cache: jax.Array,  # [num_pages, PS, Hkv, D]
    v_cache: jax.Array,
    page_table: jax.Array,  # [batch, P_padded] int32
    kv_lens: jax.Array,  # [batch] int32
    *,
    page_size: int,
    pages_per_chunk: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
):
    """Per-head NHD launch (the layout-general slow path); module-level
    for the same launch-resolution reason as ``_paged_decode_hnd_launch``.
    Returns the padded-group ``(out, lse)`` pair."""
    batch, num_qo_heads, head_dim = q.shape
    _num_pages, _ps, num_kv_heads, _ = k_cache.shape
    group = num_qo_heads // num_kv_heads
    gp = round_up(group, 8)
    qg = q.reshape(batch, num_kv_heads, group, head_dim)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    chunk_tokens = pages_per_chunk * page_size
    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        ppc=pages_per_chunk,
        sm_scale=sm_scale,
        logits_soft_cap=logits_soft_cap,
        window_left=window_left,
        out_dtype=jnp.dtype(q.dtype).name,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, num_kv_heads),
        in_specs=[
            pl.BlockSpec(
                (None, None, gp, head_dim), lambda b, h, *_: (b, h, 0, 0)
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, gp, head_dim), lambda b, h, *_: (b, h, 0, 0)
            ),
            pl.BlockSpec((None, None, gp, 128), lambda b, h, *_: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, chunk_tokens, head_dim), k_cache.dtype),
            pltpu.VMEM((2, chunk_tokens, head_dim), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2, pages_per_chunk)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, num_kv_heads, gp, head_dim), q.dtype),
            jax.ShapeDtypeStruct((batch, num_kv_heads, gp, 128), jnp.float32),
        ],
        interpret=use_interpret(),
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32), qg, k_cache, v_cache)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sm_scale", "logits_soft_cap", "window_left", "kv_layout",
        "pages_per_chunk", "return_lse", "cross_step_prefetch",
    ),
)
def paged_decode_attention(
    q: jax.Array,  # [batch, num_qo_heads, head_dim]
    k_cache: jax.Array,
    v_cache: jax.Array,
    page_table: jax.Array,  # [batch, max_pages] int32, padded with valid ids
    kv_lens: jax.Array,  # [batch] int32
    *,
    sm_scale: float = 1.0,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    kv_layout: str = "HND",
    pages_per_chunk: Optional[int] = None,
    return_lse: bool = False,
    cross_step_prefetch: bool = False,
):
    """Batched paged decode attention over a padded page table.

    ``page_table``/``kv_lens`` are the plan arrays produced by
    ``BatchDecodeWithPagedKVCacheWrapper.plan`` (padded-rectangular page
    table replaces the reference's ragged indptr + CUDAGraph buffer pinning).
    """
    # identity checks, matching the kernel's dispatch (`is True` /
    # == "static"): a truthy 1 or np.True_ must not pass validation and
    # then silently run the no-prefetch path
    if not (cross_step_prefetch is False or cross_step_prefetch is True
            or cross_step_prefetch == "static"):
        raise ValueError(
            f"cross_step_prefetch must be False, True (dynamic SMEM "
            f"parity) or 'static', got {cross_step_prefetch!r}"
        )
    if cross_step_prefetch == "static":
        cross_step_prefetch = "static"  # normalize np.str_ etc.
    batch, num_qo_heads, head_dim = q.shape
    if kv_layout == "HND":
        num_pages, num_kv_heads, page_size, _ = k_cache.shape
    else:
        num_pages, page_size, num_kv_heads, _ = k_cache.shape
    assert num_qo_heads % num_kv_heads == 0
    group = num_qo_heads // num_kv_heads

    if pages_per_chunk is None:
        pages_per_chunk = max(1, min(512 // page_size, 16))
    if kv_layout == "HND":
        # fused-heads scratch scales with num_kv_heads: clamp the
        # double-buffered K+V footprint (2 slots x 2 bufs x ppc x Hkv x
        # PS x D) to ~8 MiB so large heads/pages still compile — applies
        # to explicit/autotuned values too, which would otherwise exceed
        # a v5e core's VMEM at e.g. Hkv=16, PS=16, ppc=64
        per_page = 4 * num_kv_heads * page_size * head_dim * k_cache.dtype.itemsize
        pages_per_chunk = max(1, min(pages_per_chunk, (8 << 20) // per_page))
    max_pages = page_table.shape[1]
    # pad page table columns to a multiple of pages-per-chunk
    p_padded = round_up(max_pages, pages_per_chunk)
    if p_padded != max_pages:
        page_table = jnp.pad(page_table, ((0, 0), (0, p_padded - max_pages)))

    if kv_layout == "HND":
        # head-fused fast path: one 32KB page DMA serves all KV heads
        out, lse = _paged_decode_hnd_launch(
            q, k_cache, v_cache, page_table, kv_lens,
            page_size=page_size, pages_per_chunk=pages_per_chunk,
            sm_scale=sm_scale, logits_soft_cap=logits_soft_cap,
            window_left=window_left,
            cross_step_prefetch=cross_step_prefetch,
        )
    else:
        out, lse = _paged_decode_nhd_launch(
            q, k_cache, v_cache, page_table, kv_lens,
            page_size=page_size, pages_per_chunk=pages_per_chunk,
            sm_scale=sm_scale, logits_soft_cap=logits_soft_cap,
            window_left=window_left,
        )

    out = out[:, :, :group, :].reshape(batch, num_qo_heads, head_dim)
    if return_lse:
        return out, lse[:, :, :group, 0].reshape(batch, num_qo_heads)
    return out


# ---------------------------------------------------------------------------
# Split-KV decode: plan-time work partitioning + partial-state kernel + merge
# (reference scheduler.cuh:150,426 split-KV-then-merge, TPU-reshaped: the
# split removes per-request DMA cold starts instead of filling idle SMs)
# ---------------------------------------------------------------------------


def split_pages_per_chunk(page_size: int, num_kv_heads: int, head_dim: int,
                          itemsize: int = 2) -> int:
    """The ONE pages-per-chunk formula of the split path, shared by the
    planner (unit boundaries are chunk-aligned), the kernel entry
    (scratch shapes), and the plan-time cost model — a skew between any
    two would misalign unit spans against the DMA loop.  Same default +
    8 MiB double-buffer clamp as the unsplit fused-heads path."""
    ppc = max(1, min(512 // page_size, 16))
    per_page = 4 * num_kv_heads * page_size * head_dim * itemsize
    return max(1, min(ppc, (8 << 20) // per_page))


def decode_split_tactic_key(batch, max_pages, num_qo_heads, num_kv_heads,
                            head_dim, page_size, pages_per_chunk, q_dtype):
    """Shape key for the ``decode.splits`` knob: ``decode_tactic_key``
    fields + the pages-per-chunk the plan was built for (the split
    choice and the L009 VMEM proof both depend on it)."""
    return (batch, max_pages, num_qo_heads, num_kv_heads, head_dim,
            page_size, pages_per_chunk, str(q_dtype))


def build_decode_split_units(
    page_table: np.ndarray,  # [B_pad, P] int32 padded page table
    kv_lens: np.ndarray,  # [B_pad] kv token lengths (0 for pad rows)
    *,
    num_splits: int,
    page_size: int,
    pages_per_chunk: int,
):
    """Host-side split planner: partition every request's page list into
    ``num_splits`` contiguous, chunk-aligned KV spans (the decode
    analogue of ``build_prefill_work_units``; reference ``DecodePlan``
    split-KV work estimation, scheduler.cuh:150).

    Unit ``u = b * num_splits + s`` covers request ``b``'s pages
    ``[s * per_b, (s + 1) * per_b)`` where ``per_b = ceil(pages_b /
    num_splits)`` rounded up to a whole number of DMA chunks — chunk
    alignment keeps every unit's page walk a whole-chunk loop, so
    splits below chunk granularity degenerate into empty units (kvlen
    0) which the kernel skips without issuing DMA.  The unit order is
    split-major within each request, so partials reshape to
    ``[B_pad, num_splits, ...]`` for one batched ``merge_states`` call.

    Returns a plan dict whose five array keys (``pages``, ``kvlen``,
    ``wu_req``, ``wu_page0``, ``wu_kvlen``) are the scalar-prefetch
    operands of ``_decode_split_kernel_fused_heads`` IN ORDER (the
    L007 planner/kernel contract), plus statics (``num_units``,
    ``num_splits``, ``single_chunk``, ``pages_per_chunk``) and a
    ``stats`` dict (empty-unit count, launched-vs-real page traffic —
    the padding-waste numbers the cost model charges)."""
    pt = np.asarray(page_table)
    lens = np.asarray(kv_lens, np.int64).reshape(-1)
    B, P = pt.shape
    S = int(num_splits)
    assert S >= 1, num_splits
    W = B * S
    pages_r = -(-lens // page_size)  # cdiv; 0 for empty/pad rows
    per = -(-np.maximum(pages_r, 1) // S)
    per = -(-per // pages_per_chunk) * pages_per_chunk  # chunk-align
    wu_req = np.repeat(np.arange(B, dtype=np.int64), S)
    s_idx = np.tile(np.arange(S, dtype=np.int64), B)
    per_u = np.repeat(per, S)
    page0 = per_u * s_idx
    start_tok = page0 * page_size
    end_tok = np.minimum(start_tok + per_u * page_size, np.repeat(lens, S))
    uklen = np.maximum(end_tok - start_tok, 0)
    page0 = np.where(uklen > 0, page0, 0)  # empty units never DMA
    chunks_u = -(-uklen // (pages_per_chunk * page_size))
    max_chunks = int(chunks_u.max(initial=0))
    # pad table columns so every unit's whole-chunk walk stays in bounds
    width = max(P, int((per * (pages_r > 0)).max(initial=0)) * S,
                pages_per_chunk)
    if width != P:
        pt = np.pad(pt, ((0, 0), (0, width - P)))
    stats = {
        "units": W,
        "units_empty": int((uklen == 0).sum()),
        "max_chunks_per_unit": max_chunks,
        "pages_real": int(pages_r.sum()),
        "pages_launched": int((chunks_u * pages_per_chunk).sum()),
    }
    return dict(
        pages=pt.astype(np.int32),
        kvlen=lens.astype(np.int32),
        wu_req=wu_req.astype(np.int32),
        wu_page0=page0.astype(np.int32),
        wu_kvlen=uklen.astype(np.int32),
        num_units=W,
        num_splits=S,
        single_chunk=bool(max_chunks <= 1),
        pages_per_chunk=pages_per_chunk,
        stats=stats,
    )


def _decode_split_kernel_fused_heads(
    # scalar prefetch (the build_decode_split_units plan arrays, in order)
    pages_ref,  # [B_pad, P_w] int32 page table (padded with valid ids)
    kvlen_ref,  # [B_pad] int32 full per-request kv lengths
    req_ref,  # [W] int32 request id per work unit
    page0_ref,  # [W] int32 first page-table column of the unit's span
    uklen_ref,  # [W] int32 kv tokens in the unit's span (0 = empty unit)
    # inputs
    q_ref,  # [Hkv, Gp, D] (block of [B_pad, Hkv, Gp, D], gathered by req)
    k_hbm,  # [num_pages, Hkv, PS, D] in ANY/HBM
    v_hbm,
    # outputs (per-unit partial state)
    o_ref,  # [Hkv, Gp, D] f32 — softmax-normalized partial output
    lse_ref,  # [Hkv, Gp, 128] f32 — partial log-sum-exp (natural log)
    # scratch
    k_buf,  # [2, ppc, Hkv, PS, D]
    v_buf,
    sem,  # DMA sems [2, 2, ppc]
    *,
    page_size: int,
    ppc: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    num_kv_heads: int,
    single_chunk: bool,
):
    """Partial-state variant of ``_decode_kernel_fused_heads``: one grid
    step per work unit, per-unit ``(out, lse)`` written unreduced for a
    downstream ``merge_states`` pass.

    Pipeline shape by plan certificate: with ``single_chunk`` (every
    unit at most one DMA chunk — the shape the plan-time split chooser
    targets) the unit stream is cross-UNIT double-buffered: each unit
    issues its successor's chunk before waiting on its own, so no unit
    ever exposes a cold-start DMA stall (the per-request stall the
    unsplit kernel pays on short-context/large-batch shapes).  The
    general path (multi-chunk units) keeps the unsplit kernel's
    intra-unit double buffer and pays one cold start per unit."""
    u = pl.program_id(0)
    nu = pl.num_programs(0)
    b = req_ref[u]
    kv_len = kvlen_ref[b]
    page0 = page0_ref[u]
    uklen = uklen_ref[u]
    chunk_tokens = ppc * page_size
    num_chunks = pl.cdiv(uklen, chunk_tokens)

    def page_dmas(uu, chunk_idx, slot):
        dmas = []
        for j in range(ppc):  # wedge-lint: ok ppc bounded by the shared 8 MiB VMEM clamp (split_pages_per_chunk) — same on-chip-validated bound as the unsplit fused-heads kernel
            page = pages_ref[
                req_ref[uu], page0_ref[uu] + chunk_idx * ppc + j
            ]
            dmas.append(
                pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[slot, j], sem.at[slot, 0, j]
                )
            )
            dmas.append(
                pltpu.make_async_copy(
                    v_hbm.at[page], v_buf.at[slot, j], sem.at[slot, 1, j]
                )
            )
        return dmas

    def start_chunk(uu, chunk_idx, slot):
        for dma in page_dmas(uu, chunk_idx, slot):
            dma.start()

    def wait_chunk(uu, chunk_idx, slot):
        for dma in page_dmas(uu, chunk_idx, slot):
            dma.wait()

    q = q_ref[...]  # [Hkv, Gp, D] native dtype
    gp = q.shape[1]
    head_dim = q.shape[2]

    def chunk_update(i, carry, slot):
        m, l, acc = carry  # [Hkv, Gp, 1] x2, [Hkv, Gp, D]
        tok = i * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_tokens), 1
        )
        valid = tok < uklen
        if window_left >= 0:
            # window positions are GLOBAL kv positions of the full request
            valid = valid & (
                page0 * page_size + tok >= kv_len - 1 - window_left
            )
        ss, pvs = [], []
        # wedge-lint: ok bounded by num_kv_heads (<=16 served models, 2 dots/head); same loop as the unsplit fused-heads kernel
        for h in range(num_kv_heads):
            kh = k_buf[slot, :, h, :, :].reshape(chunk_tokens, head_dim)
            if kh.dtype != q.dtype:
                # quantized (fp8/int8) KV: in-register dequant cast, the
                # same scale-folding contract as the unsplit kernel
                kh = kh.astype(q.dtype)
            s = jax.lax.dot_general(
                q[h], kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # [Gp, chunk_tokens] f32
            if logits_soft_cap > 0.0:
                s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
            ss.append(jnp.where(valid, s, _NEG_INF))
        s_all = jnp.stack(ss)  # [Hkv, Gp, chunk]
        m_cur = jnp.max(s_all, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p_all = jnp.where(valid[None], jnp.exp(s_all - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p_all, axis=-1, keepdims=True)
        for h in range(num_kv_heads):  # wedge-lint: ok bounded by num_kv_heads; same loop as the unsplit fused-heads kernel
            vh = v_buf[slot, :, h, :, :].reshape(chunk_tokens, head_dim)
            if vh.dtype != q.dtype:
                vh = vh.astype(q.dtype)
            pvs.append(
                jax.lax.dot_general(
                    p_all[h].astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        pv = jnp.stack(pvs)  # [Hkv, Gp, D]
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((num_kv_heads, gp, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((num_kv_heads, gp, 1), jnp.float32)
    acc0 = jnp.zeros((num_kv_heads, gp, head_dim), jnp.float32)

    def finalize(m, l, acc):
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)

    if single_chunk:
        # cross-unit double buffer: successor's chunk issued before this
        # unit's wait; empty units (uklen 0) neither issue nor wait, and
        # the issue/wait conditions are the SAME uklen test, so the
        # semaphore chain stays balanced
        slot = jax.lax.rem(u, 2)

        @pl.when((u == 0) & (uklen > 0))
        def _warmup():
            start_chunk(u, 0, slot)

        @pl.when((u + 1 < nu)
                 & (uklen_ref[jnp.minimum(u + 1, nu - 1)] > 0))
        def _prefetch_next_unit():
            start_chunk(u + 1, 0, jax.lax.rem(u + 1, 2))

        @pl.when(uklen > 0)
        def _compute():
            wait_chunk(u, 0, slot)
            finalize(*chunk_update(0, (m0, l0, acc0), slot))

        @pl.when(uklen <= 0)
        def _empty():
            finalize(m0, l0, acc0)
    else:
        @pl.when(num_chunks > 0)
        def _warmup_general():
            start_chunk(u, 0, 0)

        def body(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < num_chunks)
            def _prefetch():
                start_chunk(u, i + 1, jax.lax.rem(i + 1, 2))

            wait_chunk(u, i, slot)
            return chunk_update(i, carry, slot)

        m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))
        finalize(m, l, acc)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_units", "num_splits", "single_chunk", "pages_per_chunk",
        "sm_scale", "logits_soft_cap", "window_left", "return_lse",
    ),
)
def paged_decode_attention_split(
    q: jax.Array,  # [B_pad, num_qo_heads, head_dim]
    k_cache: jax.Array,  # [num_pages, Hkv, PS, D] (HND only)
    v_cache: jax.Array,
    plan: dict,  # jnp arrays from build_decode_split_units
    *,
    num_units: int,
    num_splits: int,
    single_chunk: bool,
    pages_per_chunk: int,
    sm_scale: float = 1.0,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    return_lse: bool = False,
):
    """Split-KV batched paged decode over plan-time work units: per-unit
    partial states from ``_decode_split_kernel_fused_heads`` reduced by
    the batched ``merge_states`` operator (the online-softmax merge
    identity, ops/merge.py) — the TPU form of the reference's
    split-KV-then-merge decode (scheduler.cuh:426 + cascade.cuh:214).

    HND caches only (the fused-heads fast path); ``plan`` statics
    (``num_units``/``num_splits``/``single_chunk``/``pages_per_chunk``)
    must come from the SAME ``build_decode_split_units`` call that built
    the arrays — unit spans are chunk-aligned to that pages_per_chunk.
    Partials are f32 and the merge runs in f32, so the result matches
    the unsplit kernel to accumulation rounding (pinned by
    tests/test_split_decode.py)."""
    from flashinfer_tpu.ops.merge import merge_states

    batch, num_qo_heads, head_dim = q.shape
    _num_pages, num_kv_heads, page_size, _ = k_cache.shape
    assert num_qo_heads % num_kv_heads == 0
    group = num_qo_heads // num_kv_heads
    gp = round_up(group, 8)
    assert num_units == num_splits * batch, (num_units, num_splits, batch)

    qg = q.reshape(batch, num_kv_heads, group, head_dim)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    kernel = functools.partial(
        _decode_split_kernel_fused_heads,
        page_size=page_size,
        ppc=pages_per_chunk,
        sm_scale=sm_scale,
        logits_soft_cap=logits_soft_cap,
        window_left=window_left,
        num_kv_heads=num_kv_heads,
        single_chunk=single_chunk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(num_units,),
        in_specs=[
            pl.BlockSpec(
                (None, num_kv_heads, gp, head_dim),
                lambda u, pages, kvlen, req, *_: (req[u], 0, 0, 0),
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, num_kv_heads, gp, head_dim),
                lambda u, *_: (u, 0, 0, 0),
            ),
            pl.BlockSpec(
                (None, num_kv_heads, gp, 128), lambda u, *_: (u, 0, 0, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM(
                (2, pages_per_chunk, num_kv_heads, page_size, head_dim),
                k_cache.dtype,
            ),
            pltpu.VMEM(
                (2, pages_per_chunk, num_kv_heads, page_size, head_dim),
                v_cache.dtype,
            ),
            pltpu.SemaphoreType.DMA((2, 2, pages_per_chunk)),
        ],
    )
    o_part, lse_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (num_units, num_kv_heads, gp, head_dim), jnp.float32),
            jax.ShapeDtypeStruct(
                (num_units, num_kv_heads, gp, 128), jnp.float32),
        ],
        interpret=use_interpret(),
        name="paged_decode_attention_split",  # the device-event name
    )(
        plan["pages"], plan["kvlen"], plan["wu_req"], plan["wu_page0"],
        plan["wu_kvlen"], qg, k_cache, v_cache,
    )

    # batched merge reduction: [B, S, Hkv*Gp, ...] partials -> one state
    v_p = o_part.reshape(batch, num_splits, num_kv_heads * gp, head_dim)
    s_p = lse_part[..., 0].reshape(batch, num_splits, num_kv_heads * gp)
    merged_v, merged_s = merge_states(v_p, s_p)
    out = merged_v.reshape(batch, num_kv_heads, gp, head_dim)
    out = out[:, :, :group, :].reshape(
        batch, num_qo_heads, head_dim).astype(q.dtype)
    if return_lse:
        lse = merged_s.reshape(batch, num_kv_heads, gp)[:, :, :group]
        return out, lse.reshape(batch, num_qo_heads)
    return out
