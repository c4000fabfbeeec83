"""Fused 4-bit-KV paged decode attention kernel.

The quantized-KV completion of the decode family: the reference fuses
NVFP4 dequant into its decode path (``csrc/fp4_kv_quantization.cu``, paged
NVFP4 append ``include/flashinfer/page.cuh:810``, ``nvfp4_attention_sm120``).
The TPU layout is dictated by Mosaic's DMA tiling — an HBM slice's minor
dimension must be 128-aligned, which rules out both the naive packed
``[..., D//2]`` nibble array and NVFP4's per-16-element scale vectors
``[..., D//16]``.  So:

- **Values**: *token-pair* nibble packing ``[P, Hkv, PS//2, D] int8`` —
  byte ``(tt, d)`` holds token ``2tt``'s dim ``d`` in its low nibble and
  token ``2tt+1``'s in its high nibble.  Minor dim stays the full
  128-lane ``D``; unpacking is two shifts plus one *sublane* concat
  (both Mosaic-native).  The resulting ``[chunk, D]`` matrix holds the
  chunk's even tokens then its odd tokens — a permutation the online
  softmax is invariant to, handled by permuting the validity mask.
- **Scales**: one f32 scale per (page, head, token) at
  ``[P, 128]`` (lane ``h*PS + t``; requires ``Hkv*PS <= 128``) — the
  fp8-KV-style granularity, coarser than NVFP4's 16-element blocks but
  DMA-alignable; rows of the unpacked value matrix are rescaled via tiny
  per-page MXU dots against constant selector matrices.

Page DMA shrinks from 32 KB (bf16, D=128/PS=16/Hkv=8) to 8 KB + 512 B —
a ~3.8x cut on the op where HBM bytes are everything.  Structure mirrors
``ops/paged_decode.py:_decode_kernel_fused_heads`` (grid step per request,
whole-page DMAs serving all KV heads, double buffering).

Round-3 restructure (the ppc=16 wedge fix): the per-page DMA loops are
rolled ``fori_loop``s and the per-row dequant scales come from ONE
selector dot per (head, tensor) instead of ``2*ppc`` small dots, so the
kernel's unrolled op count no longer scales with ``pages_per_chunk`` —
the shape that hung the Mosaic compiler (repo memory ``tpu-wedge-history``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import round_up, use_interpret

_NEG_INF = -1e30


def quantize_kv_int4_paged(cache: jax.Array):
    """Quantize an HND paged cache ``[P, Hkv, PS, D]`` to the kernel's
    token-pair nibble layout -> ``(packed [P, Hkv, PS//2, D] int8,
    scales [P, 128] f32)``.  Symmetric per-(page, head, token) int4."""
    P, Hkv, PS, D = cache.shape
    assert PS % 2 == 0 and Hkv * PS <= 128
    xf = cache.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)  # [P, Hkv, PS]
    scale = jnp.maximum(amax / 7.0, 1e-12)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -7, 7).astype(jnp.int8)
    packed = ((q[:, :, 0::2, :] & 0x0F) | (q[:, :, 1::2, :] << 4)).astype(
        jnp.int8
    )
    scales = jnp.zeros((P, 128), jnp.float32)
    scales = scales.at[:, : Hkv * PS].set(scale.reshape(P, Hkv * PS))
    return packed, scales


def dequantize_kv_int4_paged(packed: jax.Array, scales: jax.Array):
    """Inverse of :func:`quantize_kv_int4_paged` -> ``[P, Hkv, PS, D]`` f32
    (the XLA oracle for the fused kernel)."""
    P, Hkv, half_ps, D = packed.shape
    PS = half_ps * 2
    p32 = packed.astype(jnp.int32)
    lo = (p32 << 28) >> 28
    hi = p32 >> 4
    q = jnp.stack([lo, hi], axis=3).reshape(P, Hkv, PS, D)
    sc = scales[:, : Hkv * PS].reshape(P, Hkv, PS)
    return q.astype(jnp.float32) * sc[..., None]


def _fp4_decode_kernel(
    # scalar prefetch
    pages_ref,  # [B, P] int32
    kvlen_ref,  # [B] int32
    # inputs
    q_ref,  # [Hkv, Gp, D]
    k4_hbm,  # [num_pages, Hkv, PS//2, D] int8 (token-pair nibbles)
    ksc_hbm,  # [num_pages, 128] f32
    v4_hbm,
    vsc_hbm,
    # outputs
    o_ref,  # [Hkv, Gp, D]
    lse_ref,  # [Hkv, Gp, 128]
    # scratch
    k_buf,  # [2, ppc, Hkv, PS//2, D] int8
    ksc_buf,  # [2, ppc, 128] f32
    v_buf,
    vsc_buf,
    sem,  # [2, 4, ppc]
    *,
    page_size: int,
    ppc: int,
    sm_scale: float,
    logits_soft_cap: float,
    window_left: int,
    num_kv_heads: int,
):
    b = pl.program_id(0)
    kv_len = kvlen_ref[b]
    chunk_tokens = ppc * page_size
    half = chunk_tokens // 2
    half_ps = page_size // 2
    num_chunks = pl.cdiv(kv_len, chunk_tokens)

    def _chunk_dmas(chunk_idx, slot, action):
        """Start or wait the chunk's 4*ppc page DMAs via a ROLLED loop.

        The round-2 wedge culprit was this kernel's fully-unrolled body at
        ppc=16 (hundreds of unrolled small ops hung the Mosaic compiler);
        rolling the per-page loop keeps the op count independent of ppc."""

        def body(j, _):
            page = pages_ref[b, chunk_idx * ppc + j]
            for src, dst, ch in (
                (k4_hbm, k_buf, 0), (ksc_hbm, ksc_buf, 1),
                (v4_hbm, v_buf, 2), (vsc_hbm, vsc_buf, 3),
            ):
                dma = pltpu.make_async_copy(
                    src.at[page], dst.at[slot, j], sem.at[slot, ch, j]
                )
                dma.start() if action == "start" else dma.wait()
            return 0

        jax.lax.fori_loop(0, ppc, body, 0)

    @pl.when(num_chunks > 0)
    def _warmup():
        _chunk_dmas(0, 0, "start")

    q = q_ref[...]
    gp, head_dim = q.shape[1], q.shape[2]

    # chunk-token index of each unpacked row (even tokens first, then odd;
    # within each parity, pages then token pairs in order) — the validity
    # mask must follow the same permutation as the unpacked value rows
    r = jax.lax.broadcasted_iota(jnp.int32, (1, chunk_tokens), 1)
    parity = (r >= half).astype(jnp.int32)
    within = jax.lax.rem(r, half)
    pg = within // half_ps
    tt = jax.lax.rem(within, half_ps)
    tok_in_chunk = pg * page_size + 2 * tt + parity  # [1, chunk]

    # constant row-index decomposition for the scale-selection dot below:
    # row r (unpacked order) = (parity, page, token-pair)
    r_sub = jax.lax.broadcasted_iota(jnp.int32, (chunk_tokens, 128), 0)
    par_c = (r_sub >= half).astype(jnp.int32)
    within_c = jax.lax.rem(r_sub, half)
    pg_c = within_c // half_ps
    tt_c = jax.lax.rem(within_c, half_ps)
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (chunk_tokens, 128), 1)

    def row_scales(sc_buf, slot, h):
        """[chunk, 1] per-row dequant scale, in unpacked row order.

        ONE selector dot per (head, tensor) — G[r, c] = 1 iff lane c of the
        scale row holds (head h, token of unpacked row r); M1 = G @ sc^T
        gives the candidate scale from every page, and a constant page-match
        mask picks row r's own page.  Replaces the former 2*ppc-small-dots
        unroll whose op count scaled with ppc (the wedge vector)."""
        G = (lane_c == h * page_size + 2 * tt_c + par_c).astype(jnp.float32)
        m1 = jax.lax.dot_general(
            G, sc_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [chunk, ppc]
        r_p = jax.lax.broadcasted_iota(jnp.int32, (chunk_tokens, ppc), 0)
        own_pg = jax.lax.rem(r_p, half) // half_ps
        pmask = (
            jax.lax.broadcasted_iota(jnp.int32, (chunk_tokens, ppc), 1)
            == own_pg
        ).astype(jnp.float32)
        return jnp.sum(m1 * pmask, axis=1, keepdims=True)  # [chunk, 1]

    def unpack(buf, slot, h):
        pk = buf[slot, :, h].reshape(ppc * half_ps, head_dim)
        p32 = pk.astype(jnp.int32)
        lo = (p32 << 28) >> 28
        hi = p32 >> 4
        return jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < num_chunks)
        def _prefetch():
            _chunk_dmas(i + 1, jax.lax.rem(i + 1, 2), "start")

        _chunk_dmas(i, slot, "wait")

        tok = i * chunk_tokens + tok_in_chunk
        valid = tok < kv_len
        if window_left >= 0:
            valid = valid & (tok >= kv_len - 1 - window_left)

        ss, pvs, vhs = [], [], []
        # wedge-lint: ok bounded by num_kv_heads (2 dots/head); ppc-scaling removed by the round-3 restructure (rolled DMA fori) — first recompile stays quarantine-gated (hw-queue item 5)
        for h in range(num_kv_heads):
            kh = (
                unpack(k_buf, slot, h) * row_scales(ksc_buf, slot, h)
            ).astype(q.dtype)  # [chunk, D]
            s = jax.lax.dot_general(
                q[h], kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if logits_soft_cap > 0.0:
                s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
            ss.append(jnp.where(valid, s, _NEG_INF))
        s_all = jnp.stack(ss)  # [Hkv, Gp, chunk]
        m_cur = jnp.max(s_all, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p_all = jnp.where(valid[None], jnp.exp(s_all - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p_all, axis=-1, keepdims=True)
        for h in range(num_kv_heads):  # wedge-lint: ok bounded by num_kv_heads; see note above
            vh = (
                unpack(v_buf, slot, h) * row_scales(vsc_buf, slot, h)
            ).astype(q.dtype)
            pvs.append(jax.lax.dot_general(
                p_all[h].astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        pv = jnp.stack(pvs)
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((num_kv_heads, gp, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((num_kv_heads, gp, 1), jnp.float32)
    acc0 = jnp.zeros((num_kv_heads, gp, head_dim), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))

    l_safe = jnp.where(l > 0.0, l, 1.0)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(l), _NEG_INF)
    lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape).astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "sm_scale", "logits_soft_cap", "window_left", "pages_per_chunk",
        "return_lse",
    ),
)
def fp4_paged_decode_attention(
    q: jax.Array,  # [batch, num_qo_heads, head_dim]
    k4: jax.Array,  # [num_pages, Hkv, PS//2, D] int8 token-pair nibbles
    ksc: jax.Array,  # [num_pages, 128] f32
    v4: jax.Array,
    vsc: jax.Array,
    page_table: jax.Array,  # [batch, max_pages] int32 (padded, valid ids)
    kv_lens: jax.Array,  # [batch] int32
    *,
    sm_scale: float = 1.0,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    pages_per_chunk: int = 8,
    return_lse: bool = False,
):
    """Batched paged decode over a 4-bit token-pair-packed KV cache."""
    batch, num_qo_heads, head_dim = q.shape
    num_pages, num_kv_heads, half_ps, _ = k4.shape
    page_size = half_ps * 2
    group = num_qo_heads // num_kv_heads
    gp = round_up(group, 8)

    p_padded = round_up(page_table.shape[1], pages_per_chunk)
    if p_padded != page_table.shape[1]:
        page_table = jnp.pad(
            page_table, ((0, 0), (0, p_padded - page_table.shape[1]))
        )
    qg = q.reshape(batch, num_kv_heads, group, head_dim)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    kernel = functools.partial(
        _fp4_decode_kernel,
        page_size=page_size, ppc=pages_per_chunk, sm_scale=sm_scale,
        logits_soft_cap=logits_soft_cap, window_left=window_left,
        num_kv_heads=num_kv_heads,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec(
                (None, num_kv_heads, gp, head_dim), lambda b, *_: (b, 0, 0, 0)
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, num_kv_heads, gp, head_dim), lambda b, *_: (b, 0, 0, 0)
            ),
            pl.BlockSpec(
                (None, num_kv_heads, gp, 128), lambda b, *_: (b, 0, 0, 0)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM(
                (2, pages_per_chunk, num_kv_heads, half_ps, head_dim),
                k4.dtype,
            ),
            pltpu.VMEM((2, pages_per_chunk, 128), ksc.dtype),
            pltpu.VMEM(
                (2, pages_per_chunk, num_kv_heads, half_ps, head_dim),
                v4.dtype,
            ),
            pltpu.VMEM((2, pages_per_chunk, 128), vsc.dtype),
            pltpu.SemaphoreType.DMA((2, 4, pages_per_chunk)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(
                (batch, num_kv_heads, gp, head_dim), q.dtype
            ),
            jax.ShapeDtypeStruct((batch, num_kv_heads, gp, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=use_interpret(),
    )(
        page_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
        qg, k4, ksc, v4, vsc,
    )
    out = out[:, :, :group, :].reshape(batch, num_qo_heads, head_dim)
    if return_lse:
        return out, lse[:, :, :group, 0].reshape(batch, num_qo_heads)
    return out
