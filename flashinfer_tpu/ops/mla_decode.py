"""Paged MLA (multi-latent attention) decode Pallas kernel.

TPU re-design of the reference MLA decode path
(``include/flashinfer/attention/mla.cuh:853`` BatchMLAPagedAttentionKernel,
CUDA-core variant decode.cuh:893): DeepSeek MLA caches a per-token
*compressed* KV — ``ckv`` (latent, head_dim_ckv=512) + ``kpe`` (RoPE part,
head_dim_kpe=64) — shared across all query heads (MQA-shaped).  Scores are
``q_nope . ckv + q_pe . kpe`` and values are the ckv latents themselves.

Kernel consequences vs the GQA decode kernel (ops/paged_decode.py):
- num_kv_heads == 1; ALL query heads form one MXU tile.
- Two autotunable scratch layouts (``mla_decode.layout`` tactic):
  "split" streams ckv and kpe into separate double-buffered VMEM
  buffers and sums two MXU score dots; "packed" exploits the
  lane-padded kpe cache (d_kpe 64 -> 128) to share one
  [chunk, d_ckv + 128] buffer — both DMA destination lane slices
  (0:512, 512:640) are 128-aligned, which a raw [chunk, 576] packing
  would violate — and collapses the scores to ONE concatenated dot.
  Either way the V matrix is the ckv lanes of the buffer itself — no
  separate V DMA, matching the reference's read-ckv-once trick.

Cache layout: ckv ``[num_pages, page_size, head_dim_ckv]``,
kpe ``[num_pages, page_size, head_dim_kpe]`` (reference MLA page layout).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import round_up, use_interpret

_NEG_INF = -1e30


def _mla_decode_kernel(
    pages_ref,  # [B, P] scalar prefetch
    kvlen_ref,  # [B]
    *refs,  # layout-dependent: see unpacking below
    page_size: int,
    ppc: int,
    d_ckv: int,
    sm_scale: float,
    packed: bool,
):
    """One kernel body, two scratch layouts (static ``packed``):

    - split (packed=False): refs = (qn_ref [Hp, d_ckv], qp_ref
      [Hp, d_kpe_pad], ckv_hbm, kpe_hbm, o_ref, lse_ref,
      ckv_buf [2, chunk, d_ckv], kpe_buf [2, chunk, d_kpe_pad], sem).
      Two score dots summed.
    - packed (packed=True): refs = (qc_ref [Hp, d_ckv + d_kpe_pad],
      ckv_hbm, kpe_hbm, o_ref, lse_ref,
      kv_buf [2, chunk, d_ckv + d_kpe_pad], sem).  ckv and the
      LANE-PADDED kpe share one buffer — both DMA destination lane
      slices (0:d_ckv and d_ckv:) are 128-aligned because d_ckv and
      d_kpe_pad are multiples of 128 (a raw [chunk, 576] packing is what
      Mosaic rejects) — and the scores collapse to ONE MXU dot over the
      concatenated axis; V is the buffer's first d_ckv lanes.  Same DMA
      count and queue depth as split.

    Everything else (double-buffered page DMAs, online softmax, lse
    epilogue) is shared — the layouts cannot drift apart.
    """
    if packed:
        qc_ref, ckv_hbm, kpe_hbm, o_ref, lse_ref, kv_buf, sem = refs
    else:
        (qn_ref, qp_ref, ckv_hbm, kpe_hbm, o_ref, lse_ref,
         ckv_buf, kpe_buf, sem) = refs
    b = pl.program_id(0)
    kv_len = kvlen_ref[b]
    chunk_tokens = ppc * page_size
    num_chunks = pl.cdiv(kv_len, chunk_tokens)

    def chunk_dmas(chunk_idx, slot):
        dmas = []
        for j in range(ppc):  # wedge-lint: ok ppc clamped <= 16 at call site; 2 DMAs/page, on-chip-validated queue depth
            page = pages_ref[b, chunk_idx * ppc + j]
            rows = pl.ds(j * page_size, page_size)
            if packed:
                d_pad = kv_buf.shape[-1]
                ckv_dst = kv_buf.at[slot, rows, pl.ds(0, d_ckv)]
                kpe_dst = kv_buf.at[slot, rows, pl.ds(d_ckv, d_pad - d_ckv)]
            else:
                ckv_dst = ckv_buf.at[slot, rows]
                kpe_dst = kpe_buf.at[slot, rows]
            dmas.append(pltpu.make_async_copy(
                ckv_hbm.at[page], ckv_dst, sem.at[slot, 0, j]))
            dmas.append(pltpu.make_async_copy(
                kpe_hbm.at[page], kpe_dst, sem.at[slot, 1, j]))
        return dmas

    def start_chunk(i, slot):
        for d in chunk_dmas(i, slot):
            d.start()

    def wait_chunk(i, slot):
        for d in chunk_dmas(i, slot):
            d.wait()

    @pl.when(num_chunks > 0)
    def _warmup():
        start_chunk(0, 0)

    # q operands are pre-scaled by sm_scale on the host
    if packed:
        qc = qc_ref[...]
        hp = qc.shape[0]
    else:
        qn = qn_ref[...]
        qp = qp_ref[...]
        hp = qn.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < num_chunks)
        def _prefetch():
            start_chunk(i + 1, jax.lax.rem(i + 1, 2))

        wait_chunk(i, slot)
        if packed:
            kv = kv_buf[slot]  # [chunk, d_ckv + d_kpe_pad]
            v = kv[:, :d_ckv]
            s = jax.lax.dot_general(
                qc, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Hp, chunk] — q_pe pad columns are zero, contribute nothing
        else:
            ckv = ckv_buf[slot]  # [chunk, d_ckv]
            kpe = kpe_buf[slot]  # [chunk, d_kpe_pad]
            v = ckv
            s = jax.lax.dot_general(
                qn, ckv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                qp, kpe, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [Hp, chunk]
        tok = i * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_tokens), 1
        )
        valid = tok < kv_len
        s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        # V is ckv itself — no second value fetch
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((hp, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((hp, 1), jnp.float32)
    acc0 = jnp.zeros((hp, d_ckv), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_chunks, body, (m0, l0, acc0))

    l_safe = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(l), _NEG_INF)
    lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "pages_per_chunk", "return_lse", "layout"),
)
def mla_paged_decode_attention(
    q_nope: jax.Array,  # [batch, num_heads, head_dim_ckv]
    q_pe: jax.Array,  # [batch, num_heads, head_dim_kpe]
    ckv_cache: jax.Array,  # [num_pages, page_size, head_dim_ckv]
    kpe_cache: jax.Array,  # [num_pages, page_size, head_dim_kpe]
    page_table: jax.Array,  # [batch, max_pages]
    kv_lens: jax.Array,  # [batch]
    *,
    sm_scale: float,
    pages_per_chunk: Optional[int] = None,
    return_lse: bool = False,
    layout: str = "split",
):
    batch, num_heads, d_ckv = q_nope.shape
    d_kpe = q_pe.shape[-1]
    page_size = ckv_cache.shape[1]
    hp = max(round_up(num_heads, 8), 8)

    # Mosaic page-DMAs need 128-aligned lane widths: the TPU-native kpe
    # cache layout is lane-padded to 128 (store it that way — e.g. via
    # page.append_paged_mla_kv_cache — to avoid this copy); q_pe's zero
    # padding makes the pad columns contribute nothing to the scores.
    d_kpe_pad = max(round_up(d_kpe, 128), 128)
    if kpe_cache.shape[-1] != d_kpe_pad:
        kpe_cache = jnp.pad(
            kpe_cache, ((0, 0), (0, 0), (0, d_kpe_pad - kpe_cache.shape[-1]))
        )
    if q_pe.shape[-1] != d_kpe_pad:
        q_pe = jnp.pad(q_pe, ((0, 0), (0, 0), (0, d_kpe_pad - d_kpe)))

    if pages_per_chunk is None:
        pages_per_chunk = max(1, min(256 // page_size, 16))
    max_pages = page_table.shape[1]
    p_padded = round_up(max_pages, pages_per_chunk)
    if p_padded != max_pages:
        page_table = jnp.pad(page_table, ((0, 0), (0, p_padded - max_pages)))

    # fold sm_scale into q halves (cheap host-side)
    qn = (q_nope.astype(jnp.float32) * sm_scale).astype(ckv_cache.dtype)
    qp = (q_pe.astype(jnp.float32) * sm_scale).astype(ckv_cache.dtype)
    if hp != num_heads:
        qn = jnp.pad(qn, ((0, 0), (0, hp - num_heads), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, hp - num_heads), (0, 0)))

    chunk_tokens = pages_per_chunk * page_size
    if layout == "packed":
        # one [chunk, d_ckv + d_kpe_pad] buffer, one score dot (see
        # _mla_decode_kernel packed=True); q halves concatenate on host
        q_operands = (jnp.concatenate([qn, qp], axis=-1),)
        q_specs = [
            pl.BlockSpec((None, hp, d_ckv + d_kpe_pad),
                         lambda b, *_: (b, 0, 0)),
        ]
        kv_scratch = [
            pltpu.VMEM((2, chunk_tokens, d_ckv + d_kpe_pad),
                       ckv_cache.dtype),
        ]
    elif layout == "split":
        q_operands = (qn, qp)
        q_specs = [
            pl.BlockSpec((None, hp, d_ckv), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((None, hp, d_kpe_pad), lambda b, *_: (b, 0, 0)),
        ]
        kv_scratch = [
            pltpu.VMEM((2, chunk_tokens, d_ckv), ckv_cache.dtype),
            pltpu.VMEM((2, chunk_tokens, d_kpe_pad), ckv_cache.dtype),
        ]
    else:
        raise ValueError(f"layout must be 'split' or 'packed', got {layout!r}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=q_specs + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((None, hp, d_ckv), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((None, hp, 128), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=kv_scratch + [
            pltpu.SemaphoreType.DMA((2, 2, pages_per_chunk)),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _mla_decode_kernel,
            page_size=page_size,
            ppc=pages_per_chunk,
            d_ckv=d_ckv,
            sm_scale=sm_scale,
            packed=(layout == "packed"),
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, hp, d_ckv), q_nope.dtype),
            jax.ShapeDtypeStruct((batch, hp, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
        interpret=use_interpret(),
    )(page_table.astype(jnp.int32), kv_lens.astype(jnp.int32), *q_operands,
      ckv_cache, kpe_cache)

    out = out[:, :num_heads]
    if return_lse:
        return out, lse[:, :num_heads, 0]
    return out


@functools.partial(jax.jit, static_argnames=("sm_scale", "return_lse"))
def xla_mla_paged_decode(
    q_nope, q_pe, ckv_cache, kpe_cache, page_table, kv_lens,
    *, sm_scale: float, return_lse: bool = False,
):
    """Dense-gather XLA reference for MLA decode."""
    batch, H, d_ckv = q_nope.shape
    page_size = ckv_cache.shape[1]
    max_kv = page_table.shape[1] * page_size
    ckv = ckv_cache[page_table].reshape(batch, max_kv, d_ckv).astype(jnp.float32)
    kpe = kpe_cache[page_table].reshape(batch, max_kv, -1).astype(jnp.float32)
    kpe = kpe[..., : q_pe.shape[-1]]  # drop TPU lane padding if present
    # HIGHEST: TPU's default matmul precision may run f32 einsums through
    # reduced-precision MXU passes — not acceptable in a correctness
    # oracle (see ops/xla_ref.py)
    prec = jax.lax.Precision.HIGHEST
    s = (
        jnp.einsum("bhd,bkd->bhk", q_nope.astype(jnp.float32), ckv,
                   precision=prec)
        + jnp.einsum("bhd,bkd->bhk", q_pe.astype(jnp.float32), kpe,
                     precision=prec)
    ) * sm_scale
    mask = jnp.arange(max_kv)[None, :] < kv_lens[:, None]
    s = jnp.where(mask[:, None], s, _NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.where(mask[:, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhk,bkd->bhd", p / jnp.where(l > 0, l, 1.0), ckv,
                     precision=prec)
    out = out.astype(q_nope.dtype)
    if return_lse:
        lse = jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(l[..., 0]), _NEG_INF)
        return out, lse
    return out
