"""Block-selected attention over paged K/V (InfLLM-v2, as MiniCPM4 and
MiniCPM-SALA run it): block scores from a cache of compressed keys, the exact
top-k of blocks per query token and K/V head, and grouped-query attention
that reads through the page table ONLY the pages of blocks some row of its
tile selected.

- :func:`compressed_block_scores`: per query token and K/V head, the
  softmax over the cached COMPRESSED keys (the mean key of ``kernel_size``
  positions, one every ``stride``) a query head, summed over the group's
  heads, max-pooled to blocks of ``block_size`` positions; the first
  ``init_blocks`` and the ``local_blocks`` newest are forced.  Plain XLA,
  float32.
- :func:`select_blocks`: the ``topk`` highest blocks as a mask, ties to the
  lower block (``ops/sparse_mla.py selection_mask`` holds them).
- :func:`tile_unions`: for tiles of a few adjacent query tokens of one
  request, the ascending list of blocks some row of the tile selected, and
  which of them each row selected.
- :func:`block_sparse_paged_attention`, the Pallas kernel
  (``name="block_sparse_paged_attn"``): grid ``(K/V heads, tiles)``; a tile
  is ``tile`` query tokens x the group's heads (the MXU's rows); its K/V
  operand is the page pool in HBM, and its pages are named by the
  scalar-prefetched union list through the request's page-table row: chunks
  of ``chunk_blocks`` blocks are copied page by page into a double buffer,
  online softmax in float32 under a per-token bias (0 where the token
  selected the block and the position is causal).  It reads no page outside
  the union.

The pool is ``[pages, Hkv, page_size, 2 D]``: a position's key and value of
one K/V head lie side by side, so one copy of ``[page_size, 2 D]`` brings
both (a copy has a cost of its own, whatever it moves).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.ops.sparse_mla import selection_mask
from flashinfer_tpu.utils import use_interpret

_NEG_INF = -1e30        # a masked logit (finite)
_CHUNK_PAGES_CAP = 64   # page copies a chunk: what the kernel unrolls


def compressed_block_scores(q, kc, pos, *, stride: int, kernel_size: int,
                            block_size: int, init_blocks: int,
                            local_blocks: int, sm_scale: float):
    """``q`` ``[Q, Hq, D]``, ``kc`` ``[C, Hkv, D]`` (the request's compressed
    keys ``c = 0 .. C - 1``; ``c`` covers positions ``stride c .. stride c +
    kernel_size - 1``), ``pos`` ``[Q]`` -> float32 ``[Q, Hkv, C // (block_size
    // stride)]``: the block scores, ``+inf`` where forced, ``-inf`` for
    blocks past the query's own."""
    n_q, hq, d = q.shape
    n_c, hkv, _ = kc.shape
    per = block_size // stride
    s = jnp.einsum("qngd,cnd->qngc", q.reshape(n_q, hkv, hq // hkv, d), kc,
                   preferred_element_type=jnp.float32) * sm_scale
    valid = (jnp.arange(n_c) * stride + kernel_size - 1)[None] <= pos[:, None]
    live = valid[:, None, None]
    m = jnp.max(jnp.where(live, s, -jnp.inf), -1, keepdims=True)
    e = jnp.where(live, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    l = jnp.sum(e, -1, keepdims=True)
    sc = jnp.sum(e / jnp.where(l > 0, l, 1.0), 2)          # [Q, Hkv, C]
    sc = jnp.where(valid[:, None], sc, -jnp.inf)
    nb = n_c // per
    # block b is touched by c = per b - 1 .. per b + per - 1
    inside = jnp.max(sc[..., :nb * per].reshape(n_q, hkv, nb, per), -1)
    before = jnp.concatenate(
        [jnp.full((n_q, hkv, 1), -jnp.inf, jnp.float32),
         sc[..., per - 1::per][..., :nb - 1]], -1)
    b = jnp.arange(nb)[None]
    own = (pos // block_size)[:, None]
    forced = (b < init_blocks) | (b > own - local_blocks)
    out = jnp.where(forced[:, None], jnp.inf, jnp.maximum(inside, before))
    return jnp.where((b <= own)[:, None], out, -jnp.inf)


def select_blocks(scores, topk: int):
    """bool ``[..., NB]``: the ``topk`` highest of each row of block scores,
    ties to the lower block; a row with at most ``topk`` blocks keeps all."""
    vals, idx = jax.lax.top_k(scores, min(topk, scores.shape[-1]))
    return selection_mask(scores, idx, vals > -jnp.inf)


def union_cap(tile: int, *, topk: int, init_blocks: int, local_blocks: int,
              blocks: int, chunk_blocks: int) -> int:
    """The most blocks a tile of ``tile`` adjacent query tokens of one
    request selects together (a multiple of ``chunk_blocks``): the forced
    blocks are shared but for one block boundary inside the tile, the others
    may all differ; never more than a request has."""
    forced = init_blocks + local_blocks
    most = topk if tile == 1 else max(
        topk + 1, forced + 1 + tile * max(topk - forced, 0))
    return -(-min(most, blocks) // chunk_blocks) * chunk_blocks


def tile_unions(mask, live, *, tile: int, cap: int):
    """``mask`` bool ``[U, Hkv, NB]`` (:func:`select_blocks` of ``U`` rows of
    one request, ``U`` a multiple of ``tile``), ``live`` ``[U]`` -> ``(union
    [Hkv, tiles, cap] int32, count [Hkv, tiles] int32, hit [Hkv, tiles, tile,
    cap] bool)``: per tile of ``tile`` rows and K/V head the blocks some live
    row selected, ascending (entries past ``count`` name block 0 and nobody
    hits them), and which of them each row selected."""
    n_u, hkv, nb = mask.shape
    rows = (mask & live[:, None, None]).reshape(n_u // tile, tile, hkv, nb)
    rows = jnp.transpose(rows, (2, 0, 1, 3))
    member = jnp.any(rows, 2)
    vals, union = jax.lax.top_k(
        jnp.where(member, nb - jnp.arange(nb), 0), min(cap, nb))
    pad = cap - union.shape[-1]
    if pad:
        vals, union = (jnp.pad(v, ((0, 0), (0, 0), (0, pad)))
                       for v in (vals, union))
    found = vals > 0
    union = jnp.where(found, union, 0).astype(jnp.int32)
    # hit[.., r, u] = rows[.., r, union[.., u]] as a product with the
    # union's one-hot matrix (exact: zeros and ones): a gather of single
    # elements costs nanoseconds each on the chip
    onehot = union[:, :, None, :] == jnp.arange(nb)[:, None]
    hit = jnp.einsum("gtrb,gtbu->gtru", rows.astype(jnp.bfloat16),
                     onehot.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32) > 0.5
    return (union, jnp.sum(found, -1).astype(jnp.int32),
            hit & found[:, :, None, :])


def selection_bias(union, hit, pos, *, block_size: int):
    """float32 ``[Hkv, tiles, tile, cap x block_size]``: 0 where the row
    selected the union's block and the position is at or before the row's
    own (``pos`` ``[tiles, tile]``), ``_NEG_INF`` elsewhere."""
    at = union[:, :, None, :, None] * block_size + jnp.arange(block_size)
    ok = hit[..., None] & (at <= pos[None, :, :, None, None])
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32).reshape(
        *hit.shape[:3], -1)


def _block_sparse_paged_kernel(
        table_ref, row_ref, union_ref, count_ref,       # scalar prefetch
        q_ref, bias_ref, kv_hbm, o_ref, buf, sem, *,
        sm_scale: float, tiles: int, cap: int, chunk_blocks: int,
        block_pages: int, page_size: int, head_dim: int):
    g, t = pl.program_id(0), pl.program_id(1)
    gt = g * tiles + t
    row = row_ref[gt]
    n_chunks = pl.cdiv(count_ref[gt], chunk_blocks)
    tokens = bias_ref.shape[0]
    rows = q_ref.shape[0]
    chunk = chunk_blocks * block_pages * page_size      # positions a chunk

    def page_dmas(c, slot):
        dmas = []
        for k in range(chunk_blocks):  # wedge-lint: ok chunk_blocks x block_pages <= _CHUNK_PAGES_CAP copies a chunk (asserted by the wrapper)
            b = union_ref[gt * cap + c * chunk_blocks + k]
            for i in range(block_pages):  # wedge-lint: ok same clamp: the two loops unroll to at most _CHUNK_PAGES_CAP copies
                j = k * block_pages + i
                page = table_ref[row, b * block_pages + i]
                dmas.append(pltpu.make_async_copy(
                    kv_hbm.at[page, g],
                    buf.at[slot, pl.ds(j * page_size, page_size), :],
                    sem.at[slot, j]))
        return dmas

    q = q_ref[...]

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _prefetch():
            for dma in page_dmas(c + 1, 1 - slot):
                dma.start()

        for dma in page_dmas(c, slot):
            dma.wait()
        kv = buf[slot]
        s = jax.lax.dot_general(
            q, kv[:, :head_dim], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # [rows, chunk]
        bias = bias_ref[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)]
        # the bias is per token: one row of it serves the group's heads
        s = (s.reshape(tokens, -1, chunk) + bias[:, None, :]).reshape(s.shape)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        # a masked logit is exactly _NEG_INF, so its p is 0 under any real
        # maximum; before a row meets a selected position its p are 1 and
        # the first real maximum's alpha = exp(_NEG_INF - m) = 0 wipes them
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        pv = jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, head_dim:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (m_new, alpha * l + jnp.sum(p, -1, keepdims=True),
                acc * alpha + pv)

    @pl.when(n_chunks > 0)
    def _warm():
        for dma in page_dmas(0, 0):
            dma.start()

    m, l, acc = jax.lax.fori_loop(
        0, n_chunks, body,
        (jnp.full((rows, 1), _NEG_INF, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, head_dim), jnp.float32)))
    # a row that met no selected position (a dead row of the tile) has only
    # masked logits under m = _NEG_INF: its sum is positions, not weights
    o_ref[...] = jnp.where(m > _NEG_INF, acc / jnp.where(l > 0, l, 1.0), 0.0)


def block_sparse_paged_attention(q, kv_pool, table, tile_row, union, count,
                                 bias, *, block_pages: int,
                                 chunk_blocks: int, sm_scale: float):
    """``q`` ``[Hkv, tiles, tile x G, D]`` (a tile's query tokens x the
    group's heads, token-major), ``kv_pool`` ``[pages, Hkv, page_size, 2 D]``
    (key | value), ``table`` int32 ``[R, W]`` page lists, ``tile_row`` ``[Hkv,
    tiles]`` each tile's row of ``table``, ``union`` ``[Hkv, tiles, cap]``
    block ids into that row (block ``b`` = its entries ``b x block_pages
    ..``), ``count`` ``[Hkv, tiles]`` how many are real, ``bias`` float32
    ``[Hkv, tiles, tile, cap x block positions]`` (:func:`selection_bias`) ->
    float32 ``[Hkv, tiles, tile x G, D]``: per token and head the softmax of
    ``q . k * sm_scale`` over the positions its bias admits, times ``v``.  A
    tile with ``count`` 0 reads nothing and gives zeros."""
    hkv, tiles, rows, d = q.shape
    _pages, _, page_size, two_d = kv_pool.shape
    assert two_d == 2 * d, (kv_pool.shape, q.shape)
    cap = union.shape[-1]
    assert cap % chunk_blocks == 0, (cap, chunk_blocks)
    assert chunk_blocks * block_pages <= _CHUNK_PAGES_CAP, (
        chunk_blocks, block_pages)
    chunk = chunk_blocks * block_pages * page_size
    tokens = bias.shape[2]
    kernel = functools.partial(
        _block_sparse_paged_kernel, sm_scale=sm_scale, tiles=tiles, cap=cap,
        chunk_blocks=chunk_blocks, block_pages=block_pages,
        page_size=page_size, head_dim=d)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(hkv, tiles),
            in_specs=[
                pl.BlockSpec((None, None, rows, d),
                             lambda g, t, *_: (g, t, 0, 0)),
                pl.BlockSpec((None, None, tokens, bias.shape[-1]),
                             lambda g, t, *_: (g, t, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, None, rows, d),
                                   lambda g, t, *_: (g, t, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, two_d), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((2, chunk_blocks * block_pages)),
            ]),
        out_shape=jax.ShapeDtypeStruct((hkv, tiles, rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="block_sparse_paged_attn",
        interpret=use_interpret(),
    )(table.astype(jnp.int32), tile_row.reshape(-1).astype(jnp.int32),
      union.reshape(-1).astype(jnp.int32),
      count.reshape(-1).astype(jnp.int32), q, bias, kv_pool)
