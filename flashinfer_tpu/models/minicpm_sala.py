"""The ``minicpm_sala`` block (MiniCPM-SALA) for the serving engine:
lightning linear-attention layers (a ``[heads, d, d]`` matrix state a
request, constant decay a head) beside InfLLM-v2 block-sparse GQA layers (a
cache of compressed keys, 64 blocks of 64 positions a query token a K/V
head), a dense SwiGLU MLP in every layer, muP-scaled ends and residuals.

This module is the model's side of the engine's seam (``serve/engine.py``,
"The model's seam"; ``serve/block_step.py``).  A lightning layer is of the
kind ``"recurrent"``: the engine keeps its state in the request's slot
(:meth:`MiniCPMSalaConfig.new_recurrent_state`).  A sparse layer is of the
kind ``"latent"``: the model's own paged arrays on the request's one page
list (:meth:`MiniCPMSalaConfig.new_latent_pages`: K|V rows ``[pages, Hkv,
page_size, 2 D]`` and the compressed keys, one row a page), written through
``ctx.append``, attended by the layer itself.  No GQA work unit is planned or
launched by the engine.  The latent kind carries the sparse layers as it is:
its contract is "the model's paged arrays, the page table, an append", and a
K/V pool with a head axis is one of the two shapes ``serve/kv_write.py
append_pages`` writes.

Equations (``n`` an RMSNorm with its own weight, no biases; bf16 weights and
activations; the matrix state, the decays, the compressed scores and every
softmax in float32; ``r = scale_depth / sqrt(L_pub)``, ``L_pub`` the PUBLISHED
depth whatever is held here):

- ends: ``x = scale_emb E[tok]``; ``logits = W_head (n(x_L) / (hidden /
  dim_model_base))``;
- layer: ``x <- x + r mixer(n(x))``; ``x <- x + r W_down(silu(W_gate u) *
  W_up u)``, ``u = n(x)``;
- lightning: ``[q | k | v | g] = h W_qkvg``; per-head RMSNorm of ``q`` and
  ``k``; both rotated (rotate-half, theta ``rope_theta``) at the token's
  position; ``S_t = lambda_h S_{t-1} + k_t^T v_t``; ``o_t = (q_t / sqrt(d))
  S_t``; ``o = n_hidden(o) * sigmoid(g)``; out ``o W_o``.  ``lambda_h =
  exp(-s_h f_l)``, ``s_h = 2^(-8 (h + 1) / H)``, ``f_l = 1 - l / (L_pub - 1)
  + 1e-5`` with ``l`` the layer's PUBLISHED index (Lightning Attention-2);
- sparse: ``[q | k | v | g] = h W_qkvg`` (``k``, ``v`` of the K/V heads);
  per-head RMSNorm of ``q`` and ``k``; NO rotation.  Compressed key ``kc_c``
  = the mean of ``k_j`` over ``stride c <= j < stride c + kernel_size``,
  there once position ``stride c + kernel_size - 1`` is cached.  Block scores
  and selection as ``ops/block_sparse_paged.py`` says; softmax over the
  positions ``j <= t`` of the selected blocks, scale ``1 / sqrt(d)``; ``o *
  sigmoid(g)``; out ``o W_o``.

Under continuous batching (``serve/recurrent.py``'s plan): one-token segments
(decode lanes) go all at once, each against its own slot and page list;
longer segments are cut into units of at most ``scan_chunk`` rows of one
request, which one ``fori_loop`` a layer with a dynamic bound runs.  A
lightning unit is chunked linear attention, all matrix products: ``(q
lambda^(i+1)) S`` from the carried state, ``((q k^T) * D) v`` inside the
unit (``D_ij = lambda^(i-j)``, ``j <= i``), ``S <- lambda^n S + (k
lambda^(n-1-j))^T v``.  A sparse unit scores its rows against the request's
compressed keys (as wide as the unit's last position needs), selects, and
runs the paged kernel over tiles of ``tile_tokens`` query tokens.  Shapes
depend on the rung alone: one trace per rung.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

_PUBLISHED_MIXERS = tuple(
    "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    """MiniCPM-SALA as published; ``mixer_types`` and
    ``layer_index_published`` say which layers are held here."""

    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    mixer_types: Tuple[str, ...] = _PUBLISHED_MIXERS
    layer_index_published: Tuple[int, ...] = tuple(range(32))
    num_hidden_layers_published: int = 32
    num_qo_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # the MiniCPM4 family's sparse_config (InfLLM-v2)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    rms_eps: float = 1e-6
    dtype: object = jnp.bfloat16
    state_dtype: object = jnp.float32
    scan_chunk: int = 128     # rows of one work unit (both kinds of layer)
    tile_tokens: int = 8      # query tokens of one tile of the paged kernel
    chunk_blocks: int = 8     # blocks the kernel copies and multiplies at once

    # -- sizes ------------------------------------------------------------

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / float(self.num_hidden_layers_published) ** 0.5

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def block_pages(self) -> int:
        return self.block_size // self.kernel_stride

    def log_decay(self, li: int):
        """float32 ``[heads]``: ``log lambda_h`` of held layer ``li``."""
        h = self.lightning_nh
        slope = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
        f = 1.0 - self.layer_index_published[li] / (
            self.num_hidden_layers_published - 1) + 1e-5
        return -slope * f

    # -- the engine's seam --------------------------------------------------

    attention_rope = False       # the paged layers do not rotate

    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("latent" if t == "minicpm4" else "recurrent"
                     for t in self.mixer_types)

    def new_recurrent_state(self, slots: int):
        """``S`` ``[slots, heads, d, d]`` of one lightning layer, zero."""
        d = self.lightning_head_dim
        return (jnp.zeros((slots, self.lightning_nh, d, d),
                          self.state_dtype),)

    def new_latent_pages(self, num_pages: int, page_size: int):
        """(K|V rows ``[pages, Hkv, page_size, 2 D]``, compressed keys
        ``[pages, Hkv, D]``: ``kc_c`` lives with the request's page ``c``)
        of one sparse layer, zero."""
        if page_size != self.kernel_stride \
                or self.kernel_size != 2 * page_size \
                or self.block_size % page_size:
            raise ValueError(
                f"page_size {page_size} must be the compressed keys' stride "
                f"({self.kernel_stride}), half their kernel "
                f"({self.kernel_size}) and divide a block "
                f"({self.block_size}): a compressed key lives with a page")
        return (jnp.zeros((num_pages, self.num_kv_heads, page_size,
                           2 * self.head_dim), self.dtype),
                jnp.zeros((num_pages, self.num_kv_heads, self.head_dim),
                          self.dtype))

    def engine_embed(self, params, tokens):
        e = params["embed"][tokens].astype(jnp.float32)
        return (self.scale_emb * e).astype(self.dtype)

    def engine_head(self, params, x_last):
        from flashinfer_tpu.norm import rmsnorm

        xf = rmsnorm(x_last, params["final_norm"], self.rms_eps)
        xf = (xf.astype(jnp.float32)
              / (self.hidden_size / self.dim_model_base)).astype(self.dtype)
        return jnp.dot(xf, params["lm_head"],
                       preferred_element_type=jnp.float32)

    def engine_layer(self, li, x, layer, cache, ctx):
        if self.mixer_types[li] == "minicpm4":
            x, cache, counts = _sparse_mixer(self, x, layer, cache, ctx)
            ctx.aux.setdefault("sala", []).append(counts)
        else:
            x, cache = _lightning_mixer(self, li, x, layer, cache, ctx)
        x = _mlp(self, x, layer)
        if li == self.num_layers - 1:
            held = ctx.aux.pop("sala", [])
            ctx.aux["sala"] = jnp.concatenate(
                [_position_counts(self, ctx, len(held)),
                 sum(held) if held else jnp.zeros((3,), jnp.int32)])
        return x, cache

    def engine_facts(self, aux, tokens: int) -> dict:
        """One step's counters (:data:`_COUNTS`): the first four from the
        schedule alone, A SPARSE LAYER each (every one scores, selects and
        writes alike); the last four are the (row, block) pairs selected,
        the (row, block) pairs and the K/V rows the paged kernel's tiles
        read, and the tiles with work, summed over the sparse layers held
        and their K/V heads."""
        return dict(zip(_COUNTS, (int(v) for v in aux["sala"])))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: MiniCPMSalaConfig, mixer: str) -> dict:
    """{name: shape} of one layer's weights; linear weights ``[in, out]``,
    ``qkvg`` the four projections side by side (query, key, value, output
    gate), ``gate_up`` likewise."""
    h = cfg.hidden_size
    out = {"input_norm": (h,), "post_norm": (h,),
           "gate_up": (h, 2 * cfg.intermediate_size),
           "down": (cfg.intermediate_size, h)}
    if mixer == "minicpm4":
        q, kv = cfg.num_qo_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        out.update(qkvg=(h, 2 * q + 2 * kv), q_norm=(cfg.head_dim,),
                   k_norm=(cfg.head_dim,), o_proj=(q, h))
    else:
        inner = cfg.lightning_nh * cfg.lightning_head_dim
        out.update(qkvg=(h, 4 * inner), q_norm=(cfg.lightning_head_dim,),
                   k_norm=(cfg.lightning_head_dim,), o_norm=(inner,),
                   o_proj=(inner, h))
    return out


def init_weight(key, name: str, shape, dtype):
    """One weight from a key: linear N(0, 0.02^2); norm weights 1 + 0.1 N."""
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def init_minicpm_sala_params(key, cfg: MiniCPMSalaConfig) -> dict:
    """Random parameter tree: ``embed``, ``lm_head`` (untied),
    ``final_norm``, ``layers[i]`` as :func:`layer_shapes` lists."""
    def tree(key, shapes):
        names = sorted(shapes)
        return {n: init_weight(k, n, shapes[n], cfg.dtype)
                for n, k in zip(names, jax.random.split(key, len(names)))}

    out = tree(jax.random.fold_in(key, 1 << 20),
               {"embed": (cfg.vocab_size, cfg.hidden_size),
                "lm_head": (cfg.hidden_size, cfg.vocab_size),
                "final_norm": (cfg.hidden_size,)})
    out["layers"] = [tree(jax.random.fold_in(key, li), layer_shapes(cfg, t))
                     for li, t in enumerate(cfg.mixer_types)]
    return out


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------


def _residual(cfg, x, delta):
    return (x.astype(jnp.float32) + cfg.residual_scale
            * delta.astype(jnp.float32)).astype(cfg.dtype)


def _mlp(cfg, x, layer):
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.norm import rmsnorm

    with jax.named_scope("mlp"):
        u = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        return _residual(cfg, x, jnp.dot(
            silu_and_mul(u @ layer["gate_up"]), layer["down"],
            preferred_element_type=jnp.float32))


def _padded(v, rows):
    return jnp.pad(v, ((0, rows),) + ((0, 0),) * (v.ndim - 1))


def _lightning_mixer(cfg, li, x, layer, cache, ctx):
    from flashinfer_tpu.norm import qk_rmsnorm, rmsnorm
    from flashinfer_tpu.rope import apply_rope_pos_ids

    (state,) = cache
    t = x.shape[0]
    nh, d = cfg.lightning_nh, cfg.lightning_head_dim
    f32 = jnp.float32
    seg, work = ctx.plan["seg"], ctx.plan["work"]

    with jax.named_scope("ssm.in_proj"):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        q, k, v, g = jnp.split(h @ layer["qkvg"], 4, -1)

    with jax.named_scope("ssm.scan"):
        q, k = qk_rmsnorm(q.reshape(t, nh, d), k.reshape(t, nh, d),
                          layer["q_norm"], layer["k_norm"], cfg.rms_eps)
        q, k = apply_rope_pos_ids(q, k, ctx.positions,
                                  rope_theta=cfg.rope_theta)
        q = (q.astype(f32) * float(d) ** -0.5).astype(cfg.dtype)
        v = v.reshape(t, nh, d)
        log_lam = cfg.log_decay(li)                          # [heads]
        n_u = min(cfg.scan_chunk, t)
        y = jnp.zeros((t + n_u, nh, d), f32)

        # one-token segments: the scheduled slots alone, all at once
        one = seg[:, 1] == 1
        rows = jnp.clip(seg[:, 0], 0, t - 1)
        s0 = jnp.where((seg[:, 2] > 0)[:, None, None, None], 0.0,
                       state.astype(f32))
        s1 = jnp.exp(log_lam)[None, :, None, None] * s0 + jnp.einsum(
            "bhd,bhe->bhde", k[rows].astype(f32), v[rows].astype(f32))
        y1 = jnp.einsum("bhd,bhde->bhe", q[rows].astype(f32), s1)
        slots = state.shape[0]
        state = state.at[jnp.where(one, jnp.arange(slots), slots)].set(
            s1.astype(state.dtype), mode="drop")
        y = y.at[jnp.where(one, rows, t + n_u)].set(y1, mode="drop")

        # longer segments: work units of at most n_u rows, in order
        qp, kp, vp = (_padded(a, n_u) for a in (q, k, v))
        i = jnp.arange(n_u, dtype=f32)
        # D[h, i, j] = lambda^(i - j) on the causal triangle, masked inside
        # the exponent; q's and k's decays are at most 1: nothing divides
        gap = i[:, None] - i[None, :]
        decay = jnp.exp(jnp.where(gap >= 0, gap, jnp.inf)[None]
                        * log_lam[:, None, None])
        q_decay = jnp.exp((i + 1.0)[:, None] * log_lam[None])    # [n_u, h]

        def unit(w, carry):
            s_prev, y, state = carry
            r0, n, slot, first, last, fresh = (work[w, c] for c in range(6))
            s_slot = jax.lax.dynamic_index_in_dim(
                state, slot, 0, False).astype(f32)
            s_in = jnp.where(first > 0, jnp.where(fresh > 0, 0.0, s_slot),
                             s_prev)
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, n_u)
            live = (jnp.arange(n_u) < n)[:, None, None]
            qc, kc, vc = cut(qp), jnp.where(live, cut(kp), 0), cut(vp)
            inter = jnp.einsum(
                "hde,ihd->ihe", s_in.astype(cfg.dtype),
                (qc.astype(f32) * q_decay[..., None]).astype(cfg.dtype),
                preferred_element_type=f32)
            scores = jnp.einsum("ihd,jhd->hij", qc, kc,
                                preferred_element_type=f32) * decay
            intra = jnp.einsum("hij,jhe->ihe", scores.astype(cfg.dtype), vc,
                               preferred_element_type=f32)
            # the state after the unit's n rows (rows past n add nothing)
            k_decay = jnp.exp((n.astype(f32) - 1.0 - i)[:, None]
                              * log_lam[None])
            s_out = jnp.exp(n.astype(f32) * log_lam)[:, None, None] * s_in \
                + jnp.einsum(
                    "jhd,jhe->hde",
                    (kc.astype(f32) * k_decay[..., None]).astype(cfg.dtype),
                    vc, preferred_element_type=f32)
            y = jax.lax.dynamic_update_slice_in_dim(
                y, jnp.where(live, inter + intra, cut(y)), r0, 0)
            state = jax.lax.dynamic_update_index_in_dim(
                state, jnp.where(last > 0, s_out, s_slot).astype(state.dtype),
                slot, 0)
            return s_out, y, state

        _, y, state = jax.lax.fori_loop(
            0, ctx.plan["n_work"], unit,
            (jnp.zeros(state.shape[1:], f32), y, state))
        o = rmsnorm(y[:t].reshape(t, nh * d).astype(cfg.dtype),
                    layer["o_norm"], cfg.rms_eps)
        o = (o.astype(f32) * jax.nn.sigmoid(g.astype(f32))).astype(cfg.dtype)

    with jax.named_scope("ssm.out"):
        x = _residual(cfg, x, jnp.dot(o, layer["o_proj"],
                                      preferred_element_type=f32))
    return x, (state,)


_COUNTS = ("attn_pairs_causal", "attn_pairs_selected", "index_rows_scored",
           "kc_rows_written", "attn_blocks_selected", "attn_blocks_read",
           "kv_rows_read", "attn_tiles")


def _position_counts(cfg, ctx, sparse_layers):
    """int32 ``[5]``, the first five of :data:`_COUNTS`: of ONE sparse layer
    of this step the sums over the step's query tokens of the context
    ``t + 1`` and of the positions ``j <= t`` inside the token's selected
    blocks (all while it has at most ``topk`` blocks; then ``topk - 1`` whole
    blocks and its own, which is forced, up to ``t``), the compressed keys
    the step's requests hold (each is scored) and those the step completes;
    and ``min(blocks, topk)`` a query token, K/V head and sparse layer."""
    pos, req = ctx.positions, ctx.tok_req
    real = jnp.arange(pos.shape[0]) < ctx.plan["n_tokens"]
    bs, k = cfg.block_size, cfg.topk
    blocks = pos // bs + 1
    chosen = jnp.where(blocks <= k, pos + 1, (k - 1) * bs + pos % bs + 1)
    slots = ctx.page_table.shape[0]
    held = jnp.zeros((slots,), jnp.int32).at[req].max(
        jnp.where(real, pos + 1, 0))
    kcs = jnp.maximum((held - cfg.kernel_size) // cfg.kernel_stride + 1, 0)
    return jnp.stack([
        jnp.sum(jnp.where(real, pos + 1, 0)),
        jnp.sum(jnp.where(real, chosen, 0)), jnp.sum(kcs),
        jnp.sum(real & _completes(cfg, pos)),
        sparse_layers * cfg.num_kv_heads * jnp.sum(jnp.where(
            real, jnp.minimum(blocks, k), 0))]).astype(jnp.int32)


def _completes(cfg, pos):
    """The rows whose position completes a compressed key's window."""
    return ((pos + 1) % cfg.kernel_stride == 0) \
        & (pos >= cfg.kernel_size - 1)


def _write_compressed(cfg, kv, kc, ctx):
    """The compressed keys this step completes: a row at position ``p`` with
    ``p + 1`` a multiple of the stride and ``p >= kernel_size - 1`` completes
    ``c = (p + 1) / stride - 2``, the mean of the K rows of the request's
    pages ``c`` and ``c + 1`` (read from the pool, AFTER the step's rows were
    appended: a chunk needs its request's previous page), written with page
    ``c``."""
    pos, req = ctx.positions, ctx.tok_req
    t, ps = pos.shape[0], ctx.page_size
    real = jnp.arange(t) < ctx.plan["n_tokens"]
    cap = t // ps + ctx.page_table.shape[0]
    (at,) = jnp.nonzero(real & _completes(cfg, pos), size=cap, fill_value=t)
    row = jnp.clip(at, 0, t - 1)
    c = jnp.maximum((pos[row] + 1) // ps - 2, 0)
    first = ctx.page_table[req[row], c]
    second = ctx.page_table[req[row], c + 1]
    d = cfg.head_dim
    both = jnp.concatenate([kv[first][..., :d], kv[second][..., :d]], 2)
    mean = jnp.mean(both.astype(jnp.float32), 2)           # [cap, Hkv, D]
    return kc.at[jnp.where(at < t, first, kc.shape[0])].set(
        mean.astype(kc.dtype), mode="drop")


def _select(cfg, q, pos, kc_win):
    """The selection mask ``[Q, Hkv, blocks of the window]`` of ``Q`` rows of
    one request against its compressed keys ``kc_win`` ``[C, Hkv, D]``."""
    from flashinfer_tpu.ops.block_sparse_paged import (
        compressed_block_scores, select_blocks)

    with jax.named_scope("attn.index"):
        scores = compressed_block_scores(
            q, kc_win, pos, stride=cfg.kernel_stride,
            kernel_size=cfg.kernel_size, block_size=cfg.block_size,
            init_blocks=cfg.init_blocks, local_blocks=cfg.local_blocks,
            sm_scale=float(cfg.head_dim) ** -0.5)
    with jax.named_scope("attn.select"):
        return select_blocks(scores, cfg.topk)


def _tiles(cfg, q, pos, live, mask, *, tile, cap):
    """What the paged kernel needs of ``U`` rows of one request, ``U`` a
    multiple of ``tile``: ``(q [Hkv, tiles, tile x G, D], union, count,
    bias)`` and the blocks its tiles read (tile rows x union blocks)."""
    from flashinfer_tpu.ops.block_sparse_paged import (selection_bias,
                                                       tile_unions)

    n_u, hq, d = q.shape
    hkv = cfg.num_kv_heads
    with jax.named_scope("attn.select"):
        union, count, hit = tile_unions(mask, live, tile=tile, cap=cap)
        bias = selection_bias(union, hit, pos.reshape(-1, tile),
                              block_size=cfg.block_size)
    qt = q.reshape(n_u // tile, tile, hkv, hq // hkv, d)
    qt = jnp.transpose(qt, (2, 0, 1, 3, 4)).reshape(
        hkv, n_u // tile, tile * (hq // hkv), d)
    rows = jnp.sum(live.reshape(-1, tile), -1)             # [tiles]
    return qt, union, count, bias, jnp.sum(count * rows[None])


def _untile(cfg, o, tile):
    """``[Hkv, tiles, tile x G, D]`` -> ``[tiles x tile, Hq, D]``."""
    hkv, tiles, _, d = o.shape
    o = o.reshape(hkv, tiles, tile, -1, d)
    return jnp.transpose(o, (1, 2, 0, 3, 4)).reshape(tiles * tile, -1, d)


def _sparse_core(cfg, q, kv, kc, ctx):
    """The step's block-sparse attention: ``q`` ``[T, Hq, D]`` -> ``([T, Hq,
    D]`` in the model's dtype, int32 ``[3]``: blocks its tiles read, K/V rows
    they read, tiles with work``)``.  Rows of no segment stay zero."""
    from flashinfer_tpu.ops.block_sparse_paged import (
        block_sparse_paged_attention, union_cap)

    t, hq, d = q.shape
    hkv, ps, per = cfg.num_kv_heads, ctx.page_size, cfg.block_pages
    table = ctx.page_table
    slots, most = table.shape
    seg, work, pos = ctx.plan["seg"], ctx.plan["work"], ctx.positions
    unit_rows = min(cfg.scan_chunk, t)
    tile = min(cfg.tile_tokens, unit_rows)
    assert unit_rows % tile == 0 and tile <= cfg.block_size, (unit_rows, tile)
    blocks = -(-most // per)
    table = jnp.pad(table, ((0, 0), (0, blocks * per - most)))
    kw = dict(block_pages=per, chunk_blocks=cfg.chunk_blocks,
              sm_scale=float(d) ** -0.5)
    caps = dict(topk=cfg.topk, init_blocks=cfg.init_blocks,
                local_blocks=cfg.local_blocks, blocks=blocks,
                chunk_blocks=cfg.chunk_blocks)

    def full(mask):
        return jnp.pad(mask, ((0, 0), (0, 0), (0, blocks - mask.shape[-1])))

    y = jnp.zeros((t + unit_rows, hq, d), cfg.dtype)

    # one-token segments: every slot at once, a tile a slot, each against
    # its own page list (a slot with nothing scheduled reads nothing)
    one = seg[:, 1] == 1
    rows = jnp.clip(seg[:, 0], 0, t - 1)
    mask1 = jax.vmap(lambda qs, p, pg: _select(
        cfg, qs[None], p[None], kc[pg[:most]])[0])(
            q[rows], pos[rows], table)                     # [B, Hkv, blocks]
    cap1 = union_cap(1, **caps)
    qt, union, count, bias, read1 = _tiles(
        cfg, q[rows], pos[rows], one, full(mask1), tile=1, cap=cap1)
    o1 = block_sparse_paged_attention(
        qt, kv, table, jnp.broadcast_to(jnp.arange(slots), (hkv, slots)),
        union, count, bias, **kw)
    y = y.at[jnp.where(one, rows, t + unit_rows)].set(
        _untile(cfg, o1, 1).astype(cfg.dtype), mode="drop")
    tiles1 = jnp.sum(count > 0)

    # longer segments: units of at most unit_rows rows of one request; the
    # compressed keys a unit scores follow its context: a quarter, a half
    # or all of the pages a request can hold, the least that covers its last
    # position
    qp, pp = _padded(q, unit_rows), _padded(pos, unit_rows)
    widths = sorted({-(-(-(-most // div)) // per) * per for div in (4, 2, 1)})
    cap = union_cap(tile, **caps)

    def at_width(pages):
        def run(qs, ps_, slot_pages):
            return full(_select(cfg, qs, ps_, kc[slot_pages[:pages]]))
        return run

    def unit(w, carry):
        y, read, tiles = carry
        r0, n, slot = work[w, 0], work[w, 1], work[w, 2]
        cut = lambda v: jax.lax.dynamic_slice_in_dim(v, r0, unit_rows)
        need = pp[r0 + n - 1] // ps + 1
        row = jax.lax.dynamic_index_in_dim(table, slot, 0, True)
        mask = jax.lax.switch(
            sum((need > w_).astype(jnp.int32) for w_ in widths[:-1]),
            [at_width(w_) for w_ in widths], cut(qp), cut(pp), row[0])
        live = jnp.arange(unit_rows) < n
        qt, union, count, bias, blocks_read = _tiles(
            cfg, cut(qp), cut(pp), live, mask, tile=tile, cap=cap)
        out = block_sparse_paged_attention(
            qt, kv, row, jnp.zeros(count.shape, jnp.int32), union, count,
            bias, **kw)
        out = _untile(cfg, out, tile).astype(cfg.dtype)
        y = jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(live[:, None, None], out, cut(y)), r0, 0)
        return y, read + blocks_read, tiles + jnp.sum(count > 0)

    y, read, tiles = jax.lax.fori_loop(
        0, ctx.plan["n_work"], unit, (y, read1, tiles1))
    return y[:t], jnp.stack(
        [read, read * cfg.block_size, tiles]).astype(jnp.int32)


def _sparse_mixer(cfg, x, layer, cache, ctx):
    from flashinfer_tpu.norm import qk_rmsnorm, rmsnorm

    kv, kc = cache
    t = x.shape[0]
    hq, hkv, d = cfg.num_qo_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn.qkv"):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        q, k, v, g = jnp.split(
            h @ layer["qkvg"], [hq * d, (hq + hkv) * d, (hq + 2 * hkv) * d],
            -1)
        q, k = qk_rmsnorm(q.reshape(t, hq, d), k.reshape(t, hkv, d),
                          layer["q_norm"], layer["k_norm"], cfg.rms_eps)
    with jax.named_scope("attn.kv_write"):
        kv = ctx.append(kv, jnp.concatenate([k, v.reshape(t, hkv, d)], -1))
        kc = _write_compressed(cfg, kv, kc, ctx)
    with jax.named_scope("attn.core"):
        o, counts = _sparse_core(cfg, q, kv, kc, ctx)
    with jax.named_scope("attn.out"):
        o = (o.reshape(t, hq * d).astype(jnp.float32)
             * jax.nn.sigmoid(g.astype(jnp.float32))).astype(cfg.dtype)
        x = _residual(cfg, x, jnp.dot(o, layer["o_proj"],
                                      preferred_element_type=jnp.float32))
    return x, (kv, kc), counts
