"""The ``glm_moe_dsa`` block (GLM-5) for the serving engine: multi-latent
attention over a latent paged cache, DeepSeek sparse attention's lightning
indexer with its own key cache beside it, top-``index_topk`` selection per
query token, and a sigmoid-routed expert layer with a shared expert after
the leading dense layers.

This module is the model's side of the engine's seam (``serve/engine.py``,
"The model's seam"; ``serve/block_step.py``).  Every layer is of the kind
``"latent"``: the engine allocates for it the two paged arrays of
:meth:`GlmDsaConfig.new_latent_pages` (one allocation of pages, two arrays
on the same page table) and donates them through the step; the step hands
the layer its page tables, an ``append`` through ``serve/kv_write.py`` and
the plan of ``serve/recurrent.py`` (each slot's segment, the longer segments
cut into units of ``scan_chunk`` rows).  No GQA work unit is planned or
launched.

Equations (``n`` an RMSNorm with its own weight, no biases but the index
key's LayerNorm; bf16 weights and activations; index scores, router scores
and the correction bias in float32):

- layer: ``x <- x + Attn(n(x))``; ``x <- x + FFN(n(x))``;
- latents (``mla.py project_latents``): ``c_q = n(h W_qa)``; ``q = c_q
  W_qb`` -> heads x (nope + rope), the rope part rotated (interleaved
  pairs); ``[c_kv | k_pe] = h W_kva``, ``c_kv`` normed, ``k_pe`` rotated.
  THE CACHE ROW IS ``[c_kv | k_pe]``;
- absorbed attention: ``W_kvb`` ``[ckv, heads, nope + v]`` is folded into the
  query (``q_abs = q_nope W_kvb,k^T``) and the output (``(p . c_kv)
  W_kvb,v``): head ``j`` scores cached token ``s`` with ``(q_abs_j . c_kv[s]
  + q_pe_j . k_pe[s]) * attention_scale`` and its value is ``c_kv[s]``;
- indexer: ``q_I = c_q W_Iq`` -> index heads x index dim; ``k_I =
  LayerNorm(h W_Ik)`` (THE INDEXER'S CACHE ROW); the first ``rope`` dims of
  both rotated; ``w = (h W_Iw) * heads_I^-1/2 * dim_I^-1/2``; ``I[t, s] =
  sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s <= t``; ``S_t`` = the
  ``index_topk`` highest (all while ``t < index_topk``); the softmax runs
  over ``S_t`` only (``ops/sparse_mla.py``);
- FFN: a dense SiLU-gated MLP in the leading layers; after them ``s =
  sigmoid(h W_r)``, the ``experts_per_tok`` largest of ``s + bias``, weights
  ``s / sum(s over the chosen) * routed_scaling_factor``
  (``fused_moe.routing.route_deepseek_v3``), the experts HELD HERE
  (``models/expert_share.py``) plus the shared expert.

Sparse attention under continuous batching: one-token segments (decode
lanes) go all slots at once, each against its own slot's pages; longer
segments are cut into units of at most ``scan_chunk`` rows of one request,
which one ``fori_loop`` with a dynamic bound runs, each unit gathering its
slot's window of index keys and latent rows once, as wide as the unit's last
position needs (a quarter, a half or all of a request's pages).  Both go
through the same function (:func:`_attend_block`): score, select, attend;
one selection rule for both.  A lane gathers its list's rows (64 MXU rows
against 2,048 gathered ones); a unit reads its window once under the list
as a mask (``ops/sparse_mla.py sparse_mla_stream``), because a gather of
``rows x index_topk`` latent rows, no two lists alike, is bound by rows per
nanosecond and not by bytes.  Shapes depend on the rung alone: one trace per
rung.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

_PUBLISHED_FFN = ("dense",) * 3 + ("moe",) * 75


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    """GLM-5 as published; ``vocab_size``, ``layer_ffn`` and
    ``local_experts`` say what is held here."""

    vocab_size: int = 154880
    hidden_size: int = 6144
    layer_ffn: Tuple[str, ...] = _PUBLISHED_FFN   # "dense" | "moe" a layer
    num_qo_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    rope_theta: float = 1e6
    intermediate_size: int = 12288       # a dense layer's width
    moe_intermediate_size: int = 2048    # one expert's, and the shared one's
    num_experts: int = 256               # the router's width
    experts_per_tok: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    local_experts: Tuple[int, int] = (0, 256)   # (first, count) held here
    rms_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    dtype: object = jnp.bfloat16
    scan_chunk: int = 128     # rows of one work unit of the sparse path

    # -- sizes ------------------------------------------------------------

    @property
    def num_layers(self) -> int:
        return len(self.layer_ffn)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attention_scale(self) -> float:
        return 1.0 / float(self.qk_nope_head_dim
                           + self.qk_rope_head_dim) ** 0.5

    # what ``EngineConfig.from_knobs`` keys its tuning tables on: all heads
    # read one latent row
    num_kv_heads = 1

    @property
    def head_dim(self) -> int:
        return self.latent_width

    # -- the engine's seam --------------------------------------------------

    def layer_kinds(self) -> Tuple[str, ...]:
        return ("latent",) * self.num_layers

    def new_latent_pages(self, num_pages: int, page_size: int):
        """(latent rows ``[pages, page_size, ckv + rope]``, index keys
        ``[pages, page_size, index dim]``) of one layer, zero: no head axis,
        one row a token, both on the request's one page list."""
        return (jnp.zeros((num_pages, page_size, self.latent_width),
                          self.dtype),
                jnp.zeros((num_pages, page_size, self.index_head_dim),
                          self.dtype))

    def engine_embed(self, params, tokens):
        return params["embed"][tokens].astype(self.dtype)

    def engine_head(self, params, x_last):
        from flashinfer_tpu.norm import rmsnorm

        xf = rmsnorm(x_last, params["final_norm"], self.rms_eps)
        return jnp.dot(xf, params["lm_head"],
                       preferred_element_type=jnp.float32)

    def engine_layer(self, li, x, layer, cache, ctx):
        if li == 0:
            ctx.aux["dsa"] = _step_counts(self, ctx)
        x, cache = _latent_attention(self, x, layer, cache, ctx)
        if self.layer_ffn[li] == "dense":
            x = _dense_mlp(self, x, layer)
        else:
            x, rows = _expert_layer(self, x, layer, ctx)
            ctx.aux.setdefault("moe_rows", []).append(rows)
        if li == self.num_layers - 1 and "moe_rows" in ctx.aux:
            ctx.aux["moe_rows"] = jnp.stack(ctx.aux["moe_rows"])
        return x, cache

    def engine_facts(self, aux, tokens: int) -> dict:
        """One step's counters: of the sparse path, from :func:`_step_counts`
        (each per layer: every layer does the same), and of the expert
        layers, from the rows each held expert got (granite's names)."""
        out = dict(zip(_COUNTS, (int(v) for v in aux["dsa"])))
        if "moe_rows" in aux:
            from flashinfer_tpu.models.expert_share import route_facts

            out.update(route_facts(aux["moe_rows"], tokens,
                                   self.experts_per_tok))
        return out


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: GlmDsaConfig, ffn: str) -> dict:
    """{name: shape} of one layer's weights; linear weights ``[in, out]``,
    ``kv_b`` in the published form ``[ckv, heads x (nope + v)]`` (the program
    folds its halves into query and output), the held experts stacked
    ``[count, in, out]`` with gate and up side by side."""
    h, heads = cfg.hidden_size, cfg.num_qo_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    out = {"input_norm": (h,), "post_norm": (h,),
           "q_a": (h, cfg.q_lora_rank), "q_a_norm": (cfg.q_lora_rank,),
           "q_b": (cfg.q_lora_rank, heads * qk),
           "kv_a": (h, cfg.latent_width), "kv_a_norm": (cfg.kv_lora_rank,),
           "kv_b": (cfg.kv_lora_rank,
                    heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
           "o_proj": (heads * cfg.v_head_dim, h),
           "idx_wq": (cfg.q_lora_rank,
                      cfg.index_n_heads * cfg.index_head_dim),
           "idx_wk": (h, cfg.index_head_dim),
           "idx_k_norm": (cfg.index_head_dim,),
           "idx_k_bias": (cfg.index_head_dim,),
           "idx_wp": (h, cfg.index_n_heads)}
    if ffn == "dense":
        out.update(gate_up=(h, 2 * cfg.intermediate_size),
                   down=(cfg.intermediate_size, h))
    else:
        held, w = cfg.local_experts[1], cfg.moe_intermediate_size
        out.update(router=(h, cfg.num_experts), e_bias=(cfg.num_experts,),
                   w_gate_up=(held, h, 2 * w), w_down=(held, w, h),
                   shared_gate_up=(h, 2 * w), shared_down=(w, h))
    return out


def init_weight(key, name: str, shape, dtype):
    """One weight from a key: linear N(0, 0.02^2); norm weights 1 + 0.1 N;
    the index key's LayerNorm bias and the router's correction bias 0.1 N,
    the latter in float32."""
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("idx_k_bias", "e_bias"):
        w = 0.1 * jax.random.normal(key, shape, jnp.float32)
        if name == "e_bias":
            return w
    else:
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def init_glm_dsa_params(key, cfg: GlmDsaConfig) -> dict:
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
    out["layers"] = [tree(jax.random.fold_in(key, li), layer_shapes(cfg, f))
                     for li, f in enumerate(cfg.layer_ffn)]
    return out


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def _residual(cfg, x, delta):
    return (x.astype(jnp.float32)
            + delta.astype(jnp.float32)).astype(cfg.dtype)


_COUNTS = ("attn_pairs_causal", "attn_pairs_selected", "index_rows_scored",
           "latent_rows_read", "latent_pages_written", "attn_units",
           "attn_units_streamed")


def _step_counts(cfg, ctx):
    """int32 ``[7]``, :data:`_COUNTS` of one layer of this step: the sums
    over the step's query tokens of the context ``ctx_t`` and of
    ``min(ctx_t, index_topk)``; the cached rows the step's requests hold
    (every index key is scored at least once; a latent row is read at most
    when some query selects it: ``min(held, queries x index_topk)`` a
    request); the pages its rows are written to; the multi-row units it runs
    and those of them that stream their window (all: at each of the three
    widths a unit chooses from the streamed form is the faster, PERF.md
    section 6, PR 36; only the lanes' one-row pass gathers)."""
    pos, req = ctx.positions, ctx.tok_req
    real = jnp.arange(pos.shape[0]) < ctx.plan["n_tokens"]
    ctx_t = jnp.where(real, pos + 1, 0)
    slots = ctx.page_table.shape[0]
    held = jnp.zeros((slots,), jnp.int32).at[req].max(ctx_t)
    queries = jnp.zeros((slots,), jnp.int32).at[req].add(
        real.astype(jnp.int32))
    first = jnp.concatenate([jnp.ones((1,), bool), req[1:] != req[:-1]])
    starts_page = first | (pos % ctx.page_size == 0)
    units = ctx.plan["n_work"]
    return jnp.stack([
        jnp.sum(ctx_t), jnp.sum(jnp.minimum(ctx_t, cfg.index_topk)),
        jnp.sum(held), jnp.sum(jnp.minimum(held, queries * cfg.index_topk)),
        jnp.sum(real & starts_page), units, units]).astype(jnp.int32)


def _index_projections(cfg, h, c_q, layer, positions):
    """``(q_I [T, Hi, Di], k_I [T, Di], w [T, Hi] float32)``."""
    from flashinfer_tpu.norm import layernorm
    from flashinfer_tpu.rope import apply_rope_pos_ids

    t, hi, di = h.shape[0], cfg.index_n_heads, cfg.index_head_dim
    q_i = (c_q @ layer["idx_wq"]).reshape(t, hi, di)
    k = layernorm(h @ layer["idx_wk"], layer["idx_k_norm"],
                  layer["idx_k_bias"], cfg.index_norm_eps)
    q_i, k = apply_rope_pos_ids(
        q_i, k[:, None], positions, rotary_dim=cfg.qk_rope_head_dim,
        interleave=True, rope_theta=cfg.rope_theta)
    w = jnp.dot(h, layer["idx_wp"], preferred_element_type=jnp.float32) \
        * (float(hi) ** -0.5 * float(di) ** -0.5)
    return q_i, k[:, 0], w


def _attend_block(cfg, q, q_i, w_i, pos, win_i, win_l, last=None):
    """Score, select, attend for ``Q`` query rows that share one request's
    window: ``q`` ``[Q, H, W]`` (absorbed, padded to the window's width),
    ``q_i`` / ``w_i`` the index queries and head weights, ``pos`` ``[Q]``,
    ``win_i`` ``[K, Di]`` and ``win_l`` ``[K, W]`` the request's cached index
    keys and latent rows by position.  Float32 ``[Q, H, ckv]``.

    One selection rule, two forms of the same softmax over it
    (``ops/sparse_mla.py``): a unit of many rows, which gives ``last`` (the
    position of its last live row), streams the window once under the
    selection as a mask, and a mask needs the ``index_topk``-th score of each
    row only: a search, no sort; one row (a decode lane) gathers its list's
    rows, so it sorts for the list."""
    from flashinfer_tpu.ops.sparse_mla import (
        index_scores, kth_score, mask_from_kth, select_topk,
        sparse_mla_attend, sparse_mla_stream)

    with jax.named_scope("attn.index"):
        scores = index_scores(q_i, w_i, win_i, pos)
    if last is not None:
        with jax.named_scope("attn.select"):
            kth = kth_score(scores, cfg.index_topk)
        return sparse_mla_stream(
            q, win_l, mask_from_kth(scores, kth, cfg.index_topk), last,
            d_v=cfg.kv_lora_rank, sm_scale=cfg.attention_scale)
    with jax.named_scope("attn.select"):
        idx, valid = select_topk(scores, cfg.index_topk)
    return sparse_mla_attend(q, win_l[idx], valid, d_v=cfg.kv_lora_rank,
                             sm_scale=cfg.attention_scale)


def _sparse_core(cfg, q, q_i, w_i, lat, kidx, ctx):
    """The step's sparse attention: ``q`` ``[T, H, ckv + rope]`` absorbed
    queries -> ``[T, H, ckv]`` (bf16), lanes and units as the module doc
    says.  Rows of no segment (padding) stay zero."""
    t, heads, _ = q.shape
    table = ctx.page_table
    # the gathered block is read at full lane width: pad the window's rows
    # (and the queries, with zeros) to a multiple of 128 once a window
    lane_pad = -lat.shape[-1] % 128
    q = jnp.pad(q, ((0, 0), (0, 0), (0, lane_pad)))
    unit_rows = min(cfg.scan_chunk, t)
    seg, work = ctx.plan["seg"], ctx.plan["work"]
    pos = ctx.positions

    def window(pages):
        return (kidx[pages].reshape(-1, kidx.shape[-1]),
                jnp.pad(lat[pages].reshape(-1, lat.shape[-1]),
                        ((0, 0), (0, lane_pad))))

    y = jnp.zeros((t + unit_rows, heads, cfg.kv_lora_rank), cfg.dtype)

    # one-token segments: every slot at once, each against its own window
    one = seg[:, 1] == 1
    rows = jnp.clip(seg[:, 0], 0, t - 1)
    win_i, win_l = jax.vmap(window)(table)
    y1 = jax.vmap(lambda *a: _attend_block(cfg, *a))(
        q[rows][:, None], q_i[rows][:, None], w_i[rows][:, None],
        pos[rows][:, None], win_i, win_l)[:, 0]
    y = y.at[jnp.where(one, rows, t + unit_rows)].set(
        y1.astype(cfg.dtype), mode="drop")

    # longer segments: units of at most unit_rows rows of one request
    qp, qip, wip, pp = (
        jnp.pad(v, ((0, unit_rows),) + ((0, 0),) * (v.ndim - 1))
        for v in (q, q_i, w_i, pos))

    # a unit's window follows its request's context: a quarter, a half or
    # the whole of the pages a request can hold, the least that covers the
    # unit's last position (score, search and gather grow with the window)
    ps, most = lat.shape[1], table.shape[1]
    widths = sorted({-(-most // d) for d in (4, 2, 1)})

    def at_width(pages):
        def run(qs, qis, wis, ps_, slot_pages, last):
            return _attend_block(cfg, qs, qis, wis, ps_,
                                 *window(slot_pages[:pages]), last)
        return run

    def unit(w, y):
        r0, n, slot = work[w, 0], work[w, 1], work[w, 2]
        cut = lambda v: jax.lax.dynamic_slice_in_dim(v, r0, unit_rows)
        last = pp[r0 + n - 1]
        need = last // ps + 1
        out = jax.lax.switch(
            sum((need > w_).astype(jnp.int32) for w_ in widths[:-1]),
            [at_width(w_) for w_ in widths],
            cut(qp), cut(qip), cut(wip), cut(pp), table[slot], last)
        live = (jnp.arange(unit_rows) < n)[:, None, None]
        return jax.lax.dynamic_update_slice_in_dim(
            y, jnp.where(live, out.astype(cfg.dtype), cut(y)), r0, 0)

    y = jax.lax.fori_loop(0, ctx.plan["n_work"], unit, y)
    return y[:t]


def _latent_attention(cfg, x, layer, cache, ctx):
    from flashinfer_tpu.mla import project_latents
    from flashinfer_tpu.norm import rmsnorm

    lat, kidx = cache
    t, heads = x.shape[0], cfg.num_qo_heads
    nope = cfg.qk_nope_head_dim
    kv_b = layer["kv_b"].reshape(cfg.kv_lora_rank, heads,
                                 nope + cfg.v_head_dim)
    with jax.named_scope("attn.qkv"):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        c_q, q_nope, q_pe, c_kv, k_pe = project_latents(
            h, layer, ctx.positions, num_heads=heads, nope=nope,
            rope=cfg.qk_rope_head_dim, ckv=cfg.kv_lora_rank,
            eps=cfg.rms_eps, rope_theta=cfg.rope_theta, interleave=True)
        q_abs = jnp.einsum("thn,chn->thc", q_nope, kv_b[..., :nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs.astype(cfg.dtype), q_pe], -1)
    with jax.named_scope("attn.core"), jax.named_scope("attn.index"):
        q_i, k_i, w_i = _index_projections(cfg, h, c_q, layer,
                                           ctx.positions)
    with jax.named_scope("attn.kv_write"):
        lat = ctx.append(lat, jnp.concatenate([c_kv, k_pe], -1))
        kidx = ctx.append(kidx, k_i)
    with jax.named_scope("attn.core"):
        o = _sparse_core(cfg, q, q_i, w_i, lat, kidx, ctx)
    with jax.named_scope("attn.out"):
        o = jnp.einsum("thc,chv->thv", o, kv_b[..., nope:],
                       preferred_element_type=jnp.float32).astype(cfg.dtype)
        x = _residual(cfg, x, jnp.dot(o.reshape(t, -1), layer["o_proj"],
                                      preferred_element_type=jnp.float32))
    return x, (lat, kidx)


def _dense_mlp(cfg, x, layer):
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.norm import rmsnorm

    with jax.named_scope("mlp"):
        u = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        return _residual(cfg, x, jnp.dot(
            silu_and_mul(u @ layer["gate_up"]), layer["down"],
            preferred_element_type=jnp.float32))


def _expert_layer(cfg, x, layer, ctx):
    """``x + (what the held experts add + the shared expert)``, and the rows
    each held expert got."""
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.fused_moe.routing import route_deepseek_v3
    from flashinfer_tpu.models.expert_share import held_experts
    from flashinfer_tpu.norm import rmsnorm

    with jax.named_scope("moe.route"):
        u = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        logits = jnp.dot(u, layer["router"],
                         preferred_element_type=jnp.float32)
        weights, ids = route_deepseek_v3(
            logits, layer["e_bias"], cfg.experts_per_tok, cfg.n_group,
            cfg.topk_group, cfg.routed_scaling_factor)
    routed, group_sizes = held_experts(
        u, weights, ids, layer["w_gate_up"], layer["w_down"],
        first=cfg.local_experts[0], n_tokens=ctx.plan["n_tokens"])
    with jax.named_scope("moe.shared"):
        shared = jnp.dot(silu_and_mul(u @ layer["shared_gate_up"]),
                         layer["shared_down"],
                         preferred_element_type=jnp.float32)
        x = _residual(cfg, x, routed + shared)
    return x, group_sizes
