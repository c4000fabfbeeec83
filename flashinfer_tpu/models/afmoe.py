"""The ``afmoe`` block (Arcee Trinity) for the serving engine: sliding-window
and full grouped-query attention layers in a published pattern, rotation on
the window layers only, per-head RMSNorm of queries and keys, a sigmoid gate
on the attention output, four norms a layer (sandwich), leading dense MLPs
and then a sigmoid-routed expert layer with a shared expert, scaled embedding
and an untied head.

This module is the model's side of the engine's seam (``serve/engine.py``,
"The model's seam"; ``serve/block_step.py``): :class:`AfmoeConfig` says which
kind each layer is (``"window"`` for ``sliding_attention``: its K/V pages live
in the engine's second pool, ``serve/window_pool.py``; ``"attention"`` for
``full_attention``), how many keys a window query sees and which kinds
rotate, and computes the embedding, each layer and the head inside the
engine's compiled step.  The engine owns tokens, schedule, both pools, the
K/V write, the rotation, the paged attention of either kind, the head's row
gather and sampling.

Equations (``n*`` RMSNorms with their own weights, no biases; bf16 weights
and activations; router scores, bias and normalisation in float32):

- ends: ``x = E[tok] * sqrt(hidden)`` (``mup_enabled``); ``logits =
  n_f(x_L) W_head`` (untied, unscaled);
- layer: ``x <- x + n_post_attn(Attn(n_in(x)))``; ``x <- x +
  n_post_mlp(F(n_pre_mlp(x)))``;
- ``Attn(h)``: ``q = h W_q``, ``k = h W_k``, ``v = h W_v``, ``g = h W_g``
  (as wide as ``q``); ``q`` and ``k`` RMS-normed over each head's dims with
  one weight each, shared by the heads; on window layers only, ``q`` and ``k``
  rotated (half-split pairs, ``rope_theta``); softmax of ``q . k /
  sqrt(head_dim)`` over keys ``j <= i``, on window layers also ``j > i -
  attention_window``; ``out = (o * sigmoid(g)) W_o``;
- ``F`` in the leading ``num_dense_layers`` layers: a SiLU-gated MLP; after
  them ``s = sigmoid(h W_r)``, the ``experts_per_tok`` largest of ``s +
  bias`` (the bias selects only), weights ``s / sum(s over the chosen) *
  route_scale`` (``fused_moe.routing.route_deepseek_v3``, one group), the
  experts HELD HERE (``models/expert_share.py``: routes to absent experts
  dropped, not renormalised) plus the shared expert once.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

_PUBLISHED_TYPES = tuple(
    "full_attention" if i % 4 == 3 else "sliding_attention"
    for i in range(32))


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Trinity-Mini as published; ``local_experts`` says which of the
    ``num_experts`` the router scores are held here."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = _PUBLISHED_TYPES
    num_qo_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    attention_window: int = 2048     # keys a window query sees, its own too
    rope_theta: float = 1e4
    num_dense_layers: int = 2
    intermediate_size: int = 6144    # a dense layer's width
    moe_intermediate_size: int = 1024   # one expert's, and the shared one's
    num_experts: int = 128           # the router's width
    experts_per_tok: int = 8
    route_scale: float = 2.826
    local_experts: Tuple[int, int] = (0, 128)   # (first, count) held here
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    # -- the engine's seam --------------------------------------------------

    # rotation is a property of the layer kind: window layers rotate, full
    # layers carry no position signal at all
    attention_rope = {"window": True, "attention": False}

    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("window" if t == "sliding_attention" else "attention"
                     for t in self.layer_types)

    def engine_embed(self, params, tokens):
        e = params["embed"][tokens].astype(jnp.float32)
        return (e * float(self.hidden_size) ** 0.5).astype(self.dtype)

    def engine_head(self, params, x_last):
        from flashinfer_tpu.norm import rmsnorm

        xf = rmsnorm(x_last, params["final_norm"], self.rms_eps)
        return jnp.dot(xf, params["lm_head"],
                       preferred_element_type=jnp.float32)

    def engine_layer(self, li, x, layer, cache, ctx):
        if li == 0:
            ctx.aux["attn"] = _step_counts(self, ctx)
        x, cache = _attention(self, x, layer, cache, ctx,
                              self.layer_kinds()[li])
        if li < self.num_dense_layers:
            x = _dense_mlp(self, x, layer)
        else:
            x, rows = _expert_layer(self, x, layer, ctx)
            ctx.aux.setdefault("moe_rows", []).append(rows)
        if li == self.num_layers - 1 and "moe_rows" in ctx.aux:
            ctx.aux["moe_rows"] = jnp.stack(ctx.aux["moe_rows"])
        return x, cache

    def engine_facts(self, aux, tokens: int) -> dict:
        """One step's counters: of attention, from :func:`_step_counts` (each
        per layer of its kind), and of the expert layers, from the rows each
        held expert got (granite's names)."""
        out = dict(zip(_COUNTS, (int(v) for v in aux["attn"])))
        if "moe_rows" in aux:
            from flashinfer_tpu.models.expert_share import route_facts

            out.update(route_facts(aux["moe_rows"], tokens,
                                   self.experts_per_tok))
        return out


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: AfmoeConfig, dense: bool) -> dict:
    """{name: shape} of one layer's weights; linear weights ``[in, out]``,
    the held experts stacked ``[count, in, out]`` with gate and up side by
    side."""
    h, d = cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_qo_heads * d, cfg.num_kv_heads * d
    out = {"input_norm": (h,), "post_attn_norm": (h,), "pre_mlp_norm": (h,),
           "post_mlp_norm": (h,), "q_proj": (h, q), "k_proj": (h, kv),
           "v_proj": (h, kv), "gate_proj": (h, q), "o_proj": (q, h),
           "q_norm": (d,), "k_norm": (d,)}
    if dense:
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
    the router's selection bias 0.1 N in float32."""
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "e_bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def init_afmoe_params(key, cfg: AfmoeConfig) -> dict:
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
    out["layers"] = [
        tree(jax.random.fold_in(key, li),
             layer_shapes(cfg, li < cfg.num_dense_layers))
        for li in range(cfg.num_layers)]
    return out


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

_COUNTS = ("attn_pairs_causal", "attn_pairs_full", "attn_pairs_window",
           "full_rows_read", "window_rows_read")


def _step_counts(cfg, ctx):
    """int32 ``[5]``, :data:`_COUNTS` of ONE layer of its kind in this step:
    the sums over the step's query tokens of the context ``ctx_t`` (what a
    full layer attends: the causal pairs) and of ``min(ctx_t,
    attention_window)`` (a window layer's); the cached K/V rows a layer must
    read: a request's whole context in a full layer, and in a window layer
    the rows its first query's window opens on, ``min(context,
    attention_window - 1 + queries)``."""
    pos, req = ctx.positions, ctx.tok_req
    real = jnp.arange(pos.shape[0]) < ctx.plan["n_tokens"]
    ctx_t = jnp.where(real, pos + 1, 0)
    slots = ctx.page_table.shape[0]
    held = jnp.zeros((slots,), jnp.int32).at[req].max(ctx_t)
    queries = jnp.zeros((slots,), jnp.int32).at[req].add(
        real.astype(jnp.int32))
    reach = jnp.where(queries > 0, cfg.attention_window - 1 + queries, 0)
    full = jnp.sum(ctx_t)
    return jnp.stack([
        full, full, jnp.sum(jnp.minimum(ctx_t, cfg.attention_window)),
        jnp.sum(held), jnp.sum(jnp.minimum(held, reach))]).astype(jnp.int32)


def _residual(cfg, x, delta):
    return (x.astype(jnp.float32)
            + delta.astype(jnp.float32)).astype(cfg.dtype)


def _attention(cfg, x, layer, cache, ctx, kind):
    from flashinfer_tpu.norm import rmsnorm

    t, hq, hkv, d = (x.shape[0], cfg.num_qo_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    with jax.named_scope("attn.qkv"):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        q = rmsnorm((h @ layer["q_proj"]).reshape(t * hq, d),
                    layer["q_norm"], cfg.rms_eps).reshape(t, hq, d)
        k = rmsnorm((h @ layer["k_proj"]).reshape(t * hkv, d),
                    layer["k_norm"], cfg.rms_eps).reshape(t, hkv, d)
        v = (h @ layer["v_proj"]).reshape(t, hkv, d)
        gate = h @ layer["gate_proj"]
    attn, cache = ctx.attend(q, k, v, cache, kind=kind)
    with jax.named_scope("attn.out"):
        gated = (attn.reshape(t, hq * d).astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
        o = jnp.dot(gated, layer["o_proj"],
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        x = _residual(cfg, x, rmsnorm(o, layer["post_attn_norm"],
                                      cfg.rms_eps))
    return x, cache


def _dense_mlp(cfg, x, layer):
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.norm import rmsnorm

    with jax.named_scope("mlp"):
        u = rmsnorm(x, layer["pre_mlp_norm"], cfg.rms_eps)
        f = jnp.dot(silu_and_mul(u @ layer["gate_up"]), layer["down"],
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
        return _residual(cfg, x, rmsnorm(f, layer["post_mlp_norm"],
                                         cfg.rms_eps))


def _expert_layer(cfg, x, layer, ctx):
    """``x + n_post_mlp(what the held experts add + the shared expert)``, and
    the rows each held expert got."""
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.fused_moe.routing import route_deepseek_v3
    from flashinfer_tpu.models.expert_share import held_experts
    from flashinfer_tpu.norm import rmsnorm

    with jax.named_scope("moe.route"):
        u = rmsnorm(x, layer["pre_mlp_norm"], cfg.rms_eps)
        logits = jnp.dot(u, layer["router"],
                         preferred_element_type=jnp.float32)
        weights, ids = route_deepseek_v3(
            logits, layer["e_bias"], cfg.experts_per_tok, 1, 1,
            cfg.route_scale)
    routed, group_sizes = held_experts(
        u, weights, ids, layer["w_gate_up"], layer["w_down"],
        first=cfg.local_experts[0], n_tokens=ctx.plan["n_tokens"])
    with jax.named_scope("moe.shared"):
        shared = jnp.dot(silu_and_mul(u @ layer["shared_gate_up"]),
                         layer["shared_down"],
                         preferred_element_type=jnp.float32)
        f = (routed + shared).astype(cfg.dtype)
        x = _residual(cfg, x, rmsnorm(f, layer["post_mlp_norm"],
                                      cfg.rms_eps))
    return x, group_sizes
