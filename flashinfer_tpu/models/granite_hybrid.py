"""The ``granitemoehybrid`` block (granite-4.0-h) for the serving engine:
Mamba-2 and no-rope attention mixers in a published pattern, a routed expert
layer with a shared expert in every layer, tied and scaled ends.

This module is the model's side of the engine's seam (``serve/engine.py``,
"The model's seam"): :class:`GraniteHybridConfig` says which kind each layer
is and what a recurrent layer keeps, and computes the embedding, each layer
and the head inside the engine's compiled step.  The engine owns tokens,
schedule, K/V pages, state slots, the head's row gather and sampling.

Equations (``n`` an RMSNorm with its own weight, no biases but the
convolution's; bf16 weights and activations, the state ``S`` and the router
in float32):

- ends: ``x = embedding_multiplier * E[tok]``; ``logits = n(x_L) E^T /
  logits_scaling`` (tied head);
- layer: ``x <- x + residual_multiplier * mixer(n(x))``; ``x <- x +
  residual_multiplier * (MoE(u) + Shared(u))``, ``u = n(x)``;
- attention: grouped-query over the engine's pages, NO rotation, softmax of
  ``q . k * attention_multiplier``;
- Mamba-2: ``[z | xBC | dt] = h W_in``; a causal depthwise convolution of
  width ``d_conv`` and SiLU over ``xBC``; ``[x | B | C] = xBC``; ``D_t =
  softplus(dt_t + dt_bias)``, ``a_t = exp(D_t A)``, ``A = -exp(A_log)``;
  ``S_t = a_t S_{t-1} + D_t x_t (x) B_t``; ``y_t = S_t C_t + D x_t``; ``g_t =
  RMSNorm(y_t silu(z_t))`` over the whole inner width; output ``g_t W_out``;
- expert layer: router over ALL the published experts in float32, the
  ``experts_per_tok`` largest, softmax over those; this chip holds experts
  ``local_experts = (first, count)`` and adds what they give; routes to
  absent experts are dropped before the grouped matmul, and nothing stands
  in for the peer chip.

Mamba-2 under continuous batching: the flat batch of one step holds, per
scheduled request, one contiguous segment (``serve/recurrent.py``).  The
convolution reads, for the first ``d_conv - 1`` rows of a segment, the
slot's tail.  The recurrence is one Pallas launch a layer
(``ops/ssm_slot_kernel.py``) over the step's ITEMS, the slots' array aliased
to its output: a one-token segment (a decode lane) is one item, the one-row
update; a longer segment is cut into work units of at most ``scan_chunk``
rows, each an item in the chunked (SSD) form, ``S`` carried in VMEM from unit
to unit of a segment, loaded at its first unit (zero for a request
(re)admitted since its last step) and written back after its last.  A slot
the step does not schedule is not touched.  Shapes depend on the rung
alone: one trace per rung.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_PUBLISHED_PATTERN = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """granite-4.0-h-small as published; ``vocab_size``, ``layer_types``
    and ``local_experts`` say what is held here."""

    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: Tuple[str, ...] = _PUBLISHED_PATTERN
    num_qo_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    num_experts: int = 72            # the router's width
    experts_per_tok: int = 10
    intermediate_size: int = 768     # one expert's width
    shared_intermediate_size: int = 1536
    local_experts: Tuple[int, int] = (0, 72)   # (first, count) held here
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    attention_multiplier: float = 0.0078125
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16
    state_dtype: object = jnp.float32
    scan_chunk: int = 128            # rows of one work unit of the scan
    attention_rope = False           # position_embedding_type "nope"

    # -- sizes ------------------------------------------------------------

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def attention_scale(self) -> float:
        return float(self.attention_multiplier)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    # -- the engine's seam --------------------------------------------------

    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple("attention" if t == "attention" else "recurrent"
                     for t in self.layer_types)

    def new_recurrent_state(self, slots: int):
        """(convolution tail ``[slots, d_conv - 1, conv_dim]``, ``S``
        ``[slots, heads x d_head, d_state]``) of one Mamba layer, zero.
        ``S`` has the axes the scan's kernel blocks it by (``_mamba_mixer``):
        on the chip a flat ``[slots, heads x d_head x d_state]`` array is
        tiled eight slots to a tile, and opening its axes would lay every
        slot out anew, twice a layer."""
        return (jnp.zeros((slots, self.mamba_d_conv - 1, self.conv_dim),
                          self.dtype),
                jnp.zeros((slots, self.mamba_n_heads * self.mamba_d_head,
                           self.mamba_d_state), self.state_dtype))

    def engine_embed(self, params, tokens):
        e = params["embed"][tokens].astype(jnp.float32)
        return (self.embedding_multiplier * e).astype(self.dtype)

    def engine_head(self, params, x_last):
        from flashinfer_tpu.norm import rmsnorm

        xf = rmsnorm(x_last, params["final_norm"], self.rms_eps)
        logits = jax.lax.dot_general(
            xf, params["embed"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits / self.logits_scaling

    def engine_layer(self, li, x, layer, cache, ctx):
        if self.layer_types[li] == "attention":
            x, cache = _attention_mixer(self, x, layer, cache, ctx)
        else:
            x, cache = _mamba_mixer(self, x, layer, cache, ctx)
        x, rows = _expert_layer(self, x, layer, ctx)
        held = ctx.aux.setdefault("moe_rows", [])
        held.append(rows)
        if li == self.num_layers - 1:
            ctx.aux["moe_rows"] = jnp.stack(held)
        return x, cache

    def engine_facts(self, aux, tokens: int) -> dict:
        """The expert layers' counters of one step, from the rows each held
        expert got in each layer (``aux["moe_rows"]`` ``[layers, held]``)."""
        from flashinfer_tpu.models.expert_share import route_facts

        return route_facts(aux["moe_rows"], tokens, self.experts_per_tok)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: GraniteHybridConfig, kind: str) -> dict:
    """{name: shape} of one layer's weights; linear weights ``[in, out]``,
    the held experts stacked ``[count, in, out]`` with gate and up side by
    side."""
    h = cfg.hidden_size
    held = cfg.local_experts[1]
    out = {"input_norm": (h,), "post_norm": (h,),
           "router": (h, cfg.num_experts),
           "w_gate_up": (held, h, 2 * cfg.intermediate_size),
           "w_down": (held, cfg.intermediate_size, h),
           "shared_gate_up": (h, 2 * cfg.shared_intermediate_size),
           "shared_down": (cfg.shared_intermediate_size, h)}
    if kind == "attention":
        q = cfg.num_qo_heads * cfg.head_dim
        kv = cfg.num_kv_heads * cfg.head_dim
        out.update(q_proj=(h, q), k_proj=(h, kv), v_proj=(h, kv),
                   o_proj=(q, h))
    else:
        out.update(
            in_proj=(h, cfg.mamba_inner + cfg.conv_dim + cfg.mamba_n_heads),
            conv_w=(cfg.mamba_d_conv, cfg.conv_dim), conv_b=(cfg.conv_dim,),
            dt_bias=(cfg.mamba_n_heads,), A_log=(cfg.mamba_n_heads,),
            D=(cfg.mamba_n_heads,), ssm_norm=(cfg.mamba_inner,),
            out_proj=(cfg.mamba_inner, h))
    return out


def init_weight(key, name: str, shape, dtype):
    """One weight from a key: linear N(0, 0.02^2); norm weights 1 + 0.1 N;
    the convolution U(-1/2, 1/2) (its fan-in is 4); ``dt_bias`` so that
    softplus of it lies in [0.001, 0.1]; ``A_log`` = log U(1, 16); D = 1
    + 0.1 N: Mamba-2's own initialisation, so that the decay per token is
    what a trained layer's is."""
    if name.endswith("norm"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "conv_w":
        w = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    elif name == "conv_b":
        w = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))     # softplus^-1(dt)
    elif name == "A_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "D":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return w.astype(dtype)


def init_granite_hybrid_params(key, cfg: GraniteHybridConfig) -> dict:
    """Random parameter tree: ``embed`` (tied head), ``final_norm``,
    ``layers[i]`` as :func:`layer_shapes` lists."""
    def tree(key, shapes):
        names = sorted(shapes)
        return {n: init_weight(k, n, shapes[n], cfg.dtype)
                for n, k in zip(names, jax.random.split(key, len(names)))}

    out = tree(jax.random.fold_in(key, 1 << 20),
               {"embed": (cfg.vocab_size, cfg.hidden_size),
                "final_norm": (cfg.hidden_size,)})
    out["layers"] = [tree(jax.random.fold_in(key, li), layer_shapes(cfg, t))
                     for li, t in enumerate(cfg.layer_types)]
    return out


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def _residual(cfg, x, delta):
    return (x.astype(jnp.float32) + cfg.residual_multiplier
            * delta.astype(jnp.float32)).astype(cfg.dtype)


def _attention_mixer(cfg, x, layer, cache, ctx):
    from flashinfer_tpu.norm import rmsnorm

    t = x.shape[0]
    with jax.named_scope("attn.qkv"):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        q = (h @ layer["q_proj"]).reshape(t, cfg.num_qo_heads, cfg.head_dim)
        k = (h @ layer["k_proj"]).reshape(t, cfg.num_kv_heads, cfg.head_dim)
        v = (h @ layer["v_proj"]).reshape(t, cfg.num_kv_heads, cfg.head_dim)
    attn, cache = ctx.attend(q, k, v, cache)
    with jax.named_scope("attn.out"):
        x = _residual(cfg, x, jnp.dot(attn.reshape(t, -1), layer["o_proj"],
                                      preferred_element_type=jnp.float32))
    return x, cache


def _mamba_mixer(cfg, x, layer, cache, ctx):
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.ops.ssm_slot_kernel import ssm_slot_update

    conv_state, ssm_state = cache
    t = x.shape[0]
    nh, dh, ds, g = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                     cfg.mamba_n_groups)
    inner, taps = cfg.mamba_inner, cfg.mamba_d_conv - 1
    seg = ctx.plan["seg"]
    row0, n_seg, fresh = seg[:, 0], seg[:, 1], seg[:, 2] > 0
    f32 = jnp.float32

    with jax.named_scope("ssm.in_proj"):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        zxd = h @ layer["in_proj"]
        z, xbc, dt = jnp.split(zxd, [inner, inner + cfg.conv_dim], -1)

    with jax.named_scope("ssm.conv"):
        # input t - k of a row lies in the flat batch where the row is at
        # least k rows into its segment, else in the slot's tail
        off = jnp.arange(t) - row0[ctx.tok_req]
        tails = jnp.where(fresh[:, None, None], 0, conv_state)
        tail_tok = tails[ctx.tok_req]                       # [T, taps, C]
        cw = layer["conv_w"].astype(f32)
        acc = layer["conv_b"].astype(f32) + cw[taps] * xbc.astype(f32)
        for k in range(1, taps + 1):
            shifted = jnp.pad(xbc, ((k, 0), (0, 0)))[:t]
            idx = jnp.clip(taps + off - k, 0, taps - 1)
            from_tail = jnp.take_along_axis(
                tail_tok, idx[:, None, None], 1)[:, 0]
            prev = jnp.where((off >= k)[:, None], shifted, from_tail)
            acc = acc + cw[taps - k] * prev.astype(f32)
        act = jax.nn.silu(acc).astype(cfg.dtype)
        # each slot's new tail: its last `taps` inputs, of this step's
        # rows and, where the segment is shorter, of the old tail
        r = jnp.arange(taps)
        o = n_seg[:, None] - taps + r[None]                  # [B, taps]
        from_flat = xbc[jnp.clip(row0[:, None] + o, 0, t - 1)]
        from_old = jnp.take_along_axis(
            tails, jnp.clip(n_seg[:, None] + r[None], 0,
                            taps - 1)[:, :, None], 1)
        conv_state = jnp.where((o >= 0)[:, :, None], from_flat,
                               from_old).astype(conv_state.dtype)

    with jax.named_scope("ssm.scan"):
        xs, bm, cm = jnp.split(act, [inner, inner + g * ds], -1)
        a_head = -jnp.exp(layer["A_log"].astype(f32))
        delta = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"].astype(f32))
        # one launch advances the scheduled slots in place and touches no
        # other slot (the module's docstring; ops/ssm_slot_kernel.py)
        y, ssm_state = ssm_slot_update(
            ssm_state, seg, ctx.plan["work"], ctx.plan["n_work"], xs, bm, cm,
            delta, a_head, heads=nh, d_head=dh, d_state=ds, groups=g,
            chunk=cfg.scan_chunk)
        y = y.reshape(t, nh, dh) + layer["D"].astype(f32)[:, None] \
            * xs.reshape(t, nh, dh).astype(f32)
        gated = (y.reshape(t, inner)
                 * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
        gated = rmsnorm(gated, layer["ssm_norm"], cfg.rms_eps)

    with jax.named_scope("ssm.out"):
        x = _residual(cfg, x, jnp.dot(gated, layer["out_proj"],
                                      preferred_element_type=f32))
    return x, (conv_state, ssm_state)


def _expert_layer(cfg, x, layer, ctx):
    """``x + residual_multiplier * (what the held experts add + the shared
    expert)``, and the rows each held expert got."""
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.fused_moe.routing import route_renormalize
    from flashinfer_tpu.models.expert_share import held_experts
    from flashinfer_tpu.norm import rmsnorm

    with jax.named_scope("moe.route"):
        u = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        logits = jnp.dot(u, layer["router"],
                         preferred_element_type=jnp.float32)
        weights, ids = route_renormalize(logits, cfg.experts_per_tok)
    routed, group_sizes = held_experts(
        u, weights, ids, layer["w_gate_up"], layer["w_down"],
        first=cfg.local_experts[0], n_tokens=ctx.plan["n_tokens"])
    with jax.named_scope("moe.shared"):
        shared = jnp.dot(silu_and_mul(u @ layer["shared_gate_up"]),
                         layer["shared_down"],
                         preferred_element_type=jnp.float32)
        x = _residual(cfg, x, routed + shared)
    return x, group_sizes
