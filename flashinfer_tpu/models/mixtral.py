"""Mixtral-style MoE decoder wired to the library.

The second integration model (reference keeps MoE serving in its
consumers and ships the fused-MoE blocks — ``flashinfer/fused_moe/``;
SURVEY §2.3): the llama attention sublayer (paged decode + RoPE + fused
AR) with the MLP replaced by the routed ``fused_moe`` expert block.

Entry points mirror ``models/llama.py``:

- ``mixtral_decode_step`` — single device, jittable.
- ``make_ep_sharded_decode_step`` — shard_map over a dp x ep mesh:
  attention weights replicated per dp shard, experts contiguously
  sharded over the ep axis with ``fused_moe_ep`` (allgather dispatch for
  decode's small token counts; the capacity-bucketed all_to_all mode is
  one kwarg away for prefill-sized batches).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flashinfer_tpu.comm.mapping import Mapping
from flashinfer_tpu.fused_moe import fused_moe, fused_moe_ep, route_renormalize
from flashinfer_tpu.models.llama import (
    LlamaConfig,
    _attn_decode,
    _mm,
)
from flashinfer_tpu.norm import rmsnorm
from flashinfer_tpu.utils import is_tpu


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2

    @staticmethod
    def tiny(**over) -> "MixtralConfig":
        d = dict(
            vocab_size=512, hidden_size=128, intermediate_size=128,
            num_layers=2, num_qo_heads=8, num_kv_heads=4, head_dim=32,
            num_experts=4, top_k=2,
        )
        d.update(over)
        return MixtralConfig(**d)


def init_mixtral_params(key: jax.Array, cfg: MixtralConfig) -> Dict:
    """Random-init pytree: llama attention weights + per-layer router and
    stacked expert weights ([E, hidden, 2*inter] / [E, inter, hidden])."""
    h, qh, kvh, hd = (
        cfg.hidden_size, cfg.num_qo_heads, cfg.num_kv_heads, cfg.head_dim,
    )
    E, inter = cfg.num_experts, cfg.intermediate_size
    keys = iter(jax.random.split(key, 4 + 8 * cfg.num_layers))

    def w(shape, scale=0.02):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * scale
        ).astype(cfg.dtype)

    layers = []
    for _ in range(cfg.num_layers):
        layers.append(
            dict(
                input_norm=jnp.ones((h,), cfg.dtype),
                q_proj=w((h, qh * hd)),
                k_proj=w((h, kvh * hd)),
                v_proj=w((h, kvh * hd)),
                o_proj=w((qh * hd, h)),
                post_norm=jnp.ones((h,), cfg.dtype),
                router=w((h, E), scale=0.1).astype(jnp.float32),
                w_gate_up=w((E, h, 2 * inter)),
                w_down=w((E, inter, h)),
            )
        )
    return dict(
        embed=w((cfg.vocab_size, h)),
        final_norm=jnp.ones((h,), cfg.dtype),
        lm_head=w((h, cfg.vocab_size)),
        layers=layers,
    )


def _moe_block(h, layer, cfg: MixtralConfig, moe_fn=fused_moe):
    """Route + expert compute; ``moe_fn`` swaps the single-device kernel
    for an EP-sharded one (keeps routing in ONE place for both steps)."""
    logits = h.astype(jnp.float32) @ layer["router"]
    wts, ids = route_renormalize(logits, cfg.top_k)
    return moe_fn(
        h, layer["w_gate_up"], layer["w_down"], wts, ids, cfg.num_experts
    ).astype(h.dtype)


def mixtral_decode_step(
    params: Dict,
    cfg: MixtralConfig,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B]
    kv_caches: List[Tuple[jax.Array, jax.Array]],
    page_table: jax.Array,  # [B, P]
    kv_lens: jax.Array,  # [B]
    use_pallas: bool = True,
) -> Tuple[jax.Array, List[Tuple[jax.Array, jax.Array]]]:
    """Single-device batched decode step -> (logits [B, vocab], caches)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    new_caches = []
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        attn, cache = _attn_decode(
            h, layer, cfg, kv_caches[li], page_table, kv_lens, positions,
            cfg.num_qo_heads, cfg.num_kv_heads, use_pallas,
        )
        new_caches.append(cache)
        x = x + _mm(attn, layer, "o_proj").astype(cfg.dtype)
        h = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        x = x + _moe_block(h, layer, cfg)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = _mm(x, params, "lm_head").astype(jnp.float32)
    return logits, new_caches


def make_ep_sharded_decode_step(
    mapping: Mapping, cfg: MixtralConfig, mesh=None,
):
    """dp x ep sharded Mixtral decode step via shard_map.

    The batch shards over the FLATTENED (dp, ep) axes — every chip holds
    its own token slice — and experts shard contiguously over the ep
    axis (``Mapping.AXIS_TP`` doubles as the expert axis, the ep_experts
    partition).  Attention weights are replicated so the attention
    sublayer runs collective-free on local tokens; ``fused_moe_ep``'s
    allgather dispatch + psum_scatter combine over the ep group is the
    only cross-chip traffic.

    Returns (step_fn, mesh, specs)."""
    mesh = mesh or mapping.make_mesh()
    ep_ax, dp = Mapping.AXIS_TP, Mapping.AXIS_DP
    ep = mapping.tp_size
    assert cfg.num_experts % ep == 0, (cfg.num_experts, ep)

    layer_spec = dict(
        input_norm=P(None),
        q_proj=P(None, None), k_proj=P(None, None), v_proj=P(None, None),
        o_proj=P(None, None),
        post_norm=P(None),
        router=P(None, None),
        w_gate_up=P(ep_ax, None, None),  # experts contiguously sharded
        w_down=P(ep_ax, None, None),
    )
    param_specs = dict(
        embed=P(None, None), final_norm=P(None), lm_head=P(None, None),
        layers=[layer_spec for _ in range(cfg.num_layers)],
    )
    b = P((dp, ep_ax))  # batch over ALL chips
    cache_spec = [
        (P((dp, ep_ax), None, None, None, None),
         P((dp, ep_ax), None, None, None, None))
        for _ in range(cfg.num_layers)
    ]
    in_specs = (
        param_specs, b, b, cache_spec, P((dp, ep_ax), None), b,
    )
    out_specs = (b, cache_spec)

    def step(params, tokens, positions, kv_caches, page_table, kv_lens):
        x = params["embed"][tokens].astype(cfg.dtype)
        new_caches = []
        use_pallas = is_tpu()
        for li, layer in enumerate(params["layers"]):
            h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
            attn, cache = _attn_decode(
                h, layer, cfg,
                (kv_caches[li][0][0], kv_caches[li][1][0]),
                page_table, kv_lens, positions,
                cfg.num_qo_heads, cfg.num_kv_heads, use_pallas,
            )
            new_caches.append((cache[0][None], cache[1][None]))
            x = x + _mm(attn, layer, "o_proj").astype(cfg.dtype)
            h = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
            x = x + _moe_block(
                h, layer, cfg,
                moe_fn=functools.partial(
                    fused_moe_ep, axis=ep_ax, dispatch="allgather"
                ),
            )
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        logits = _mm(x, params, "lm_head").astype(jnp.float32)
        return logits, new_caches

    sharded = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )
    return sharded, mesh, dict(params=param_specs, cache=cache_spec)
