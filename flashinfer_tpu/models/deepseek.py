"""DeepSeek-V3-style MLA + routed-MoE decoder wired to the library.

The third integration model family (reference serves this architecture
through its MLA + fused-MoE + DSv3-routing blocks: ``flashinfer/mla/``,
``flashinfer/fused_moe/``, ``noAuxTcKernels``; benchmarks
``bench_deepseek_mla.py``).  What it exercises end-to-end that llama/
mixtral do not:

- **MLA decode with weight absorption**: queries live in the compressed
  latent space — ``q_nope`` is absorbed through ``w_kc`` into the ckv
  dimension BEFORE attention, so the paged cache stores only the latent
  ``ckv`` (kv_lora_rank) plus the shared rope key ``kpe``; attention
  runs on ``ops/mla_decode`` and the output is un-absorbed through
  ``w_vc``.  The kpe cache uses the TPU-native lane-padded-128 layout.
- **DeepSeek-V3 no-aux routing**: sigmoid scores + correction bias,
  group-limited top-k (``route_deepseek_v3``), router logits via
  ``dsv3_ops.router_gemm``, plus a SHARED expert alongside the routed
  block and dense first-k layers — the real DSv3 layer plan.

Entry points mirror ``models/mixtral.py``:

- ``deepseek_decode_step`` — single device, jittable.
- ``make_ep_sharded_decode_step`` — shard_map over dp x ep: attention +
  shared expert replicated per chip on its local batch rows, routed
  experts contiguously sharded over ep via ``fused_moe_ep``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flashinfer_tpu.activation import silu_and_mul
from flashinfer_tpu.comm.mapping import Mapping
from flashinfer_tpu.dsv3_ops import router_gemm
from flashinfer_tpu.fused_moe import fused_moe, fused_moe_ep
from flashinfer_tpu.fused_moe.routing import route_deepseek_v3
from flashinfer_tpu.norm import rmsnorm
from flashinfer_tpu.ops.mla_decode import (
    mla_paged_decode_attention,
    xla_mla_paged_decode,
)
from flashinfer_tpu.utils import is_tpu


@dataclass(frozen=True)
class DeepseekConfig:
    # defaults are DeepSeek-V3 671B scale (config.json of the released
    # model); use .tiny() for test shapes
    vocab_size: int = 129280
    hidden_size: int = 7168
    num_layers: int = 61
    num_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512  # ckv latent dim
    head_dim_nope: int = 128  # per-head latent-query dim
    head_dim_kpe: int = 64  # shared rope dim
    # MoE
    num_experts: int = 256
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 2048
    shared_intermediate_size: int = 2048
    first_k_dense: int = 3  # leading dense-MLP layers
    dense_intermediate_size: int = 18432
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    dtype: object = jnp.float32

    @staticmethod
    def tiny(**over) -> "DeepseekConfig":
        d = dict(
            vocab_size=512, hidden_size=256, num_layers=2, num_heads=4,
            q_lora_rank=96, kv_lora_rank=128, head_dim_nope=32,
            head_dim_kpe=64, num_experts=8, top_k=2, n_group=4,
            topk_group=2, routed_scaling_factor=1.0,
            moe_intermediate_size=64, shared_intermediate_size=64,
            first_k_dense=1, dense_intermediate_size=128,
        )
        d.update(over)
        return DeepseekConfig(**d)


def init_deepseek_params(key: jax.Array, cfg: DeepseekConfig) -> Dict:
    h, H = cfg.hidden_size, cfg.num_heads
    nope, kpe, ckv = cfg.head_dim_nope, cfg.head_dim_kpe, cfg.kv_lora_rank
    keys = iter(jax.random.split(key, 4 + 16 * cfg.num_layers))

    def w(shape, scale=0.02):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * scale
        ).astype(cfg.dtype)

    layers = []
    for li in range(cfg.num_layers):
        layer = dict(
            input_norm=jnp.ones((h,), cfg.dtype),
            q_a=w((h, cfg.q_lora_rank)),
            q_a_norm=jnp.ones((cfg.q_lora_rank,), cfg.dtype),
            q_b=w((cfg.q_lora_rank, H * (nope + kpe))),
            kv_a=w((h, ckv + kpe)),
            kv_a_norm=jnp.ones((ckv,), cfg.dtype),
            # absorption weights (reference k_b/v_b projections reshaped
            # per head): scores and outputs stay in the ckv latent space
            w_kc=w((H, nope, ckv)),
            w_vc=w((H, ckv, nope)),
            o_proj=w((H * nope, h)),
            post_norm=jnp.ones((h,), cfg.dtype),
        )
        if li < cfg.first_k_dense:
            di = cfg.dense_intermediate_size
            layer.update(
                gate_up=w((h, 2 * di)),
                down=w((di, h)),
            )
        else:
            E, I = cfg.num_experts, cfg.moe_intermediate_size
            Is = cfg.shared_intermediate_size
            layer.update(
                router=w((h, E), scale=0.1).astype(jnp.float32),
                e_bias=jnp.zeros((E,), jnp.float32),
                w_gate_up=w((E, h, 2 * I)),
                w_down=w((E, I, h)),
                shared_gate_up=w((h, 2 * Is)),
                shared_down=w((Is, h)),
            )
        layers.append(layer)
    return dict(
        embed=w((cfg.vocab_size, h)),
        final_norm=jnp.ones((h,), cfg.dtype),
        lm_head=w((h, cfg.vocab_size)),
        layers=layers,
    )


def _project_latents(x, layer, cfg: DeepseekConfig, positions):
    """Shared per-token MLA projections on a FLAT token axis: returns
    (q_nope [N, H, nope], roped q_pe [N, H, kpe], ckv [N, ckv],
    roped kpe [N, kpe]) — one definition for the decode step and the
    prefill path (their cache-sharing contract depends on identical
    latent math)."""
    from flashinfer_tpu.mla import project_latents

    return project_latents(
        x, layer, positions, num_heads=cfg.num_heads, nope=cfg.head_dim_nope,
        rope=cfg.head_dim_kpe, ckv=cfg.kv_lora_rank, eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta)[1:]


def _append_latents(cache, rows, ckv, kpe_vec, kpe_dim: int):
    """Write per-token latents into the paged (ckv, lane-padded kpe)
    caches at flat ``rows`` — the ONE cache-append definition."""
    ckv_cache, kpe_cache = cache
    cflat = ckv_cache.reshape(-1, ckv_cache.shape[-1])
    pflat = kpe_cache.reshape(-1, kpe_cache.shape[-1])
    cflat = cflat.at[rows].set(ckv.astype(cflat.dtype))
    pflat = pflat.at[rows, :kpe_dim].set(kpe_vec.astype(pflat.dtype))
    return cflat.reshape(ckv_cache.shape), pflat.reshape(kpe_cache.shape)


def _mla_attn_decode(
    x, layer, cfg: DeepseekConfig, cache, page_table, kv_lens, positions,
    use_pallas: bool,
):
    """Absorbed MLA decode sublayer -> (o [B, H*nope], new (ckv, kpe)).

    Score identity: ``q_nope_abs . ckv_j == q_nope . (w_kc ckv_j)`` — the
    unabsorbed per-head key — so softmax scale is 1/sqrt(nope + kpe),
    the reference's qk_head_dim scale."""
    B = x.shape[0]
    H, nope, kpe = cfg.num_heads, cfg.head_dim_nope, cfg.head_dim_kpe

    q_nope, q_pe, ckv_new, kpe_new = _project_latents(
        x, layer, cfg, positions
    )

    # absorb the nope query into the latent space: [B, H, ckv]
    q_abs = jnp.einsum(
        "bhn,hnc->bhc", q_nope.astype(jnp.float32),
        layer["w_kc"].astype(jnp.float32),
    ).astype(x.dtype)

    # append this token's (ckv, kpe) into the paged caches
    ps = cache[0].shape[1]
    page_id = page_table[jnp.arange(B), positions // ps]
    rows = page_id * ps + positions % ps
    ckv_cache, kpe_cache = _append_latents(
        cache, rows, ckv_new, kpe_new, kpe
    )

    kv_lens_inc = jnp.maximum(kv_lens, positions + 1)
    sm_scale = 1.0 / float(nope + kpe) ** 0.5
    fn = mla_paged_decode_attention if use_pallas else xla_mla_paged_decode
    out = fn(
        q_abs, q_pe, ckv_cache, kpe_cache, page_table, kv_lens_inc,
        sm_scale=sm_scale,
    )  # [B, H, ckv]

    # un-absorb: latent outputs back to per-head nope dims
    o = jnp.einsum(
        "bhc,hcn->bhn", out.astype(jnp.float32),
        layer["w_vc"].astype(jnp.float32),
    ).astype(x.dtype)
    return o.reshape(B, H * nope), (ckv_cache, kpe_cache)


def _dsv3_moe_block(h, layer, cfg: DeepseekConfig, moe_fn=fused_moe):
    """DSv3 layer MLP: no-aux-routed experts + the always-on shared
    expert.  ``moe_fn`` swaps in the EP-sharded kernel (routing stays in
    ONE place for both step builders)."""
    logits = router_gemm(h, layer["router"])
    wts, ids = route_deepseek_v3(
        logits, layer["e_bias"], cfg.top_k, cfg.n_group, cfg.topk_group,
        cfg.routed_scaling_factor,
    )
    routed = moe_fn(
        h, layer["w_gate_up"], layer["w_down"], wts, ids, cfg.num_experts
    )
    shared = silu_and_mul(h @ layer["shared_gate_up"]) @ layer["shared_down"]
    return (routed + shared.astype(jnp.float32)).astype(h.dtype)


def _layer_mlp(h, layer, cfg: DeepseekConfig, moe_fn=fused_moe):
    if "router" in layer:
        return _dsv3_moe_block(h, layer, cfg, moe_fn)
    return (silu_and_mul(h @ layer["gate_up"]) @ layer["down"]).astype(
        h.dtype
    )


def deepseek_prefill(
    params: Dict,
    cfg: DeepseekConfig,
    tokens: jax.Array,  # [B, L] int32 prompt tokens
    caches: List[Tuple[jax.Array, jax.Array]],  # per layer (ckv, kpe)
    page_table: jax.Array,  # [B, max_pages]
):
    """Batched prefill -> (logits [B, L, vocab], caches).

    MLA prefill runs UNABSORBED (the reference's prefill regime: at long
    q the per-head materialization amortizes, and the fmha path wants
    standard per-head K/V): explicit per-head keys ``k_nope = w_kc ckv``
    and values ``v = w_vc^T ckv`` run through the library's STREAMING
    segment-flash attention (asymmetric qk/vo head dims; scores never
    materialize as [L, L] per head), while the paged cache still stores
    only the LATENT (ckv, lane-padded kpe) — so decode continues
    ABSORBED from the same cache.  The absorption identity makes the two
    regimes numerically interchangeable (tested against a pure
    stepwise-decode consumption)."""
    from flashinfer_tpu.ops.flash_attention import flash_attention
    from flashinfer_tpu.ops.xla_ref import xla_ragged_attention
    from flashinfer_tpu.utils import is_tpu

    B, L = tokens.shape
    H, nope, kpe = cfg.num_heads, cfg.head_dim_nope, cfg.head_dim_kpe
    N = B * L
    sm = 1.0 / float(nope + kpe) ** 0.5
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    pos_flat = positions.reshape(-1)
    seg = jnp.repeat(jnp.arange(B, dtype=jnp.int32), L)
    attn_fn = flash_attention if is_tpu() else xla_ragged_attention

    x = params["embed"][tokens].astype(cfg.dtype).reshape(N, -1)
    new_caches = []
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        q_nope, q_pe, ckv, kpe_vec = _project_latents(
            h, layer, cfg, pos_flat
        )
        # append the latents into the paged cache (decode reads these)
        ps = caches[li][0].shape[1]
        page_id = jnp.take_along_axis(page_table, positions // ps, axis=1)
        rows = (page_id * ps + positions % ps).reshape(-1)
        new_caches.append(
            _append_latents(caches[li], rows, ckv, kpe_vec, kpe)
        )

        # unabsorbed per-head K/V from the latent; attention streams
        # through the segment-flash kernel (qk dim nope+kpe, vo dim nope)
        k_nope = jnp.einsum(
            "nc,hdc->nhd", ckv.astype(jnp.float32),
            layer["w_kc"].astype(jnp.float32),
        )
        v = jnp.einsum(
            "nc,hcd->nhd", ckv.astype(jnp.float32),
            layer["w_vc"].astype(jnp.float32),
        )
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kpe_vec[:, None, :].astype(
                jnp.float32), (N, H, kpe))],
            axis=-1,
        ).astype(cfg.dtype)
        q = jnp.concatenate(
            [q_nope.astype(jnp.float32), q_pe.astype(jnp.float32)], -1
        ).astype(cfg.dtype)
        attn = attn_fn(
            q, k, v.astype(cfg.dtype), seg, seg, pos_flat, pos_flat,
            causal=True, sm_scale=sm,
        )  # [N, H, nope]
        x = x + (
            attn.reshape(N, H * nope).astype(cfg.dtype) @ layer["o_proj"]
        ).astype(cfg.dtype)
        h = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        x = x + _layer_mlp(h, layer, cfg)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits.reshape(B, L, -1), new_caches


def deepseek_decode_step(
    params: Dict,
    cfg: DeepseekConfig,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] write positions
    caches: List[Tuple[jax.Array, jax.Array]],  # per layer (ckv, kpe)
    page_table: jax.Array,  # [B, max_pages]
    kv_lens: jax.Array,  # [B]
    use_pallas: Optional[bool] = None,
):
    """Single-device batched decode step -> (logits [B, vocab], caches).

    ``use_pallas`` defaults to the platform (``is_tpu()``) — on a real
    chip the paged MLA kernel runs, off-chip the XLA dense-gather
    reference; pass explicitly to pin either."""
    if use_pallas is None:
        use_pallas = is_tpu()
    x = params["embed"][tokens].astype(cfg.dtype)
    new_caches = []
    for li, layer in enumerate(params["layers"]):
        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        attn, cache = _mla_attn_decode(
            h, layer, cfg, caches[li], page_table, kv_lens, positions,
            use_pallas,
        )
        new_caches.append(cache)
        x = x + (attn @ layer["o_proj"]).astype(cfg.dtype)
        h = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        x = x + _layer_mlp(h, layer, cfg)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return (x @ params["lm_head"]).astype(jnp.float32), new_caches


def make_ep_sharded_decode_step(
    mapping: Mapping, cfg: DeepseekConfig, mesh=None,
):
    """dp x ep sharded DeepSeek decode step via shard_map.

    Batch shards over the FLATTENED (dp, ep) axes; routed experts shard
    contiguously over ep (``Mapping.AXIS_TP`` doubles as the expert
    axis).  Attention + shared expert are replicated and run
    collective-free on local rows; ``fused_moe_ep``'s allgather dispatch
    + psum_scatter combine is the only cross-chip traffic.  Dense
    first-k layers stay fully local.

    Returns (step_fn, mesh, specs)."""
    from jax import shard_map

    mesh = mesh or mapping.make_mesh()
    ep_ax, dp = Mapping.AXIS_TP, Mapping.AXIS_DP
    ep = mapping.tp_size
    assert cfg.num_experts % ep == 0, (cfg.num_experts, ep)

    # layer param structure is fully determined by cfg (first_k_dense),
    # so specs build from cfg at construction time and the shard_map is
    # wrapped + jitted ONCE (mixtral/llama builder pattern — the decode
    # loop must replay a compiled step, not re-trace per token)
    def layer_spec(li: int):
        names = ["input_norm", "q_a", "q_a_norm", "q_b", "kv_a",
                 "kv_a_norm", "w_kc", "w_vc", "o_proj", "post_norm"]
        ndims = dict(input_norm=1, q_a=2, q_a_norm=1, q_b=2, kv_a=2,
                     kv_a_norm=1, w_kc=3, w_vc=3, o_proj=2, post_norm=1)
        if li < cfg.first_k_dense:
            names += ["gate_up", "down"]
            ndims.update(gate_up=2, down=2)
        else:
            names += ["router", "e_bias", "w_gate_up", "w_down",
                      "shared_gate_up", "shared_down"]
            ndims.update(router=2, e_bias=1, w_gate_up=3, w_down=3,
                         shared_gate_up=2, shared_down=2)
        spec = {k: P(*([None] * ndims[k])) for k in names}
        if li >= cfg.first_k_dense:
            spec["w_gate_up"] = P(ep_ax, None, None)
            spec["w_down"] = P(ep_ax, None, None)
        return spec

    b = P((dp, ep_ax))  # batch over ALL chips
    cache_spec = [
        (P((dp, ep_ax), None, None, None), P((dp, ep_ax), None, None, None))
        for _ in range(cfg.num_layers)
    ]
    param_specs = dict(
        embed=P(None, None), final_norm=P(None), lm_head=P(None, None),
        layers=[layer_spec(li) for li in range(cfg.num_layers)],
    )

    def step(params, tokens, positions, caches, page_table, kv_lens):
        x = params["embed"][tokens].astype(cfg.dtype)
        new_caches = []
        use_pallas = is_tpu()
        ep_moe = functools.partial(
            fused_moe_ep, axis=ep_ax, dispatch="allgather"
        )
        for li, layer in enumerate(params["layers"]):
            h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
            attn, cache = _mla_attn_decode(
                h, layer, cfg,
                (caches[li][0][0], caches[li][1][0]),
                page_table, kv_lens, positions, use_pallas,
            )
            new_caches.append((cache[0][None], cache[1][None]))
            x = x + (attn @ layer["o_proj"]).astype(cfg.dtype)
            h = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
            x = x + _layer_mlp(h, layer, cfg, moe_fn=ep_moe)
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        return (x @ params["lm_head"]).astype(jnp.float32), new_caches

    sharded = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(param_specs, b, b, cache_spec, P((dp, ep_ax), None), b),
            out_specs=(b, cache_spec),
            check_vma=False,
        )
    )
    return sharded, mesh, dict(params=param_specs, cache=cache_spec)
