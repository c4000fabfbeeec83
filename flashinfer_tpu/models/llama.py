"""Flagship integration model: Llama-3-style decoder wired to the library.

The reference keeps models in its consumers and ships integration blocks
(``examples/pytorch/flashinfer_modules.py`` — FlashInferAttentionDispatcher /
Linear / RMSNorm / FFN); this module is the TPU equivalent *and* the
end-to-end proof for the minimum slice (SURVEY §7 step 2): a paged-KV batch
decode step built entirely from flashinfer_tpu ops:

    rmsnorm -> qkv proj -> RoPE -> append_paged_kv_cache ->
    paged_decode_attention -> o proj -> fused allreduce+add+rmsnorm (TP) ->
    silu_and_mul MLP -> logits

Two entry points: ``llama_decode_step`` (single device, jittable) and
``make_sharded_decode_step`` (shard_map over a Mapping mesh with dp x tp
sharding; TP allreduces ride ICI via the comm layer).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flashinfer_tpu.activation import silu_and_mul
from flashinfer_tpu.comm.allreduce import allreduce_fusion
from flashinfer_tpu.comm.mapping import Mapping
from flashinfer_tpu.norm import rmsnorm
from flashinfer_tpu.ops.paged_decode import paged_decode_attention
from flashinfer_tpu.ops.xla_ref import xla_paged_decode
from flashinfer_tpu.rope import apply_rope_pos_ids
from flashinfer_tpu.utils import is_tpu


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_qo_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 5e5
    rms_eps: float = 1e-5
    dtype: object = jnp.bfloat16
    # int8 KV serving path: allocate caches as int8 and set the static
    # dequant scales (high_precision = int8 * scale); halves KV HBM traffic
    # and benches ~29% faster than bf16 decode on v5e (test_quant_kv.py,
    # .chip_probe measurements). bf16 caches ignore the scales.
    kv_k_scale: float = 0.05
    kv_v_scale: float = 0.05

    @staticmethod
    def llama3_8b(**over) -> "LlamaConfig":
        return LlamaConfig(**over)

    @staticmethod
    def tiny(**over) -> "LlamaConfig":
        """Small config for tests/dryruns."""
        d = dict(
            vocab_size=512, hidden_size=256, intermediate_size=512,
            num_layers=2, num_qo_heads=8, num_kv_heads=4, head_dim=32,
        )
        d.update(over)
        return LlamaConfig(**d)


def init_llama_params(key: jax.Array, cfg: LlamaConfig) -> Dict:
    """Random-initialized parameter pytree (layout mirrors HF llama naming)."""
    h, qh, kvh, hd = cfg.hidden_size, cfg.num_qo_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 4 + 8 * cfg.num_layers))

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(
            cfg.dtype
        )

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
                gate_proj=w((h, cfg.intermediate_size)),
                up_proj=w((h, cfg.intermediate_size)),
                down_proj=w((cfg.intermediate_size, h)),
            )
        )
    return dict(
        embed=w((cfg.vocab_size, h)),
        final_norm=jnp.ones((h,), cfg.dtype),
        lm_head=w((h, cfg.vocab_size)),
        layers=layers,
    )


_LINEAR_NAMES = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
)


def quantize_llama_weights(params: Dict, include_lm_head: bool = True) -> Dict:
    """Per-output-channel symmetric int8 quantization of every linear
    weight -> params pytree with int8 weights + ``<name>_scale`` entries.

    The int8-weight serving mode (reference analogue: the fp8/int8 weight
    paths of trtllm-gen GEMMs): weights cross HBM at half width and every
    projection runs on the native int8 MXU (``gemm.mm_int8``) with dynamic
    per-row activation quantization.  Embedding stays high-precision (it
    is a gather, not a GEMM)."""
    from flashinfer_tpu.quantization import quantize_int8

    def q(w):
        wq, ws = quantize_int8(w.astype(jnp.float32), axis=0)  # [1, out]
        return wq, ws

    out = dict(params)
    new_layers = []
    for layer in params["layers"]:
        nl = dict(layer)
        for name in _LINEAR_NAMES:
            nl[name], nl[name + "_scale"] = q(layer[name])
        new_layers.append(nl)
    out["layers"] = new_layers
    if include_lm_head:
        out["lm_head"], out["lm_head_scale"] = q(params["lm_head"])
    return out


def _pre_quant(x, store, name="q_proj"):
    """Quantize an activation ONCE for reuse across the projections that
    share it (q/k/v, gate/up) — returns None on the bf16 path."""
    if store[name].dtype != jnp.int8:
        return None
    from flashinfer_tpu.quantization import quantize_int8

    return quantize_int8(x)


def _mm(x, store, name, pre=None):
    """Linear projection dispatching on the stored weight dtype: bf16
    einsum, or int8 MXU with folded activation/weight scales.  ``pre``
    is an optional pre-quantized ``(xq, xs)`` of ``x`` (``_pre_quant``)."""
    w = store[name]
    if w.dtype == jnp.int8:
        from flashinfer_tpu.gemm import mm_int8
        from flashinfer_tpu.quantization import quantize_int8

        xq, xs = pre if pre is not None else quantize_int8(x)
        return mm_int8(xq, w, xs, store[name + "_scale"], out_dtype=x.dtype)
    return x @ w


def _attn_decode(
    x, layer, cfg: LlamaConfig, kv_cache, page_table, kv_lens, positions,
    num_qo_heads: int, num_kv_heads: int, use_pallas: bool,
):
    """One decode-attention sublayer over local (possibly TP-sharded) heads.

    Returns (o_partial [B, qh*hd], updated kv_cache).  Cache layout HND:
    [num_pages, kvh, page_size, hd] (TPU-preferred, ops/paged_decode.py)."""
    B = x.shape[0]
    hd = cfg.head_dim
    pre = _pre_quant(x, layer)
    q = _mm(x, layer, "q_proj", pre).reshape(B, num_qo_heads, hd)
    k = _mm(x, layer, "k_proj", pre).reshape(B, num_kv_heads, hd)
    v = _mm(x, layer, "v_proj", pre).reshape(B, num_kv_heads, hd)
    q, k = apply_rope_pos_ids(q, k, positions, rope_theta=cfg.rope_theta)

    # append this step's K/V: page_table row lookup at the write position
    k_cache, v_cache = kv_cache
    page_size = k_cache.shape[2]
    page_in_req = positions // page_size
    slot = positions % page_size
    page_id = page_table[jnp.arange(B), page_in_req]
    int8_kv = k_cache.dtype == jnp.int8
    if int8_kv:
        from flashinfer_tpu.quantization import quantize_symmetric_int8

        k_w = quantize_symmetric_int8(k, cfg.kv_k_scale)
        v_w = quantize_symmetric_int8(v, cfg.kv_v_scale)
    else:
        k_w, v_w = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
    # scatter [B, kvh, hd] rows into [pages, kvh, page_size, hd]
    k_cache = k_cache.at[page_id, :, slot, :].set(k_w)
    v_cache = v_cache.at[page_id, :, slot, :].set(v_w)

    kv_lens_inc = jnp.maximum(kv_lens, positions + 1)
    sm_scale = 1.0 / float(hd) ** 0.5
    if int8_kv:
        sm_scale = sm_scale * cfg.kv_k_scale
    fn = paged_decode_attention if use_pallas else xla_paged_decode
    kw = {}
    if use_pallas:
        # same tactic cache the decode wrapper consults (measured default
        # "static", scripts/exp_decode_prefetch.py: hides the per-request
        # cold-start chunk DMA with static slot indices); a banked "off"
        # for this shape reaches the model path too
        from flashinfer_tpu.autotuner import AutoTuner
        from flashinfer_tpu.ops.paged_decode import decode_tactic_key

        pf = AutoTuner.get().lookup(
            "paged_decode.prefetch",
            decode_tactic_key(B, page_table.shape[1], num_qo_heads,
                              num_kv_heads, hd, page_size, q.dtype),
            default="static",
        )
        kw["cross_step_prefetch"] = "static" if pf == "static" else False
    o = fn(
        q, k_cache, v_cache, page_table, kv_lens_inc,
        sm_scale=sm_scale, kv_layout="HND", **kw,
    )
    if int8_kv:
        o = (o.astype(jnp.float32) * cfg.kv_v_scale).astype(q.dtype)
    return o.reshape(B, num_qo_heads * hd), (k_cache, v_cache)


def llama_decode_step(
    params: Dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32 (position of the new token)
    kv_caches: List[Tuple[jax.Array, jax.Array]],  # per layer, HND paged
    page_table: jax.Array,  # [B, P]
    kv_lens: jax.Array,  # [B] lens BEFORE this step
    use_pallas: bool = True,
) -> Tuple[jax.Array, List[Tuple[jax.Array, jax.Array]]]:
    """Single-device batched decode step -> (logits [B, vocab], new caches)."""
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
        pre2 = _pre_quant(h, layer, "gate_proj")
        mlp_in = jnp.concatenate(
            [_mm(h, layer, "gate_proj", pre2),
             _mm(h, layer, "up_proj", pre2)], -1
        )
        x = x + _mm(silu_and_mul(mlp_in), layer, "down_proj").astype(cfg.dtype)
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    logits = _mm(x, params, "lm_head").astype(jnp.float32)
    return logits, new_caches



def _tp_param_specs(cfg: LlamaConfig, tp: str, layer_leading=None,
                    quantized: bool = False):
    """Shared TP weight-sharding spec table (column-shard q/k/v/gate/up,
    row-shard o/down); ``layer_leading`` prepends an axis (pp layer stacks).
    With ``quantized``, each linear's [1, out] scale shards with the
    weight's out axis (tp for column-sharded, replicated for row-sharded
    whose out dim is full-width)."""
    def lp(*axes):
        return P(layer_leading, *axes) if layer_leading else P(*axes)

    layer = dict(
        input_norm=lp(None),
        q_proj=lp(None, tp), k_proj=lp(None, tp), v_proj=lp(None, tp),
        o_proj=lp(tp, None),
        post_norm=lp(None),
        gate_proj=lp(None, tp), up_proj=lp(None, tp),
        down_proj=lp(tp, None),
    )
    if quantized:
        for name in ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"):
            layer[name + "_scale"] = lp(None, tp)
        for name in ("o_proj", "down_proj"):
            layer[name + "_scale"] = lp(None, None)
    return layer


def _check_head_divisibility(cfg: LlamaConfig, tp_size: int) -> None:
    assert cfg.num_qo_heads % tp_size == 0, (
        f"num_qo_heads {cfg.num_qo_heads} not divisible by tp {tp_size}"
    )
    assert cfg.num_kv_heads % tp_size == 0, (
        f"num_kv_heads {cfg.num_kv_heads} not divisible by tp {tp_size}"
    )


def make_sharded_decode_step(mapping: Mapping, cfg: LlamaConfig, mesh=None,
                             quantized: bool = False):
    """Build a jitted dp x tp sharded decode step via shard_map.

    Weight sharding: q/k/v/gate/up column-sharded over tp, o/down
    row-sharded; attention runs on local kv heads; the o_proj and down_proj
    partial sums are combined with the fused allreduce(+residual+RMSNorm)
    from the comm layer — the reference's AR+norm fusion pattern
    (trtllm_allreduce_fusion) expressed as a compiled ICI collective.

    Returns (step_fn, mesh, specs) where specs maps each argument to its
    PartitionSpec."""
    mesh = mesh or mapping.make_mesh()
    tp, dp = Mapping.AXIS_TP, Mapping.AXIS_DP
    _check_head_divisibility(cfg, mapping.tp_size)
    qh_l = cfg.num_qo_heads // mapping.tp_size
    kvh_l = cfg.num_kv_heads // mapping.tp_size

    param_specs = dict(
        embed=P(None, None),
        final_norm=P(None),
        lm_head=P(None, tp),
        layers=[
            _tp_param_specs(cfg, tp, quantized=quantized)
            for _ in range(cfg.num_layers)
        ],
    )
    if quantized:
        param_specs["lm_head_scale"] = P(None, tp)
    cache_spec = [(P(dp, None, tp, None, None), P(dp, None, tp, None, None))
                  for _ in range(cfg.num_layers)]
    in_specs = (
        param_specs,
        P(dp),  # tokens [B]
        P(dp),  # positions [B]
        cache_spec,  # per layer (k, v): [dp, pages, kvh, page_size, hd]
        P(dp, None),  # page_table [B, P]
        P(dp),  # kv_lens [B]
    )
    out_specs = (P(dp, tp), cache_spec)

    def step(params, tokens, positions, kv_caches, page_table, kv_lens):
        page_table_l = page_table
        kv_lens_l = kv_lens
        x = params["embed"][tokens].astype(cfg.dtype)
        new_caches = []
        use_pallas = is_tpu()
        for li, layer in enumerate(params["layers"]):
            h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
            attn, cache = _attn_decode(
                h, layer, cfg, (kv_caches[li][0][0], kv_caches[li][1][0]),
                page_table_l, kv_lens_l, positions, qh_l, kvh_l, use_pallas,
            )
            new_caches.append((cache[0][None], cache[1][None]))
            # fused AR + residual-add + post-attention RMSNorm
            o_partial = _mm(attn, layer, "o_proj")
            h, x = allreduce_fusion(
                o_partial, residual=x, rms_weight=layer["post_norm"],
                eps=cfg.rms_eps, axis=tp,
            )
            h = h.astype(cfg.dtype)
            pre2 = _pre_quant(h, layer, "gate_proj")
            mlp_in = jnp.concatenate(
                [_mm(h, layer, "gate_proj", pre2),
                 _mm(h, layer, "up_proj", pre2)], -1
            )
            d_partial = _mm(silu_and_mul(mlp_in), layer, "down_proj")
            # MLP residual uses plain AR + add (next layer norms it)
            (x,) = allreduce_fusion(d_partial, residual=x, axis=tp)
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        logits = _mm(x, params, "lm_head").astype(jnp.float32)  # [B, vocab/tp]
        return logits, new_caches

    sharded = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )
    return sharded, mesh, dict(params=param_specs, cache=cache_spec)


def stack_layer_params(params: Dict) -> Dict:
    """Stack the per-layer weight dicts into leading-layer-dim arrays
    (required for pipeline sharding: the layer dim shards over pp)."""
    layers = params["layers"]
    stacked = {
        k: jnp.stack([l[k] for l in layers]) for k in layers[0]
    }
    out = dict(params)
    out["layers"] = stacked
    return out


def make_cp_prefill_step(mapping: Mapping, cfg: LlamaConfig, mesh=None):
    """dp x cp x tp sharded PREFILL step: sequence sharded over the
    context-parallel axis with **ring attention** (parallel/attention.py),
    heads sharded over tp with fused-AR collectives — the long-context
    configuration (SURVEY §5: ring/Ulysses SP is first-class).

    Processes a [B, S] token block; returns sequence-sharded logits and the
    per-layer K/V (for cache append by the caller).  Causal over the global
    sequence (ring handles cross-chunk masking via positions).
    """
    mesh = mesh or mapping.make_mesh()
    tp, dp, cp = Mapping.AXIS_TP, Mapping.AXIS_DP, Mapping.AXIS_CP
    _check_head_divisibility(cfg, mapping.tp_size)
    qh_l = cfg.num_qo_heads // mapping.tp_size
    kvh_l = cfg.num_kv_heads // mapping.tp_size

    param_specs = dict(
        embed=P(None, None),
        final_norm=P(None),
        lm_head=P(None, tp),
        layers=[_tp_param_specs(cfg, tp) for _ in range(cfg.num_layers)],
    )
    in_specs = (param_specs, P(dp, cp))  # tokens [B, S] seq-sharded over cp
    kv_spec = [(P(dp, cp, tp, None), P(dp, cp, tp, None))
               for _ in range(cfg.num_layers)]
    out_specs = (P(dp, cp, tp), kv_spec)

    from flashinfer_tpu.parallel.attention import ring_attention

    def step(params, tokens):
        B, S_local = tokens.shape
        me = jax.lax.axis_index(cp)
        pos = (me * S_local + jnp.arange(S_local, dtype=jnp.int32))
        x = params["embed"][tokens].astype(cfg.dtype)  # [B, S_local, h]
        kvs = []
        for layer in params["layers"]:
            h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
            pre = _pre_quant(h, layer)
            q = _mm(h, layer, "q_proj", pre).reshape(B, S_local, qh_l, cfg.head_dim)
            k = _mm(h, layer, "k_proj", pre).reshape(B, S_local, kvh_l, cfg.head_dim)
            v = _mm(h, layer, "v_proj", pre).reshape(B, S_local, kvh_l, cfg.head_dim)
            qr, kr = jax.vmap(
                lambda qq, kk: apply_rope_pos_ids(
                    qq, kk, pos, rope_theta=cfg.rope_theta
                )
            )(q, k)
            attn = jax.vmap(
                lambda qq, kk, vv: ring_attention(
                    qq, kk, vv, axis=cp, causal=True,
                    sm_scale=1.0 / float(cfg.head_dim) ** 0.5,
                )
            )(qr, kr, v)
            kvs.append((kr, v))
            o_partial = _mm(attn.reshape(B, S_local, qh_l * cfg.head_dim), layer, "o_proj")
            h2, x = allreduce_fusion(
                o_partial, residual=x, rms_weight=layer["post_norm"],
                eps=cfg.rms_eps, axis=tp,
            )
            h2 = h2.astype(cfg.dtype)
            _pq2 = _pre_quant(h2, layer, "gate_proj")
            mlp_in = jnp.concatenate(
                [_mm(h2, layer, "gate_proj", _pq2),
                 _mm(h2, layer, "up_proj", _pq2)], -1
            )
            d_partial = _mm(silu_and_mul(mlp_in), layer, "down_proj")
            (x,) = allreduce_fusion(d_partial, residual=x, axis=tp)
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        logits = _mm(x, params, "lm_head").astype(jnp.float32)
        return logits, kvs

    sharded = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )
    return sharded, mesh, dict(
        params=param_specs, tokens=P(dp, cp), kv=kv_spec
    )


def _make_pp_layer_runner(cfg, qh_l, kvh_l, tp):
    """Per-stage layer scan shared by the sequential and microbatched pp
    steps (one definition — a numerics fix must reach both schedules)."""

    def run_local_layers(layers, x, caches, page_table, kv_lens, positions):
        use_pallas = is_tpu()

        def body(x, inp):
            layer, kc, vc = inp
            h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
            attn, (kc2, vc2) = _attn_decode(
                h, layer, cfg, (kc, vc), page_table, kv_lens, positions,
                qh_l, kvh_l, use_pallas,
            )
            o_partial = _mm(attn, layer, "o_proj")
            h2, x2 = allreduce_fusion(
                o_partial, residual=x, rms_weight=layer["post_norm"],
                eps=cfg.rms_eps, axis=tp,
            )
            h2 = h2.astype(cfg.dtype)
            _pq2 = _pre_quant(h2, layer, "gate_proj")
            mlp_in = jnp.concatenate(
                [_mm(h2, layer, "gate_proj", _pq2),
                 _mm(h2, layer, "up_proj", _pq2)], -1
            )
            d_partial = _mm(silu_and_mul(mlp_in), layer, "down_proj")
            (x3,) = allreduce_fusion(d_partial, residual=x2, axis=tp)
            return x3, (kc2, vc2)

        kcs, vcs = caches
        x, (kcs2, vcs2) = jax.lax.scan(body, x, (layers, kcs, vcs))
        return x, (kcs2, vcs2)

    return run_local_layers


def make_pp_sharded_decode_step(mapping: Mapping, cfg: LlamaConfig, mesh=None):
    """dp x tp x pp sharded decode step.

    Pipeline parallelism the TPU way: layers stack along a leading dim
    sharded over the ``pp`` mesh axis (Mapping.pp_layers partition);
    activations traverse stages via ``lax.ppermute`` ring hops, and the
    final stage's logits are broadcast back with a masked psum.  Single
    token-batch decode runs the stages sequentially (microbatch overlap is
    a scheduling refinement on top of the same wiring).  TP within each
    stage works exactly as in make_sharded_decode_step (fused AR+norm).

    Expects ``stack_layer_params``-formatted params and per-layer-stacked
    caches ``(k, v) [L, dp, pages, kvh, ps, hd]``.
    """
    mesh = mesh or mapping.make_mesh()
    tp, dp, pp = Mapping.AXIS_TP, Mapping.AXIS_DP, Mapping.AXIS_PP
    assert cfg.num_layers % mapping.pp_size == 0
    _check_head_divisibility(cfg, mapping.tp_size)
    qh_l = cfg.num_qo_heads // mapping.tp_size
    kvh_l = cfg.num_kv_heads // mapping.tp_size
    pp_size = mapping.pp_size

    layer_specs = _tp_param_specs(cfg, tp, layer_leading=pp)
    param_specs = dict(
        embed=P(None, None), final_norm=P(None), lm_head=P(None, tp),
        layers=layer_specs,
    )
    cache_spec = (
        P(pp, dp, None, tp, None, None),
        P(pp, dp, None, tp, None, None),
    )
    in_specs = (param_specs, P(dp), P(dp), cache_spec, P(dp, None), P(dp))
    out_specs = (P(dp, tp), cache_spec)

    run_local_layers = _make_pp_layer_runner(cfg, qh_l, kvh_l, tp)

    def step(params, tokens, positions, kv_caches, page_table, kv_lens):
        my_stage = jax.lax.axis_index(pp)
        x = params["embed"][tokens].astype(cfg.dtype)
        # drop the sharded leading dims: layers [L_local, ...], cache
        # [L_local, 1(dp), pages, kvh_l, ps, hd]
        kcs = kv_caches[0][:, 0]
        vcs = kv_caches[1][:, 0]
        perm = [(i, (i + 1) % pp_size) for i in range(pp_size)]

        def stage_iter(s, carry):
            x, kcs, vcs = carry
            is_mine = my_stage == s
            x2, (kcs2, vcs2) = run_local_layers(
                params["layers"], x, (kcs, vcs), page_table, kv_lens, positions
            )
            # only the active stage advances the activation/caches
            x = jnp.where(is_mine, x2, x)
            kcs = jnp.where(is_mine, kcs2, kcs)
            vcs = jnp.where(is_mine, vcs2, vcs)
            # hand the activation to the next stage
            x = jax.lax.ppermute(x, pp, perm)
            return (x, kcs, vcs)

        x, kcs, vcs = jax.lax.fori_loop(
            0, pp_size, stage_iter, (x, kcs, vcs)
        )
        # after pp_size ring hops the fully-processed activation is back at
        # every rank in turn; it now sits on stage 0 — broadcast via psum
        x = jax.lax.psum(jnp.where(my_stage == 0, x, 0.0), pp)
        x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
        logits = _mm(x, params, "lm_head").astype(jnp.float32)
        return logits, (kcs[:, None], vcs[:, None])

    sharded = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )
    return sharded, mesh, dict(params=param_specs, cache=cache_spec)


def make_pp_microbatch_decode_step(
    mapping: Mapping, cfg: LlamaConfig, num_microbatches: int, mesh=None,
):
    """GPipe-style microbatched dp x tp x pp decode step.

    ``make_pp_sharded_decode_step`` runs the stages sequentially — at any
    tick pp_size - 1 stages idle.  Here each dp shard's token batch
    splits into M microbatches that flow through the stage ring as a
    (M + pp_size - 1)-tick software pipeline: stage s runs microbatch m
    at tick s + m, so in steady state every stage computes while the
    ring ppermutes activations one hop per tick (all inside one jitted
    fori_loop — no host threads, uniform control flow, masked commits).

    Reference analogue: Mapping.pp_layers layer partitioning
    (/root/reference/flashinfer/comm/mapping.py:442); the schedule is
    TPU-native.  Same params/cache/spec layout as the sequential step.
    """
    mesh = mesh or mapping.make_mesh()
    tp, dp, pp = Mapping.AXIS_TP, Mapping.AXIS_DP, Mapping.AXIS_PP
    assert cfg.num_layers % mapping.pp_size == 0
    _check_head_divisibility(cfg, mapping.tp_size)
    qh_l = cfg.num_qo_heads // mapping.tp_size
    kvh_l = cfg.num_kv_heads // mapping.tp_size
    pp_size = mapping.pp_size
    M = int(num_microbatches)

    layer_specs = _tp_param_specs(cfg, tp, layer_leading=pp)
    param_specs = dict(
        embed=P(None, None), final_norm=P(None), lm_head=P(None, tp),
        layers=layer_specs,
    )
    cache_spec = (
        P(pp, dp, None, tp, None, None),
        P(pp, dp, None, tp, None, None),
    )
    in_specs = (param_specs, P(dp), P(dp), cache_spec, P(dp, None), P(dp))
    out_specs = (P(dp, tp), cache_spec)

    run_local_layers = _make_pp_layer_runner(cfg, qh_l, kvh_l, tp)

    def step(params, tokens, positions, kv_caches, page_table, kv_lens):
        my_stage = jax.lax.axis_index(pp)
        b_local = tokens.shape[0]
        assert b_local % M == 0, (
            f"per-dp-shard batch {b_local} must divide into "
            f"{M} microbatches"
        )
        mbs = b_local // M
        x_all = params["embed"][tokens].astype(cfg.dtype)
        kcs = kv_caches[0][:, 0]
        vcs = kv_caches[1][:, 0]
        perm = [(i, (i + 1) % pp_size) for i in range(pp_size)]
        # the final stage BUFFERS its finished activations per tick and
        # the vocab projection (decode's largest matmul at 128k vocab)
        # runs ONCE over the whole batch after the loop — not per tick
        # per stage
        xfin_buf = jnp.zeros((b_local, x_all.shape[1]), cfg.dtype)
        act = jnp.zeros((mbs, x_all.shape[1]), cfg.dtype)

        def tick(t, carry):
            act, kcs, vcs, xfin_buf = carry
            mb_idx = t - my_stage
            active = (mb_idx >= 0) & (mb_idx < M)
            safe = jnp.clip(mb_idx, 0, M - 1)
            row0 = safe * mbs
            # stage 0 ingests a fresh microbatch; later stages use the
            # activation the ring delivered last tick
            fresh = jax.lax.dynamic_slice_in_dim(x_all, row0, mbs, 0)
            inp = jnp.where(my_stage == 0, fresh, act)
            pt_mb = jax.lax.dynamic_slice_in_dim(page_table, row0, mbs, 0)
            lens_mb = jax.lax.dynamic_slice_in_dim(kv_lens, row0, mbs, 0)
            pos_mb = jax.lax.dynamic_slice_in_dim(positions, row0, mbs, 0)
            x2, (kcs2, vcs2) = run_local_layers(
                params["layers"], inp, (kcs, vcs), pt_mb, lens_mb, pos_mb
            )
            # only active ticks commit state (bubbles pass through)
            out_act = jnp.where(active, x2, inp)
            kcs = jnp.where(active, kcs2, kcs)
            vcs = jnp.where(active, vcs2, vcs)
            # final stage banks this microbatch's finished activation
            cur = jax.lax.dynamic_slice_in_dim(xfin_buf, row0, mbs, 0)
            emit = active & (my_stage == pp_size - 1)
            xfin_buf = jax.lax.dynamic_update_slice_in_dim(
                xfin_buf, jnp.where(emit, x2, cur), row0, 0
            )
            act = jax.lax.ppermute(out_act, pp, perm)
            return (act, kcs, vcs, xfin_buf)

        act, kcs, vcs, xfin_buf = jax.lax.fori_loop(
            0, M + pp_size - 1, tick, (act, kcs, vcs, xfin_buf)
        )
        # finished activations live on the last stage; broadcast, then
        # one final-norm + lm_head over the whole batch
        xfin = jax.lax.psum(
            jnp.where(my_stage == pp_size - 1,
                      xfin_buf.astype(jnp.float32), 0.0), pp
        ).astype(cfg.dtype)
        xf = rmsnorm(xfin, params["final_norm"], cfg.rms_eps)
        logits = _mm(xf, params, "lm_head").astype(jnp.float32)
        return logits, (kcs[:, None], vcs[:, None])

    sharded = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )
    return sharded, mesh, dict(params=param_specs, cache=cache_spec)
