"""The int8-weight shard step on one device: the unsharded oracle of
``parallel/plan.py``'s sharded step.

One decode step of an int8 pipeline (int8 weights + int8 KV, fused-wqkv
projections; the shape of one chip's tensor-parallel share of a large
Llama) as ONE jitted program:

- :func:`build_fused_step` — rmsnorm -> fused int8 qkv -> RoPE ->
  **paged int8-KV append** -> int8-KV paged decode attention -> o/mlp
  int8 GEMMs -> lm_head shard -> top-k sampling, with the KV caches,
  page table, lens, and sampling key donated.

``parallel/plan.py build_sharded_fused_step`` runs the same arithmetic
over a mesh; ``tests/test_sharded_step.py`` and ``chip_smoke.py --chips
4`` hold the sharded step to this one's tokens and cache codes.

Scale conventions (sm_scale*k_scale folding, output *v_scale) follow
the models/llama.py int8-KV contract and tests/test_quant_kv.py.
"""

from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Int8ShardSpec:
    """Frozen statics of the int8 shard pipeline (the quantization
    mode, page geometry, and sampling config of the fused step)."""

    bs: int
    hidden: int
    hq: int
    hkv: int
    hd: int
    inter: int
    vocab_shard: int
    page_size: int = 16
    k_scale: float = 0.05
    v_scale: float = 0.05
    top_k: int = 40
    # attention backend, resolved EAGERLY when the spec is built
    # (pass flashinfer_tpu.utils.is_tpu()): the step closure reads no
    # environment at trace time (L003 staticness)
    use_pallas: bool = False

    @property
    def qdim(self) -> int:
        return self.hq * self.hd

    @property
    def kvdim(self) -> int:
        return self.hkv * self.hd


def shard_layer(x, w, kcl, vcl, pt, lens, spec: Int8ShardSpec):
    """One decoder layer of the int8 shard pipeline, INCLUDING the
    per-step paged KV append (quantize + scatter of the new token's
    K/V — the real serving write path; the fused step never excludes
    it).  ``w`` is the per-layer weight tuple
    ``(wqkv, sqkv, wo, so, wgu, sgu, wd, sd, n1, n2)``."""
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.gemm import mm_int8
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.ops import paged_decode_attention
    from flashinfer_tpu.ops.xla_ref import xla_paged_decode
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.rope import apply_rope_pos_ids

    bs, qdim, kvdim = spec.bs, spec.qdim, spec.kvdim
    PS = spec.page_size
    wqkv, sqkv, wo, so, wgu, sgu, wd, sd, n1, n2 = w
    h = rmsnorm(x, n1.astype(x.dtype))
    hq8, hs = quantize_int8(h)
    qkv = mm_int8(hq8, wqkv, hs, sqkv)
    q = qkv[:, :qdim].reshape(bs, spec.hq, spec.hd)
    k = qkv[:, qdim:qdim + kvdim].reshape(bs, spec.hkv, spec.hd)
    q, k = apply_rope_pos_ids(q, k, lens)
    v = qkv[:, qdim + kvdim:].reshape(bs, spec.hkv, spec.hd)
    pages = jnp.take_along_axis(pt, lens[:, None] // PS, axis=1)[:, 0]
    slots = lens % PS
    k8 = jnp.clip(jnp.round(k.astype(jnp.float32) / spec.k_scale),
                  -127, 127).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(v.astype(jnp.float32) / spec.v_scale),
                  -127, 127).astype(jnp.int8)
    kcl = kcl.at[pages, :, slots, :].set(k8)
    vcl = vcl.at[pages, :, slots, :].set(v8)
    attn_fn = paged_decode_attention if spec.use_pallas \
        else xla_paged_decode
    attn = attn_fn(
        q.astype(jnp.bfloat16), kcl, vcl, pt, lens + 1,
        sm_scale=spec.hd ** -0.5 * spec.k_scale, kv_layout="HND",
    ) * spec.v_scale
    a8, as_ = quantize_int8(attn.reshape(bs, qdim).astype(x.dtype))
    x = x + mm_int8(a8, wo, as_, so)
    h2 = rmsnorm(x, n2.astype(x.dtype))
    g8, gs = quantize_int8(h2)
    mlp = silu_and_mul(mm_int8(g8, wgu, gs, sgu))
    m8, ms = quantize_int8(mlp)
    x = (x + mm_int8(m8, wd, ms, sd)).astype(x.dtype)
    return x, kcl, vcl


def head_and_sample(x, head, head_s, skey, spec: Int8ShardSpec):
    """The lm_head shard + top-k sampling epilogue; the sampled token
    folds into the PRNG key so consecutive steps chain without an
    embedding matrix (the shard pipeline has none)."""
    from flashinfer_tpu.gemm import mm_int8
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.sampling import (sampling_from_logits,
                                         top_k_mask_logits)

    hq8, hs = quantize_int8(
        rmsnorm(x, jnp.ones((spec.hidden,), x.dtype)))
    logits = mm_int8(hq8, head, hs, head_s, out_dtype=jnp.float32)
    tok = sampling_from_logits(top_k_mask_logits(logits, spec.top_k),
                               skey)
    return tok, jax.random.fold_in(skey, tok[0])


def build_fused_step(spec: Int8ShardSpec, *, donate: bool = True):
    """The compile-once fused shard step: ONE jitted program per
    serving session, KV caches / page table / lens / PRNG key donated.

    Signature: ``step(x0, layer_ws, caches, head, head_s, pt, lens,
    skey) -> (tok, caches, pt, lens, skey)`` where ``layer_ws`` is a
    list of per-layer weight tuples and ``caches`` a matching list of
    ``(k, v)`` int8 HND pages.  ``lens`` passes through unchanged
    (each step overwrites the same slot; a serving engine advances
    lens between plans)."""

    def _body(x0, layer_ws, caches, head, head_s, pt, lens, skey):
        x = x0
        new_caches = []
        for w, (kcl, vcl) in zip(layer_ws, caches):
            x, kcl, vcl = shard_layer(x, w, kcl, vcl, pt, lens, spec)
            new_caches.append((kcl, vcl))
        tok, skey = head_and_sample(x, head, head_s, skey, spec)
        return tok, new_caches, pt, lens, skey

    donate_argnums = (2, 5, 6, 7) if donate else ()
    return jax.jit(_body, donate_argnums=donate_argnums)
