"""Prefill/append attention: stateless single op + batch plan/run wrappers.

TPU-native re-design of the reference prefill layer
(``flashinfer/prefill.py:1117,1492,2947``; kernels prefill.cuh:2448-4057;
plan ``PrefillPlan``/``PrefillSplitQOKVIndptr`` scheduler.cuh:545-897).

The reference's plan bin-packs (request, qo-tile, kv-chunk) work units onto
CTAs.  The TPU design replaces that with *flattened token axes + segment
ids*: plan() lays all requests end-to-end on one padded token axis and
emits per-token segment/position arrays; the one flash kernel
(ops/flash_attention.py) then serves single, ragged-batch and paged-batch
prefill.  Padding is bucketed (powers of two) to bound recompiles.

For the paged case, plan() precomputes the flat cache-row gather index for
every kv token, so run() is gather + flash kernel — prefill is
compute-bound, so the one extra HBM pass is cheap relative to the matmuls
(documented trade-off; a fused paged-prefill kernel is a later
optimization).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from flashinfer_tpu.api_logging import flashinfer_api
import numpy as np

from flashinfer_tpu.ops.flash_attention import flash_attention
from flashinfer_tpu.ops.xla_ref import xla_ragged_attention
from flashinfer_tpu.utils import (
    check_kv_layout,
    check_pos_encoding_mode,
    fold_scalar_scale,
    get_alibi_slopes,
    get_sm_scale,
    next_power_of_two,
    normalize_backend,
    resolve_backend,
    TensorLayout,
)

_Q_PAD_SEG = -1
_KV_PAD_SEG = -2


def _apply_plan_rope(plan, q, k):
    """ROPE_LLAMA pre-pass shared by the batch wrappers' run() paths:
    rotate q/k at the plan's absolute positions (sub-16-bit caches upcast
    first — rotating in fp8 would re-quantize every key; bf16 keeps the
    native dtype, the same one-rounding a rotated-at-append cache has)."""
    if plan.rope is None:
        return q, k
    from flashinfer_tpu.rope import rotate_at_positions

    rs, rt = plan.rope
    if k.dtype.itemsize < 2:
        k = k.astype(jnp.bfloat16)
    return (rotate_at_positions(q, plan.q_pos, rs, rt),
            rotate_at_positions(k, plan.kv_pos, rs, rt))

# ALiBi rides the dense xla path, which materializes [H, Tq_pad, Tkv_pad]
# f32 logits; cap that tensor so a long-context ALiBi prefill fails with
# instructions instead of an opaque device OOM (the Pallas flash kernel
# has no bias mode yet — chunk the prefill or precompute additive masks)
_ALIBI_DENSE_LOGITS_CAP = 4 << 30


def _check_alibi_dense_size(num_heads: int, tq: int, tkv: int) -> None:
    need = num_heads * tq * tkv * 4
    if need > _ALIBI_DENSE_LOGITS_CAP:
        raise NotImplementedError(
            f"pos_encoding_mode='ALIBI' runs on the dense path; this "
            f"geometry needs {need / (1 << 30):.1f} GiB of logits "
            f"({num_heads} heads x {tq} x {tkv}). Chunk the prefill to "
            f"shorter qo spans (kv length is the roofline term that "
            f"matters) or open an issue for a biased flash kernel."
        )

# flash-kernel launch-geometry candidates: (block_q, block_kv).  The tactic
# space the reference explores per-arch via jinja template instantiation
# (prefill.cuh CTA_TILE_Q x CTA_TILE_KV) collapses on TPU to these two
# Pallas grid block sizes; VMEM (scratch = bq x D f32 + 2 x bq x 128) and
# MXU utilization trade off across them.
_FLASH_BLOCK_CANDIDATES = (
    (256, 512), (128, 512), (512, 512), (256, 1024), (128, 1024), (256, 256),
)


def flash_block_key(total_q, total_kv, num_qo_heads, num_kv_heads,
                    head_dim, dtype, causal) -> tuple:
    """The ``flash_attention.blocks`` tactic key for a shape — pow2-
    bucketed token axes keep the key space finite and make shipped-config
    keys hit across nearby lengths.  THE key builder (``_tuned_flash``
    calls it)."""
    return (
        next_power_of_two(max(int(total_q), 16)),
        next_power_of_two(max(int(total_kv), 128)),
        num_qo_heads, num_kv_heads, head_dim, str(dtype), int(causal),
    )


def _tuned_flash(
    q, k, v, q_seg, kv_seg, q_pos, kv_pos, *,
    causal, sm_scale, logits_soft_cap, window_left, return_lse,
    alibi_slopes=None,
):
    """flash_attention with autotuned (block_q, block_kv).

    Zero-overhead outside an ``autotune()`` context: shipped v5e/v5p config
    or defaults are used (reference AutoTuner.choose_one over kernel
    tactics, autotuner.py:1419)."""
    from flashinfer_tpu.autotuner import AutoTuner

    from flashinfer_tpu.ops import flash_attention as _fa_module

    kwargs = dict(
        causal=causal, sm_scale=sm_scale, logits_soft_cap=logits_soft_cap,
        window_left=window_left, return_lse=return_lse,
    )
    if alibi_slopes is not None:
        kwargs["alibi_slopes"] = alibi_slopes
    key = flash_block_key(
        q.shape[0], k.shape[0], q.shape[1], k.shape[1], q.shape[2],
        q.dtype, causal,
    )
    bq, bkv = AutoTuner.get().choose_one(
        "flash_attention.blocks", key, _FLASH_BLOCK_CANDIDATES,
        lambda c: (lambda: flash_attention(
            q, k, v, q_seg, kv_seg, q_pos, kv_pos,
            block_q=c[0], block_kv=c[1], **kwargs,
        )),
        default=_FLASH_BLOCK_CANDIDATES[0],
        module=_fa_module,
    )
    return flash_attention(
        q, k, v, q_seg, kv_seg, q_pos, kv_pos,
        block_q=int(bq), block_kv=int(bkv), **kwargs,
    )


@flashinfer_api
def single_prefill_with_kv_cache(
    q: jax.Array,  # [qo_len, num_qo_heads, head_dim]
    k: jax.Array,  # [kv_len, num_kv_heads, head_dim] (NHD) or HND
    v: jax.Array,
    scale_q: Optional[jax.Array] = None,
    scale_k: Optional[jax.Array] = None,
    scale_v: Optional[jax.Array] = None,
    o_dtype=None,
    custom_mask: Optional[jax.Array] = None,
    packed_custom_mask: Optional[jax.Array] = None,
    causal: bool = False,
    kv_layout: str = "NHD",
    pos_encoding_mode: str = "NONE",
    use_fp16_qk_reduction: bool = False,
    sm_scale: Optional[float] = None,
    window_left: int = -1,
    logits_soft_cap: Optional[float] = None,
    rope_scale: Optional[float] = None,
    rope_theta: Optional[float] = None,
    backend: str = "auto",
    return_lse: bool = False,
    kv_cache_sf=None,
    k_scale: Optional[float] = None,
    v_scale: Optional[float] = None,
):
    """Single-request prefill/append attention (reference
    ``single_prefill_with_kv_cache``, flashinfer/prefill.py:1117) with
    the reference's FULL kwargs surface and positional order (scale_q/
    scale_k/scale_v sit between v and o_dtype).

    Causal alignment is bottom-right: query ``i`` attends to kv positions
    ``<= kv_len - qo_len + i`` (matching the reference's append semantics).
    ``custom_mask`` ([qo_len, kv_len] bool) / ``packed_custom_mask``
    (packbits form) route through the xla backend (dense mask — the
    reference's MaskMode::kCustom).

    Scale handling mirrors the reference fp8 regime by FOLDING:
    per-tensor scale_q/scale_k (and float k_scale, scalar
    kv_cache_sf[k]) multiply the softmax scale; scale_v / v_scale /
    kv_cache_sf[v] multiply the output.  Non-scalar (per-head/block)
    scale tensors are a different numerics regime and are rejected.
    ``use_fp16_qk_reduction`` is a CUDA-accumulator knob (inert: the MXU
    accumulates f32).  ``pos_encoding_mode="ROPE_LLAMA"`` rotates q/k at
    their absolute positions as an elementwise pre-pass (rope_scale/
    rope_theta honored; position-equivalent to the reference's in-kernel
    rotation) before any backend.  ``pos_encoding_mode="ALIBI"`` adds
    ``slope_h * (kv_pos - q_pos)`` to the scaled logits (reference
    variants.cuh:68) on the dense xla backend by default, in-kernel with
    explicit backend="pallas"."""
    check_pos_encoding_mode(pos_encoding_mode)  # typos raise KeyError
    alibi = pos_encoding_mode == "ALIBI"
    if check_kv_layout(kv_layout) == TensorLayout.HND:
        k = jnp.swapaxes(k, 0, 1)
        v = jnp.swapaxes(v, 0, 1)
    qo_len, _, head_dim = q.shape
    kv_len = k.shape[0]
    sm_scale = get_sm_scale(head_dim, sm_scale)
    if pos_encoding_mode == "ROPE_LLAMA":
        # in-attention RoPE (reference applies it in-kernel from an
        # unrotated cache): rotate q at its bottom-right-aligned absolute
        # positions and k at 0..kv_len-1 as an elementwise pre-pass —
        # position-equivalent, and every backend (incl. the flash
        # kernel) then serves the rotated tensors at full speed
        from flashinfer_tpu.rope import rotate_at_positions

        q = rotate_at_positions(
            q, jnp.arange(qo_len, dtype=jnp.int32) + (kv_len - qo_len),
            rope_scale=rope_scale or 1.0, rope_theta=rope_theta or 1e4,
        )
        k = rotate_at_positions(
            k, jnp.arange(kv_len, dtype=jnp.int32),
            rope_scale=rope_scale or 1.0, rope_theta=rope_theta or 1e4,
        )

    def _fold(x, name):
        return fold_scalar_scale(
            x, f"single_prefill_with_kv_cache {name}")

    out_mul = 1.0
    for s, nm in ((scale_q, "scale_q"), (scale_k, "scale_k"),
                  (k_scale, "k_scale")):
        f = _fold(s, nm)
        if f is not None:
            sm_scale *= f
    for s, nm in ((scale_v, "scale_v"), (v_scale, "v_scale")):
        f = _fold(s, nm)
        if f is not None:
            out_mul *= f
    if kv_cache_sf is not None:
        ksf, vsf = (kv_cache_sf if isinstance(kv_cache_sf, tuple)
                    else (kv_cache_sf, kv_cache_sf))
        ksf = _fold(ksf, "kv_cache_sf[k]")
        vsf = _fold(vsf, "kv_cache_sf[v]")
        sm_scale *= 1.0 if ksf is None else ksf
        out_mul *= 1.0 if vsf is None else vsf
    if packed_custom_mask is not None and custom_mask is None:
        # reference mask-bit convention is LSB-first within each byte
        # (flashinfer packbits bitorder='little')
        bits = jnp.unpackbits(
            packed_custom_mask.view(jnp.uint8), count=qo_len * kv_len,
            bitorder="little",
        )
        custom_mask = bits.reshape(qo_len, kv_len).astype(bool)
    explicit_pallas = backend == "pallas"
    backend = resolve_backend(backend, "single_prefill")
    kw = {}
    if alibi:
        kw["alibi_slopes"] = get_alibi_slopes(q.shape[1])
        if explicit_pallas and custom_mask is None:
            # explicit backend="pallas": the flash kernel's in-kernel bias
            # (SMEM slope per grid head) — no dense logits tensor.
            # Opt-in until the biased kernel has an on-chip verdict.
            # (a custom_mask call still lands on the dense path below, so
            # it keeps the size guard in the else branch)
            pass
        else:
            _check_alibi_dense_size(q.shape[1], qo_len, kv_len)
            backend = "xla"  # auto: dense reference path until hw-banked
    args = (
        q, k, v,
        jnp.zeros((qo_len,), jnp.int32), jnp.zeros((kv_len,), jnp.int32),
        jnp.arange(qo_len, dtype=jnp.int32) + (kv_len - qo_len),
        jnp.arange(kv_len, dtype=jnp.int32),
    )
    if custom_mask is not None:
        # MaskMode::CUSTOM semantics (reference variants.cuh LogitsMask):
        # the custom mask replaces causal, but sliding window still ANDs in
        res = xla_ragged_attention(
            *args, custom_mask=custom_mask, causal=False,
            window_left=window_left, sm_scale=sm_scale,
            logits_soft_cap=logits_soft_cap or 0.0, return_lse=return_lse,
            **kw,
        )
    else:
        fn = _tuned_flash if backend == "pallas" else xla_ragged_attention
        res = fn(
            *args, causal=causal, sm_scale=sm_scale,
            logits_soft_cap=logits_soft_cap or 0.0,
            window_left=window_left, return_lse=return_lse, **kw,
        )
    if out_mul == 1.0 and o_dtype is None:
        return res
    o, lse = res if return_lse else (res, None)
    if out_mul != 1.0:
        o = (o.astype(jnp.float32) * out_mul).astype(o.dtype)
    if o_dtype is not None:
        o = o.astype(jnp.dtype(o_dtype))
    return (o, lse) if return_lse else o


def build_multi_item_mask(
    prefix_len: int,
    item_lens,
    qo_len: Optional[int] = None,
) -> jax.Array:
    """Mask for multi-item scoring (reference prefill.py multi-item params
    ``prefix_len_ptr``/``token_pos_in_items_ptr``): the sequence is a shared
    prefix followed by independent items; each item's tokens attend the
    prefix and their own item causally, never other items — one packed
    forward scores many candidate continuations (reward-model batching).

    Returns a [qo_len, kv_len] bool mask for the custom-mask path, where
    ``kv_len = prefix_len + sum(item_lens)`` and q covers the same tokens
    (pass ``qo_len`` for append-style suffixes covering only the tail).
    """
    import numpy as np

    item_lens = [int(x) for x in np.asarray(item_lens).reshape(-1)]
    kv_len = prefix_len + sum(item_lens)
    q_len = qo_len if qo_len is not None else kv_len
    off = kv_len - q_len  # q tokens are the tail of the kv axis
    mask = np.zeros((q_len, kv_len), bool)
    # prefix visible to everyone, causal within the prefix rows
    starts = [prefix_len]
    for l in item_lens:
        starts.append(starts[-1] + l)
    for qi in range(q_len):
        pos = qi + off
        if pos < prefix_len:
            mask[qi, : pos + 1] = True
            continue
        # which item does pos belong to?
        for s, e in zip(starts[:-1], starts[1:]):
            if s <= pos < e:
                mask[qi, :prefix_len] = True
                mask[qi, s : pos + 1] = True
                break
    return jnp.asarray(mask)


def _flat_mask_bits(qo_lens, kv_lens, custom_mask, packed_custom_mask):
    """Validate and normalize the reference's flat per-request mask concat
    (MaskMode::CUSTOM, packed LSB-first takes precedence) to a flat bool
    array of ``sum(qo_len*kv_len)`` bits.  Returns None if no mask."""
    total_bits = int(np.sum(qo_lens * kv_lens))
    if packed_custom_mask is not None:
        custom_mask = np.unpackbits(
            np.asarray(packed_custom_mask).view(np.uint8), bitorder="little"
        )[:total_bits].astype(bool)
    if custom_mask is None:
        return None
    flat = np.asarray(custom_mask).astype(bool).reshape(-1)
    if flat.size != total_bits:
        raise ValueError(
            f"custom_mask has {flat.size} bits; expected sum(qo_len*kv_len) "
            f"= {total_bits} (flat per-request concat, not a dense mask)"
        )
    return flat


def _expand_flat_mask(
    qo_indptr, kv_indptr, qo_lens, kv_lens, tq_pad, tkv_pad,
    custom_mask, packed_custom_mask,
):
    """Expand the flat mask into the dense [tq_pad, tkv_pad] mask the
    flattened-token-axis XLA backend consumes.  Returns None if no mask."""
    flat = _flat_mask_bits(qo_lens, kv_lens, custom_mask, packed_custom_mask)
    if flat is None:
        return None
    dense = np.zeros((tq_pad, tkv_pad), bool)
    off = 0
    for r in range(len(qo_lens)):
        qn, kn = int(qo_lens[r]), int(kv_lens[r])
        dense[
            int(qo_indptr[r]) : int(qo_indptr[r]) + qn,
            int(kv_indptr[r]) : int(kv_indptr[r]) + kn,
        ] = flat[off : off + qn * kn].reshape(qn, kn)
        off += qn * kn
    return jnp.asarray(dense)


@dataclass(frozen=True)
class _PrefillPlan:
    # token-axis fields are None in the "light" plan built for the fused
    # paged backend (deferred to the gather-plan builder on first fallback)
    q_seg: Optional[jax.Array]  # [Tq_pad] int32 (-1 pad)
    q_pos: Optional[jax.Array]  # [Tq_pad]
    kv_seg: Optional[jax.Array]  # [Tkv_pad] int32 (-2 pad)
    kv_pos: Optional[jax.Array]  # [Tkv_pad]
    kv_gather_rows: Optional[jax.Array]  # [Tkv_pad] flat cache rows (paged)
    out_scatter: Optional[jax.Array]  # [Tq_pad] original token idx (unpad)
    total_q: int
    total_kv: int
    tq_pad: int
    tkv_pad: int
    batch_size: int
    num_qo_heads: int
    num_kv_heads: int
    head_dim: int
    page_size: int
    causal: bool
    sm_scale: float
    logits_soft_cap: float
    window_left: int
    custom_mask: Optional[jax.Array] = None  # [Tq_pad, Tkv_pad] bool (dense)
    # pos_encoding_mode="ALIBI": plan-derived slope vector (dense xla path)
    alibi_slopes: Optional[jax.Array] = None
    # pos_encoding_mode="ROPE_LLAMA": (rope_scale, rope_theta) — q/k are
    # rotated at plan positions in run() (any backend)
    rope: Optional[Tuple[float, float]] = None
    # ISSUE 14 ingest-mode plan static: True = run_ingest() launches the
    # fused RoPE+quantize-append+attention kernel, False = it composes
    # the separate ops, None = resolve lazily (knob -> cost-model
    # chooser) on first run_ingest()
    fused_ingest: Optional[bool] = None


_INGEST_PROJECT_CACHE: list = []  # one-element AST-project cache


def _ingest_vmem_feasible(fused_key) -> bool:
    """Prune the fused-ingest candidate through the L009 VMEM
    evaluator before the roofline race (the decode.py
    ``_split_vmem_feasible`` pattern).  The ingest launcher's own
    binding (``prefill.fused_ingest``) registers the launch but its
    scratch shapes hinge on launch statics the key does not carry —
    per the binding's contract the compile-feasibility proof rides the
    ``fused_prefill.blocks`` evaluation of the shared chunk/tile
    shapes, priced at the (block_q, pages_per_chunk) tactic the ingest
    launch would actually run with (same key, same tuner lookup and
    default as plan()).  The evaluator is a LOWER bound, so False is a
    proof of infeasibility; anything unresolvable (or any analysis
    failure) keeps the candidate — pruning must never be a guess."""
    try:
        from flashinfer_tpu.analysis.core import Project
        from flashinfer_tpu.analysis.vmem_budget import (KNOB_LAUNCHES,
                                                         _estimate)
        from flashinfer_tpu.autotuner import AutoTuner
        from flashinfer_tpu.obs import hwspec
        from flashinfer_tpu.ops import paged_prefill as _pp

        if not _INGEST_PROJECT_CACHE:
            _INGEST_PROJECT_CACHE.append(
                Project.from_paths([_pp.__file__]))
        page_size = int(fused_key[5])
        bq, ppc = AutoTuner.get().lookup(
            "fused_prefill.blocks", fused_key,
            default=(128, max(1, 128 // page_size)))
        est = _estimate(
            _INGEST_PROJECT_CACHE[0],
            KNOB_LAUNCHES["fused_prefill.blocks"],
            (int(bq), int(ppc)), [str(f) for f in fused_key])
        if est is None:
            return True
        total, declared, _launcher = est
        budget = declared if declared is not None \
            else hwspec.current_spec().vmem_bytes
        return total <= budget
    except Exception:
        return True


def resolve_prefill_ingest(
    fused_key, *, total_q: int, total_kv: int, num_qo_heads: int,
    num_kv_heads: int, head_dim: int, q_bytes: int = 2,
    kv_bytes: int = 2, cache_bytes: int = 2,
) -> bool:
    """Resolve the ``prefill.fused_ingest`` knob for one shape: a
    shipped/tuned config entry wins; absent entries default via the
    cost-model chooser (``costmodel.predict_prefill_ingest_win`` — the
    ``choose_decode_splits`` pattern: the fused launch must beat the
    separate-op composition by >2% predicted time or the proven
    composition stays).  THE single resolution point of the knob."""
    from flashinfer_tpu.autotuner import AutoTuner

    v = AutoTuner.get().lookup("prefill.fused_ingest", fused_key,
                               default=None)
    if v is not None:
        return str(v) == "on"
    from flashinfer_tpu.obs import costmodel, hwspec

    spec = hwspec.current_spec()
    use, _ = costmodel.predict_prefill_ingest_win(
        total_q, total_kv, num_qo_heads, num_kv_heads, head_dim,
        hbm_tbps=spec.hbm_tbps, peak_tflops=spec.peak_tflops("bf16"),
        q_bytes=q_bytes, kv_bytes=kv_bytes, cache_bytes=cache_bytes,
        feasible=lambda: _ingest_vmem_feasible(fused_key))
    return use


def _build_token_axis(
    indptr: np.ndarray, pad_to: int, pad_seg: int, pos_offset: np.ndarray
):
    """Flatten ragged requests to one token axis: returns (seg, pos, total).
    Hot host loop -> native planner (csrc/planner.cpp token_axis_plan)."""
    from flashinfer_tpu import native

    seg, pos = native.token_axis_plan(indptr, pos_offset, pad_to, pad_seg)
    return seg, pos, int(indptr[-1])


class BatchPrefillWithRaggedKVCacheWrapper:
    """Ragged-KV batch prefill (reference
    ``BatchPrefillWithRaggedKVCacheWrapper``, flashinfer/prefill.py:2947)."""

    def __init__(
        self,
        float_workspace_buffer=None,
        kv_layout: str = "NHD",
        use_cuda_graph: bool = False,
        backend: str = "auto",
        jit_args=None,
        **_unused,
    ):
        check_kv_layout(kv_layout)
        self._kv_layout = kv_layout
        self._backend = normalize_backend(backend)
        self._plan: Optional[_PrefillPlan] = None
        # reference custom-variant declaration (prefill.py:2947 jit_args):
        # positions 7/9 name the extra run() tensors/scalars in call
        # order.  The TPU build has no jinja codegen, but the DECLARED
        # extras define how positional run() extras are interpreted —
        # "sink" (LSE epilogue) and "sm_scale" (plan rebind) are honored,
        # anything else is rejected loudly.
        self._extra_names: tuple = ()
        if jit_args is not None and len(jit_args) >= 10:
            self._extra_names = tuple(jit_args[7]) + tuple(jit_args[9])

    def plan(
        self,
        qo_indptr,
        kv_indptr,
        num_qo_heads: int,
        num_kv_heads: int,
        head_dim: int,
        custom_mask=None,  # flat concat of per-request [qo_i*kv_i] bools
        packed_custom_mask=None,  # packbits(LSB-first) form; takes precedence
        causal: bool = False,
        pos_encoding_mode: str = "NONE",
        window_left: int = -1,
        logits_soft_cap: Optional[float] = None,
        sm_scale: Optional[float] = None,
        q_data_type=jnp.bfloat16,
        kv_data_type=None,
        rope_scale: Optional[float] = None,
        rope_theta: Optional[float] = None,
        **_unused,
    ) -> None:
        check_pos_encoding_mode(pos_encoding_mode)  # typos raise KeyError
        replan = self._plan is not None
        alibi = pos_encoding_mode == "ALIBI"
        rope = (
            (rope_scale or 1.0, rope_theta or 1e4)
            if pos_encoding_mode == "ROPE_LLAMA" else None
        )
        qo_indptr = np.asarray(qo_indptr)
        kv_indptr = np.asarray(kv_indptr)
        batch = len(qo_indptr) - 1
        qo_lens = qo_indptr[1:] - qo_indptr[:-1]
        kv_lens = kv_indptr[1:] - kv_indptr[:-1]
        tq_pad = max(next_power_of_two(int(qo_indptr[-1])), 128)
        tkv_pad = max(next_power_of_two(int(kv_indptr[-1])), 128)
        if alibi:
            _check_alibi_dense_size(num_qo_heads, tq_pad, tkv_pad)
        # bottom-right causal alignment: q token i of request r sits at
        # absolute position kv_len_r - qo_len_r + i
        q_seg, q_pos, total_q = _build_token_axis(
            qo_indptr, tq_pad, _Q_PAD_SEG, kv_lens - qo_lens
        )
        kv_seg, kv_pos, total_kv = _build_token_axis(
            kv_indptr, tkv_pad, _KV_PAD_SEG, np.zeros(batch, np.int64)
        )
        # MaskMode::CUSTOM: causal is ignored; window still applies
        dense_mask = _expand_flat_mask(
            qo_indptr, kv_indptr, qo_lens, kv_lens, tq_pad, tkv_pad,
            custom_mask, packed_custom_mask,
        )
        if dense_mask is not None:
            causal = False  # custom mask overrides causal (only)
        self._plan = _PrefillPlan(
            q_seg=jnp.asarray(q_seg), q_pos=jnp.asarray(q_pos),
            kv_seg=jnp.asarray(kv_seg), kv_pos=jnp.asarray(kv_pos),
            kv_gather_rows=None,
            out_scatter=jnp.arange(tq_pad, dtype=jnp.int32),
            total_q=total_q, total_kv=total_kv,
            tq_pad=tq_pad, tkv_pad=tkv_pad, batch_size=batch,
            num_qo_heads=num_qo_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, page_size=0,
            causal=causal, sm_scale=get_sm_scale(head_dim, sm_scale),
            logits_soft_cap=logits_soft_cap or 0.0, window_left=window_left,
            custom_mask=dense_mask,
            alibi_slopes=(
                get_alibi_slopes(num_qo_heads) if alibi else None
            ),
            rope=rope,
        )
        from flashinfer_tpu import obs

        obs.record_plan(
            self, replan=replan,
            padded_vs_actual=(("q_tokens", tq_pad, total_q),
                              ("kv_tokens", tkv_pad, total_kv)),
            statics=self._plan,  # retrace-cause diff source (obs.spans)
        )

    def run(
        self,
        q: jax.Array,  # [total_q, num_qo_heads, head_dim]
        k: jax.Array,  # [total_kv, num_kv_heads, head_dim]
        v: jax.Array,
        *extra,
        return_lse: bool = False,
    ):
        plan = self._plan
        if plan is None:
            raise RuntimeError("plan() must be called before run()")
        sink = None
        if extra:
            # custom-variant positional extras, in the ctor-declared order
            # (e.g. the attention-sink module: run(q, k, v, sink,
            # sm_scale)).  sm_scale is PER-CALL (reference kernels take
            # it as a run scalar): it overrides the plan locally, never
            # stickily.
            if len(extra) > len(self._extra_names):
                raise TypeError(
                    f"run() got {len(extra)} positional extras but the "
                    f"wrapper declares {self._extra_names or 'none'} "
                    "(pass jit_args at construction)")
            for name, val in zip(self._extra_names, extra):
                if name == "sink":
                    sink = jnp.asarray(val)
                elif name == "sm_scale":
                    if val is not None and float(val) != plan.sm_scale:
                        import dataclasses

                        from flashinfer_tpu import obs

                        obs.counter_inc("plan.sm_scale_rebinds",
                                        wrapper=type(self).__name__)
                        plan = dataclasses.replace(
                            plan, sm_scale=float(val))
                else:
                    raise NotImplementedError(
                        f"custom-variant extra {name!r} has no TPU "
                        "implementation (supported: sink, sm_scale)")
        if sink is not None:
            from flashinfer_tpu.attention import sink_epilogue

            out, lse = self._run_planned(plan, q, k, v, return_lse=True)
            return sink_epilogue(out, lse, sink, return_lse)
        return self._run_planned(plan, q, k, v, return_lse=return_lse)

    def _run_planned(self, plan, q, k, v, *, return_lse: bool):
        tq, tkv = plan.tq_pad, plan.tkv_pad
        if q.shape[0] != tq:
            q = jnp.pad(q, ((0, tq - q.shape[0]), (0, 0), (0, 0)))
        if k.shape[0] != tkv:
            k = jnp.pad(k, ((0, tkv - k.shape[0]), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, tkv - v.shape[0]), (0, 0), (0, 0)))
        q, k = _apply_plan_rope(plan, q, k)
        backend = resolve_backend(self._backend, "batch_prefill_ragged")
        alibi_kw = {}
        if plan.alibi_slopes is not None:
            backend = "xla"  # the bias term lives on the dense path
            alibi_kw["alibi_slopes"] = plan.alibi_slopes
        if plan.custom_mask is not None:
            # custom-mask mode runs on the dense xla backend; sliding window
            # still ANDs in (reference variants.cuh LogitsMask — only causal
            # is subsumed by the custom mask)
            out = xla_ragged_attention(
                q, k, v, plan.q_seg, plan.kv_seg, plan.q_pos, plan.kv_pos,
                causal=False, sm_scale=plan.sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left,
                return_lse=return_lse, custom_mask=plan.custom_mask,
                **alibi_kw,
            )
        else:
            fn = _tuned_flash if backend == "pallas" else xla_ragged_attention
            out = fn(
                q, k, v, plan.q_seg, plan.kv_seg, plan.q_pos, plan.kv_pos,
                causal=plan.causal, sm_scale=plan.sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left, return_lse=return_lse,
                **alibi_kw,
            )
        if return_lse:
            return out[0][: plan.total_q], out[1][: plan.total_q]
        return out[: plan.total_q]

    forward = run

    def run_return_lse(self, q, k, v, *extra, **kw):
        """Reference ``run_return_lse`` (prefill.py:2900, partialmethod
        with return_lse=True)."""
        kw.pop("return_lse", None)
        return self.run(q, k, v, *extra, return_lse=True, **kw)

    forward_return_lse = run_return_lse

    def end_forward(self) -> None:
        pass


class BatchPrefillWithPagedKVCacheWrapper:
    """Paged-KV batch prefill/append (reference
    ``BatchPrefillWithPagedKVCacheWrapper``, flashinfer/prefill.py:1492).

    plan() precomputes flat gather rows for every kv token of every request;
    run() gathers the paged cache into the flattened ragged KV axis and
    invokes the segment flash kernel."""

    def __init__(
        self,
        float_workspace_buffer=None,
        kv_layout: str = "NHD",
        use_cuda_graph: bool = False,
        backend: str = "auto",
        **_unused,
    ):
        check_kv_layout(kv_layout)
        self._kv_layout = kv_layout
        self._backend = normalize_backend(backend)
        self._plan: Optional[_PrefillPlan] = None
        self._fused_plan = None  # work-unit plan for backend="pallas_fused"
        self._ingest_plan = None  # lazy ingest-mode plan (run_ingest)

    def plan(
        self,
        qo_indptr,
        paged_kv_indptr,
        paged_kv_indices,
        paged_kv_last_page_len,
        num_qo_heads: int,
        num_kv_heads: int,
        head_dim: int,
        page_size: int,
        causal: bool = False,
        custom_mask=None,  # flat concat of per-request [qo_i*kv_i] bools
        packed_custom_mask=None,  # packbits(LSB-first) form; takes precedence
        pos_encoding_mode: str = "NONE",
        window_left: int = -1,
        logits_soft_cap: Optional[float] = None,
        sm_scale: Optional[float] = None,
        q_data_type=jnp.bfloat16,
        kv_data_type=None,
        rope_scale: Optional[float] = None,
        rope_theta: Optional[float] = None,
        fused_ingest: Optional[bool] = None,
        **_unused,
    ) -> None:
        check_pos_encoding_mode(pos_encoding_mode)  # typos raise KeyError
        replan = self._plan is not None
        alibi = pos_encoding_mode == "ALIBI"
        rope = (
            (rope_scale or 1.0, rope_theta or 1e4)
            if pos_encoding_mode == "ROPE_LLAMA" else None
        )
        self._ingest_plan = None  # rebuilt lazily per plan geometry
        qo_indptr = np.asarray(qo_indptr)
        kv_indptr_pages = np.asarray(paged_kv_indptr)
        kv_indices = np.asarray(paged_kv_indices)
        last_page_len = np.asarray(paged_kv_last_page_len)
        batch = len(qo_indptr) - 1
        pages_per_req = kv_indptr_pages[1:] - kv_indptr_pages[:-1]
        kv_lens = np.where(
            pages_per_req > 0,
            (pages_per_req - 1) * page_size + last_page_len,
            0,
        ).astype(np.int64)
        kv_indptr = np.concatenate([[0], np.cumsum(kv_lens)])
        qo_lens = qo_indptr[1:] - qo_indptr[:-1]

        tq_pad = max(next_power_of_two(int(qo_indptr[-1])), 128)
        tkv_pad = max(next_power_of_two(int(kv_indptr[-1])), 128)
        if alibi:
            _check_alibi_dense_size(num_qo_heads, tq_pad, tkv_pad)

        # paged-batch MaskMode::CUSTOM (reference prefill.py:1117-2947):
        # the fused work-unit kernel consumes the packed mask directly
        # (per-unit byte bitmaps, no dense [qo, kv] materialization —
        # reference analogue prefill.cuh:2682).  Packed input stays
        # packed end-to-end on the fused path (the native planner reads
        # LSB-first bytes); bool input is validated here; the gather
        # fallback expands densely, lazily, from the original args.
        mask_total_bits = int(np.sum(qo_lens * kv_lens))
        if packed_custom_mask is not None:
            mask_flat = np.asarray(packed_custom_mask).view(
                np.uint8
            ).reshape(-1)
            if mask_flat.size * 8 < mask_total_bits:
                raise ValueError(
                    f"packed_custom_mask has {mask_flat.size * 8} bits; "
                    f"expected sum(qo_len*kv_len) = {mask_total_bits}"
                )
        else:
            mask_flat = _flat_mask_bits(qo_lens, kv_lens, custom_mask, None)
        if mask_flat is not None:
            causal = False  # custom mask overrides causal (only)

        def build_gather_plan() -> _PrefillPlan:
            # token axes + flat gather rows — O(tkv_pad) host work that the
            # fused default never consumes; built lazily on first fallback
            dense_mask = _expand_flat_mask(
                qo_indptr, kv_indptr, qo_lens, kv_lens, tq_pad, tkv_pad,
                custom_mask, packed_custom_mask,
            )
            q_seg, q_pos, total_q = _build_token_axis(
                qo_indptr, tq_pad, _Q_PAD_SEG, kv_lens - qo_lens
            )
            kv_seg, kv_pos, total_kv = _build_token_axis(
                kv_indptr, tkv_pad, _KV_PAD_SEG, np.zeros(batch, np.int64)
            )
            from flashinfer_tpu import native

            rows = native.paged_gather_plan(
                kv_indptr, kv_indptr_pages, kv_indices, page_size, tkv_pad
            )
            return _PrefillPlan(
                q_seg=jnp.asarray(q_seg), q_pos=jnp.asarray(q_pos),
                kv_seg=jnp.asarray(kv_seg), kv_pos=jnp.asarray(kv_pos),
                kv_gather_rows=jnp.asarray(rows, dtype=jnp.int32),
                out_scatter=jnp.arange(tq_pad, dtype=jnp.int32),
                total_q=total_q, total_kv=total_kv,
                tq_pad=tq_pad, tkv_pad=tkv_pad, batch_size=batch,
                num_qo_heads=num_qo_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, page_size=page_size,
                causal=causal, sm_scale=get_sm_scale(head_dim, sm_scale),
                logits_soft_cap=logits_soft_cap or 0.0,
                window_left=window_left,
                custom_mask=dense_mask,
                alibi_slopes=(
                    get_alibi_slopes(num_qo_heads) if alibi else None
                ),
                rope=rope,
            )

        self._gather_plan_builder = build_gather_plan
        # ALiBi is a dense-path mode (the fused kernel has no bias term);
        # in-attention RoPE needs the gathered token axis to rotate
        use_fused = (not alibi) and (rope is None) and (
            self._backend == "pallas_fused" or (
            # hardware-validated default for the TPU-preferred HND layout;
            # NHD would need a whole-cache transpose per run() to feed the
            # fused kernel's contiguous page DMAs, so it keeps gather+flash.
            # resolve_backend gates on is_tpu() and the env override, so
            # off-TPU auto stays on compiled XLA and FLASHINFER_TPU_BACKEND
            # =xla can force the fallback on TPU.
            self._backend == "auto"
            and check_kv_layout(self._kv_layout) == TensorLayout.HND
            and resolve_backend("auto", "batch_prefill_paged") == "pallas"
        ))
        if use_fused:
            from flashinfer_tpu.ops.paged_prefill import (
                build_prefill_work_units,
            )
            from flashinfer_tpu.autotuner import AutoTuner

            # (block_q, pages_per_chunk) comes from the shipped/tuned config;
            # profiling happens in run() (inside autotune()) where live
            # tensors exist, then the work-unit plan is rebuilt with the
            # winner — the raw indptr arrays are kept for that rebuild.
            fused_key = (
                batch, tq_pad, num_qo_heads, num_kv_heads, head_dim,
                page_size,
            )
            bq_u, ppc_u = AutoTuner.get().lookup(
                "fused_prefill.blocks", fused_key,
                default=(128, max(1, 128 // page_size)),
            )
            self._fused_raw = (
                np.asarray(qo_indptr), np.asarray(kv_indptr_pages),
                np.asarray(kv_indices), np.asarray(kv_lens), page_size,
                fused_key, mask_flat, mask_total_bits,
                causal, window_left,
            )
            self._fused_tuned = False
            units = build_prefill_work_units(
                qo_indptr, kv_indptr_pages, kv_indices, kv_lens,
                block_q=int(bq_u), pages_per_chunk=int(ppc_u),
                page_size=page_size, mask_flat=mask_flat,
                mask_total_bits=mask_total_bits,
                # the plan prunes + FULL-codes units under the SAME
                # causal/window the kernel will run with (paged_prefill
                # module contract)
                causal=causal, window_left=window_left,
            )
            statics = dict(
                num_units=units.pop("num_units"),
                block_q=units.pop("block_q"),
                pages_per_chunk=units.pop("pages_per_chunk"),
            )
            fused_stats = units.pop("stats")
            self._fused_plan = (
                {k: jnp.asarray(v) for k, v in units.items()}, statics,
            )
            # light plan: config fields only — the heavy gather arrays are
            # deferred to _gather_plan_builder on first fallback run()
            self._plan = _PrefillPlan(
                q_seg=None, q_pos=None, kv_seg=None, kv_pos=None,
                kv_gather_rows=None,
                out_scatter=None,
                total_q=int(qo_indptr[-1]), total_kv=int(kv_indptr[-1]),
                tq_pad=tq_pad, tkv_pad=tkv_pad, batch_size=batch,
                num_qo_heads=num_qo_heads, num_kv_heads=num_kv_heads,
                head_dim=head_dim, page_size=page_size,
                causal=causal, sm_scale=get_sm_scale(head_dim, sm_scale),
                logits_soft_cap=logits_soft_cap or 0.0,
                window_left=window_left,
                fused_ingest=fused_ingest,
            )
        else:
            self._fused_plan = None
            fused_stats = None
            self._plan = build_gather_plan()
        # plan-time work accounting (launched vs effective MXU cells,
        # tiles, pruned units) — the cost model's input for roofline
        # attribution (obs.costmodel.fused_prefill_from_stats)
        self._fused_stats = fused_stats
        from flashinfer_tpu import obs

        # work-unit fill axes ride the same padding-waste histograms the
        # token axes use, so the packing win (ISSUE 3 tentpole d) is
        # measurable: unit_rows = idle qo-tile rows across all units,
        # mxu_cells = idle (row, kv-col) positions across all MXU dots
        unit_axes = ()
        if fused_stats is not None:
            unit_axes = (
                ("prefill_unit_rows", fused_stats["unit_rows_total"],
                 fused_stats["unit_rows_valid"]),
                ("prefill_mxu_cells", fused_stats["mxu_cells_total"],
                 fused_stats["mxu_cells_valid"]),
            )
            if fused_stats["units_pruned"]:
                obs.counter_inc(
                    "plan.prefill_units_pruned",
                    fused_stats["units_pruned"],
                    wrapper=type(self).__name__,
                )
        obs.record_plan(
            self, replan=replan,
            padded_vs_actual=(("q_tokens", tq_pad, int(qo_indptr[-1])),
                              ("kv_tokens", tkv_pad, int(kv_indptr[-1])),
                              *unit_axes),
            statics=self._plan,  # retrace-cause diff source (obs.spans)
        )

    @property
    def fused_prefill_config(self) -> Optional[dict]:
        """The live fused-path launch config (block_q / pages_per_chunk /
        num_units) or None on the gather path — bench rows carry this as
        block-config metadata (docs/performance.md)."""
        if self._fused_plan is None:
            return None
        return dict(self._fused_plan[1])

    @property
    def fused_prefill_stats(self) -> Optional[dict]:
        """The live plan's post-pruning/post-packing work accounting
        (``build_prefill_work_units`` ``stats``: units/tiles/pruned +
        launched-vs-valid unit rows and MXU cells), or None on the
        gather path — obs.costmodel derives launched-vs-effective
        roofline work from this."""
        if self._fused_plan is None or self._fused_stats is None:
            return None
        return dict(self._fused_stats)

    def _materialize_gather_plan(self) -> "_PrefillPlan":
        """Materialize the deferred gather plan if the light fused-path
        plan is live (the builder recomputes PLANNED values, so any
        live sm_scale / logits_soft_cap rebind is carried over), for
        run()'s return_lse fallback.  Returns the (possibly new) live
        plan."""
        plan = self._plan
        if plan.kv_gather_rows is None:
            new_plan = self._gather_plan_builder()
            if new_plan.sm_scale != plan.sm_scale \
                    or new_plan.logits_soft_cap != plan.logits_soft_cap:
                import dataclasses

                new_plan = dataclasses.replace(
                    new_plan, sm_scale=plan.sm_scale,
                    logits_soft_cap=plan.logits_soft_cap)
            plan = self._plan = new_plan
        return plan

    def _rebind_sm_scale(self, *, absolute=None, multiplier=None):
        """Per-call sm_scale override: swap in a plan with the new scale
        and return the plan to restore in the caller's ``finally`` (or
        None if nothing changed).  A later lazy gather-plan rebuild
        preserves the live rebind (see run()'s materialization site), so
        no eager plan build is needed here."""
        if self._plan is None or (absolute is None and multiplier is None):
            return None
        new = (float(absolute) if absolute is not None
               else self._plan.sm_scale * float(multiplier))
        if new == self._plan.sm_scale:
            return None
        import dataclasses

        from flashinfer_tpu import obs

        obs.counter_inc("plan.sm_scale_rebinds", wrapper=type(self).__name__)
        restore = self._plan
        self._plan = dataclasses.replace(restore, sm_scale=new)
        return restore

    def _rebind_soft_cap(self, soft_cap):
        """Per-call logits_soft_cap override (the reference forwards the
        run() value to the kernel, attention/_core.py:250): swap in a
        plan with the new cap and return the plan to restore in the
        caller's ``finally`` (None if unchanged).  The cap is a kernel
        jit-static, so a novel value compiles a fresh variant — the same
        frozen-plan-replace contract as ``_rebind_sm_scale``."""
        if self._plan is None or soft_cap is None:
            return None
        new = float(soft_cap)
        if new == self._plan.logits_soft_cap:
            return None
        import dataclasses

        from flashinfer_tpu import obs

        obs.counter_inc("plan.soft_cap_rebinds", wrapper=type(self).__name__)
        restore = self._plan
        self._plan = dataclasses.replace(restore, logits_soft_cap=new)
        return restore

    def run(
        self,
        q: jax.Array,  # [total_q, num_qo_heads, head_dim]
        paged_kv_cache: Union[Tuple[jax.Array, jax.Array], jax.Array],
        *,
        k_scale=None,
        v_scale=None,
        sinks=None,
        out=None,
        lse=None,
        return_lse: bool = False,
    ):
        plan = self._plan
        if plan is None:
            raise RuntimeError("plan() must be called before run()")
        if out is not None or lse is not None:
            raise NotImplementedError(
                "pre-allocated out=/lse= buffers are not supported (XLA "
                "owns buffers; docs/migration.md)")
        if k_scale is not None or v_scale is not None or sinks is not None:
            # reference per-run kwargs (prefill.py:2520): k_scale folds
            # into sm_scale FOR THIS CALL, v_scale scales the output,
            # sinks renormalize via the LSE epilogue.  The inner call is
            # NON-VIRTUAL: a subclass run (e.g. the sink wrapper's) must
            # not re-apply its own epilogue on this internal re-entry.
            restore_plan = self._rebind_sm_scale(multiplier=k_scale)
            try:
                need_lse = return_lse or sinks is not None
                res = BatchPrefillWithPagedKVCacheWrapper.run(
                    self, q, paged_kv_cache, return_lse=need_lse)
            finally:
                if restore_plan is not None:
                    self._plan = restore_plan
            o, l = res if need_lse else (res, None)
            if sinks is not None:
                from flashinfer_tpu.attention import sink_epilogue

                res2 = sink_epilogue(o, l, sinks, return_lse)
                o, l = res2 if return_lse else (res2, None)
            if v_scale is not None:
                o = (o.astype(jnp.float32) * float(v_scale)).astype(o.dtype)
            return (o, l) if return_lse else o
        if isinstance(paged_kv_cache, tuple):
            k_cache, v_cache = paged_kv_cache
        else:
            k_cache, v_cache = paged_kv_cache[:, 0], paged_kv_cache[:, 1]
        if self._fused_plan is not None and not return_lse:
            # fused work-unit kernel: KV pages DMA'd straight from the cache
            from flashinfer_tpu.ops import paged_prefill as _pp_module
            from flashinfer_tpu.ops.paged_prefill import fused_paged_prefill

            if check_kv_layout(self._kv_layout) == TensorLayout.NHD:
                k_hnd = jnp.swapaxes(k_cache, 1, 2)
                v_hnd = jnp.swapaxes(v_cache, 1, 2)
            else:
                k_hnd, v_hnd = k_cache, v_cache
            unit_plan, statics = self._fused_plan
            total_q = q.shape[0]
            # bucketed q padding bounds recompiles (same contract as the
            # gather path; pad rows are touched by no work unit)
            if total_q != plan.tq_pad:
                q = jnp.pad(q, ((0, plan.tq_pad - total_q), (0, 0), (0, 0)))

            from flashinfer_tpu.autotuner import AutoTuner

            tuner = AutoTuner.get()
            if tuner.tuning_enabled and not self._fused_tuned:
                self._fused_tuned = True
                from flashinfer_tpu.ops.paged_prefill import (
                    build_prefill_work_units,
                )

                (qo_i, kvp_i, kvi_i, kvl_i, ps, fkey, mflat,
                 mbits, causal_p, wl_p) = self._fused_raw
                from flashinfer_tpu.ops.paged_prefill import (
                    block_candidates,
                )

                # the shared grid (W002-safe chunk ceiling documented at
                # the definition) — the offline sweep explores the same
                cands = block_candidates(ps)

                def _build(c):
                    u = build_prefill_work_units(
                        qo_i, kvp_i, kvi_i, kvl_i,
                        block_q=c[0], pages_per_chunk=c[1], page_size=ps,
                        mask_flat=mflat, mask_total_bits=mbits,
                        causal=causal_p, window_left=wl_p,
                    )
                    st = dict(
                        num_units=u.pop("num_units"),
                        block_q=u.pop("block_q"),
                        pages_per_chunk=u.pop("pages_per_chunk"),
                    )
                    stats = u.pop("stats")
                    return ({k2: jnp.asarray(v2) for k2, v2 in u.items()},
                            st, stats)

                def _runner(c):
                    up, st, _ = _build(c)
                    return lambda: fused_paged_prefill(
                        q, k_hnd, v_hnd, up,
                        sm_scale=plan.sm_scale,
                        logits_soft_cap=plan.logits_soft_cap,
                        window_left=plan.window_left, causal=plan.causal,
                        **st,
                    )

                cur = (statics["block_q"], statics["pages_per_chunk"])
                best = tuner.choose_one(
                    "fused_prefill.blocks", fkey, cands, _runner, default=cur,
                    module=_pp_module,
                )
                best = (int(best[0]), int(best[1]))
                if best != cur:
                    # stats are per-block-config (unit/tile/cell counts):
                    # the retuned plan must refresh them or the cost
                    # model would attribute the OLD launch shape
                    unit_plan, statics, self._fused_stats = _build(best)
                    self._fused_plan = (unit_plan, statics)

            out = fused_paged_prefill(
                q, k_hnd, v_hnd, unit_plan,
                sm_scale=plan.sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left, causal=plan.causal,
                **statics,
            )
            return out[:total_q]
        if plan.kv_gather_rows is None:
            # fused plan was active but this call needs the gather path
            # (return_lse): materialize the deferred plan once, rebinds
            # preserved
            plan = self._materialize_gather_plan()
        if check_kv_layout(self._kv_layout) == TensorLayout.HND:
            k_cache = jnp.swapaxes(k_cache, 1, 2)
            v_cache = jnp.swapaxes(v_cache, 1, 2)
        # [num_pages, page_size, Hkv, D] -> row gather
        kflat = k_cache.reshape(-1, *k_cache.shape[2:])
        vflat = v_cache.reshape(-1, *v_cache.shape[2:])
        k = kflat[plan.kv_gather_rows]
        v = vflat[plan.kv_gather_rows]
        tq = plan.tq_pad
        if q.shape[0] != tq:
            q = jnp.pad(q, ((0, tq - q.shape[0]), (0, 0), (0, 0)))
        q, k = _apply_plan_rope(plan, q, k)
        alibi_kw = {}
        if plan.alibi_slopes is not None:
            alibi_kw["alibi_slopes"] = plan.alibi_slopes
        if plan.custom_mask is not None:
            # paged-batch MaskMode::CUSTOM runs on the dense xla backend
            # over the gathered KV (same contract as the ragged wrapper)
            out = xla_ragged_attention(
                q, k, v, plan.q_seg, plan.kv_seg, plan.q_pos, plan.kv_pos,
                causal=False, sm_scale=plan.sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left, return_lse=return_lse,
                custom_mask=plan.custom_mask, **alibi_kw,
            )
        else:
            backend = resolve_backend(
                "pallas" if self._backend == "pallas_fused" else self._backend,
                "batch_prefill_paged",
            )
            if plan.alibi_slopes is not None:
                backend = "xla"  # the bias term lives on the dense path
            fn = _tuned_flash if backend == "pallas" else xla_ragged_attention
            out = fn(
                q, k, v, plan.q_seg, plan.kv_seg, plan.q_pos, plan.kv_pos,
                causal=plan.causal, sm_scale=plan.sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left, return_lse=return_lse,
                **alibi_kw,
            )
        if return_lse:
            return out[0][: plan.total_q], out[1][: plan.total_q]
        return out[: plan.total_q]

    forward = run

    def _resolve_ingest(self) -> bool:
        """The plan's ``fused_ingest`` static, resolved at most once:
        an explicit plan(fused_ingest=) wins; None routes through
        :func:`resolve_prefill_ingest` (knob -> cost-model chooser).
        The resolution is frozen back onto the plan so the flight
        recorder's replan diffs see which mode served."""
        plan = self._plan
        if plan.fused_ingest is None:
            from flashinfer_tpu.ops.paged_prefill import (
                ingest_rows_aligned)

            # the raw rows are request-contiguous: bases = cumsum(kv);
            # the compiled kernel needs them aligned (INGEST_KV_ALIGN)
            kvl = np.asarray(self._fused_raw[3], np.int64)
            fkey = self._fused_raw[5]
            resolved = ingest_rows_aligned(
                np.cumsum(kvl)[:-1],
                self._fused_plan[1]["pages_per_chunk"] * self._fused_raw[4],
            ) and resolve_prefill_ingest(
                fkey, total_q=plan.total_q, total_kv=plan.total_kv,
                num_qo_heads=plan.num_qo_heads,
                num_kv_heads=plan.num_kv_heads, head_dim=plan.head_dim)
            import dataclasses

            self._plan = plan = dataclasses.replace(
                plan, fused_ingest=resolved)
        return bool(plan.fused_ingest)

    def _ingest_positions(self):
        """Host-side (q_pos, kv_pos, kv_req) of the planned geometry —
        the separate-op composition's rotation/append coordinates
        (kv positions 0..kv_len-1 per request: run_ingest serves the
        from-scratch prefill form, where the raw rows ARE the kv)."""
        qo_i, _, _, kvl_i = self._fused_raw[:4]
        B = len(qo_i) - 1
        qo_lens = (qo_i[1:] - qo_i[:-1]).astype(np.int64)
        kvl = np.asarray(kvl_i, np.int64)
        kv_pos = np.concatenate(
            [np.arange(n) for n in kvl] or [np.zeros(0)]).astype(np.int32)
        kv_req = np.repeat(np.arange(B), kvl).astype(np.int32)
        q_pos = np.concatenate(
            [np.arange(n) + (kvl[r] - n)
             for r, n in enumerate(qo_lens)] or [np.zeros(0)]
        ).astype(np.int32)
        return q_pos, kv_pos, kv_req

    def run_ingest(
        self,
        q: jax.Array,  # [total_q, num_qo_heads, head_dim] RAW (pre-RoPE)
        k_new: jax.Array,  # [total_kv, num_kv_heads, head_dim] RAW
        v_new: jax.Array,
        paged_kv_cache: Tuple[jax.Array, jax.Array],
        *,
        rope_scale: float = 1.0,
        rope_theta: float = 1e4,
        rope_interleave: bool = False,
        k_scale: Optional[float] = None,
        v_scale: Optional[float] = None,
        return_lse: bool = False,
    ):
        """Fused prefill INGEST (ISSUE 14): RoPE + KV-quantize-append +
        attention over RAW pre-RoPE q/k/v in one launch.  The raw k/v
        rows ARE the planned KV axis (from-scratch prefill: positions
        0..kv_len-1 per request); returns ``(out, (k_cache, v_cache))``
        (+ ``lse`` in the middle with ``return_lse``) with the caches
        updated to exactly the bits the separate rotate -> quant-append
        composition writes (bit-for-bit, tests/test_prefill_ingest.py;
        rows past each sequence's end in its last partial page are
        deterministically zeroed — see fused_paged_prefill_ingest).

        Dispatch follows the ``fused_ingest`` plan static (explicit
        plan(fused_ingest=), else knob -> chooser): OFF composes the
        separate ops through the SAME entry point — the oracle tier —
        so A/B and fallback share one call shape.  ``k_scale`` /
        ``v_scale`` are the quant-append scales (high = code * scale)
        and are REQUIRED for int8/fp8 caches."""
        plan = self._plan
        if plan is None:
            raise RuntimeError("plan() must be called before run_ingest()")
        if self._fused_plan is None:
            raise NotImplementedError(
                "run_ingest needs the fused work-unit path (HND layout, "
                "no ALIBI/ROPE_LLAMA plan mode, TPU or "
                "FLASHINFER_TPU_BACKEND=pallas) — this plan resolved to "
                "the gather fallback")
        k_cache, v_cache = paged_kv_cache
        kv_quant = (
            "int8" if k_cache.dtype == jnp.int8 else
            "fp8" if k_cache.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2)
            else "none")
        if kv_quant != "none" and (k_scale is None or v_scale is None):
            raise ValueError(
                f"{kv_quant} KV cache needs explicit k_scale/v_scale "
                "(the quant-append scales: high_precision = code * scale)")
        ks = float(k_scale) if k_scale is not None else 1.0
        vs = float(v_scale) if v_scale is not None else 1.0
        if k_new.shape[0] != plan.total_kv:
            raise ValueError(
                f"k_new has {k_new.shape[0]} raw rows; the plan's kv "
                f"axis is {plan.total_kv} tokens (run_ingest ingests "
                "the WHOLE planned KV — from-scratch prefill)")

        if self._resolve_ingest():
            from flashinfer_tpu.ops.paged_prefill import (
                build_prefill_ingest_units, fused_paged_prefill_ingest,
            )

            _, statics = self._fused_plan
            if self._ingest_plan is None:
                (qo_i, kvp_i, kvi_i, kvl_i, ps, _fkey, mflat, mbits,
                 causal_p, wl_p) = self._fused_raw
                up = build_prefill_ingest_units(
                    qo_i, kvp_i, kvi_i, kvl_i,
                    block_q=statics["block_q"],
                    pages_per_chunk=statics["pages_per_chunk"],
                    page_size=ps, mask_flat=mflat, mask_total_bits=mbits,
                    causal=causal_p, window_left=wl_p,
                )
                ist = dict(
                    num_units=up.pop("num_units"),
                    block_q=up.pop("block_q"),
                    pages_per_chunk=up.pop("pages_per_chunk"),
                )
                self._ingest_stats = up.pop("stats")
                self._ingest_plan = (
                    {k2: jnp.asarray(v2) for k2, v2 in up.items()}, ist)
            unit_plan, ist = self._ingest_plan
            total_q = q.shape[0]
            if total_q != plan.tq_pad:
                q = jnp.pad(q, ((0, plan.tq_pad - total_q), (0, 0),
                                (0, 0)))
            res = fused_paged_prefill_ingest(
                q, k_new, v_new, k_cache, v_cache, unit_plan,
                sm_scale=plan.sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left, causal=plan.causal,
                return_lse=return_lse, rope_scale=rope_scale,
                rope_theta=rope_theta, rope_interleave=rope_interleave,
                kv_quant=kv_quant, k_scale=ks, v_scale=vs,
                kv_aligned=self._ingest_stats["ingest_kv_aligned"],
                **ist,
            )
            if return_lse:
                out, lse, caches = res
                return out[:total_q], lse[:total_q], caches
            out, caches = res
            return out[:total_q], caches

        # ---- the separate-op composition (the oracle tier) ----
        from flashinfer_tpu.page import (
            append_paged_kv_cache, append_paged_kv_cache_quant_fp8,
            append_paged_kv_cache_quant_int8,
        )
        from flashinfer_tpu.rope import rotate_at_positions_static

        q_pos, kv_pos, kv_req = self._ingest_positions()
        # static-scale/theta rotation — bitwise what the ingest kernel
        # computes (rotate_at_positions_static docstring: a traced
        # theta's runtime pow would drift the oracle ~1 ULP)
        q_rot = rotate_at_positions_static(
            q, jnp.asarray(q_pos), rope_scale=rope_scale,
            rope_theta=rope_theta, interleave=rope_interleave)
        k_rot = rotate_at_positions_static(
            k_new, jnp.asarray(kv_pos), rope_scale=rope_scale,
            rope_theta=rope_theta, interleave=rope_interleave)
        kvi = jnp.asarray(self._fused_raw[2])
        kvp = jnp.asarray(self._fused_raw[1])
        if kv_quant == "int8":
            caches = append_paged_kv_cache_quant_int8(
                k_rot, v_new, jnp.asarray(kv_req), jnp.asarray(kv_pos),
                (k_cache, v_cache), kvi, kvp, jnp.float32(ks),
                jnp.float32(vs), self._kv_layout)
        elif kv_quant == "fp8":
            caches = append_paged_kv_cache_quant_fp8(
                k_rot, v_new, jnp.asarray(kv_req), jnp.asarray(kv_pos),
                (k_cache, v_cache), kvi, kvp, jnp.float32(ks),
                jnp.float32(vs), self._kv_layout)
        else:
            caches = append_paged_kv_cache(
                k_rot, v_new, jnp.asarray(kv_req), jnp.asarray(kv_pos),
                (k_cache, v_cache), kvi, kvp, None, self._kv_layout)
        scale_kw = {}
        if kv_quant != "none":
            scale_kw = dict(k_scale=ks, v_scale=vs)
        res = self.run(q_rot, caches, return_lse=return_lse, **scale_kw)
        if return_lse:
            return res[0], res[1], caches
        return res, caches

    def run_return_lse(self, q, paged_kv_cache, **kw):
        """Reference ``run_return_lse`` (prefill.py:4075, partialmethod
        with return_lse=True)."""
        kw.pop("return_lse", None)
        return self.run(q, paged_kv_cache, return_lse=True, **kw)

    forward_return_lse = run_return_lse

    def end_forward(self) -> None:
        pass
