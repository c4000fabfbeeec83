"""Rotary position embedding (RoPE) family.

TPU-native re-design of the reference RoPE ops (``flashinfer/rope.py:768-1159``,
``include/flashinfer/pos_enc.cuh:294-1580``): plain Llama RoPE, Llama-3.1
frequency-scaled RoPE, position-id and ragged-indptr input forms, and the
cos/sin-cache form.

Functional (out-of-place) semantics; the reference's ``*_inplace`` variants
map to the same functions under jit buffer donation.  All forms are pure-XLA:
RoPE is a cheap elementwise transform that XLA fuses into neighbouring ops —
a dedicated Pallas kernel only adds a fusion barrier (SURVEY §7 design note).
"""

from __future__ import annotations

import collections
import functools
import weakref
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from flashinfer_tpu.api_logging import flashinfer_api


def _rope_freqs(
    rotary_dim: int, rope_theta: float, rope_scale: float
) -> jax.Array:
    """Inverse frequencies, shape [rotary_dim // 2], fp32."""
    i = jnp.arange(rotary_dim // 2, dtype=jnp.float32)
    return 1.0 / (rope_scale * rope_theta ** (2.0 * i / rotary_dim))


def _llama31_scale_freqs(
    freqs: jax.Array,
    rope_scale: float,
    low_freq_factor: float,
    high_freq_factor: float,
    old_context_len: int,
) -> jax.Array:
    """Llama-3.1 piecewise frequency rescaling (pos_enc.cuh Llama31 path)."""
    wavelen = 2.0 * jnp.pi / freqs
    low_bound = old_context_len / low_freq_factor
    high_bound = old_context_len / high_freq_factor
    smooth = (old_context_len / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    scaled = jnp.where(
        wavelen > low_bound,
        freqs / rope_scale,
        jnp.where(
            wavelen < high_bound,
            freqs,
            (1.0 - smooth) * freqs / rope_scale + smooth * freqs,
        ),
    )
    return scaled


def _apply_rotary(
    x: jax.Array,  # [n, heads, head_dim]
    cos: jax.Array,  # [n, rotary_dim // 2]
    sin: jax.Array,  # [n, rotary_dim // 2]
    rotary_dim: int,
    interleave: bool,
) -> jax.Array:
    xf = x.astype(jnp.float32)
    rot, rest = xf[..., :rotary_dim], xf[..., rotary_dim:]
    c = cos[:, None, :]
    s = sin[:, None, :]
    if interleave:
        x1 = rot[..., 0::2]
        x2 = rot[..., 1::2]
        o1 = x1 * c - x2 * s
        o2 = x2 * c + x1 * s
        out_rot = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
    else:
        half = rotary_dim // 2
        x1 = rot[..., :half]
        x2 = rot[..., half:]
        out_rot = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out_rot, rest], axis=-1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("interleave",))
def rotate_at_positions(
    x: jax.Array,  # [nnz, heads, head_dim]
    pos_ids: jax.Array,  # [nnz] int
    rope_scale=1.0,
    rope_theta=1e4,
    *,
    interleave: bool = False,
) -> jax.Array:
    """Rotate one tensor by per-row absolute positions — the in-attention
    RoPE primitive the pos_encoding_mode="ROPE_LLAMA" paths use (the
    reference rotates q/k inside the kernel from an UNROTATED cache;
    here rotation happens as an elementwise pass before attention, which
    is position-equivalent up to one rounding in x.dtype — callers with
    sub-16-bit caches upcast first).  scale/theta ride as traced scalars
    (plan-derived), so one compiled rotation serves every geometry.
    ``interleave=True`` is the GPT-NeoX-interleaved (is_neox=False)
    pairing."""
    head_dim = x.shape[-1]
    freqs = _rope_freqs(head_dim, rope_theta, rope_scale)
    angles = pos_ids.astype(jnp.float32)[:, None] * freqs[None, :]
    return _apply_rotary(
        x, jnp.cos(angles), jnp.sin(angles), head_dim, interleave
    )


@functools.partial(
    jax.jit,
    static_argnames=("rope_scale", "rope_theta", "interleave"),
)
def rotate_at_positions_static(
    x: jax.Array,
    pos_ids: jax.Array,
    *,
    rope_scale: float = 1.0,
    rope_theta: float = 1e4,
    interleave: bool = False,
) -> jax.Array:
    """:func:`rotate_at_positions` with STATIC scale/theta — the
    fused-ingest ORACLE rotation (prefill.run_ingest composed tier and
    the parity tests).  The ingest kernel's trace closes over python-
    float scale/theta, so its freq ``pow`` lowers with a CONSTANT base;
    a traced theta's runtime pow rounds ~1 ULP differently on XLA CPU —
    enough to break the f32 bitwise fused-vs-composed pin.  Statics
    here reproduce the kernel's constant-base lowering exactly."""
    head_dim = x.shape[-1]
    freqs = _rope_freqs(head_dim, float(rope_theta), float(rope_scale))
    angles = pos_ids.astype(jnp.float32)[:, None] * freqs[None, :]
    return _apply_rotary(
        x, jnp.cos(angles), jnp.sin(angles), head_dim, interleave
    )


@functools.partial(
    jax.jit,
    static_argnames=("rotary_dim", "interleave", "rope_scale", "rope_theta"),
)
def apply_rope_pos_ids(
    q: jax.Array,  # [nnz, num_qo_heads, head_dim]
    k: jax.Array,  # [nnz, num_kv_heads, head_dim]
    pos_ids: jax.Array,  # [nnz] int32
    rotary_dim: Optional[int] = None,
    interleave: bool = False,
    rope_scale: float = 1.0,
    rope_theta: float = 1e4,
) -> Tuple[jax.Array, jax.Array]:
    """Apply RoPE at explicit positions (reference ``apply_rope_pos_ids``,
    flashinfer/rope.py:768 family)."""
    head_dim = q.shape[-1]
    rd = rotary_dim or head_dim
    freqs = _rope_freqs(rd, rope_theta, rope_scale)
    angles = pos_ids.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (
        _apply_rotary(q, cos, sin, rd, interleave),
        _apply_rotary(k, cos, sin, rd, interleave),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "rotary_dim", "interleave", "rope_scale", "rope_theta",
        "low_freq_factor", "high_freq_factor", "old_context_len",
    ),
)
def apply_llama31_rope_pos_ids(
    q: jax.Array,
    k: jax.Array,
    pos_ids: jax.Array,
    rotary_dim: Optional[int] = None,
    interleave: bool = False,
    rope_scale: float = 8.0,
    rope_theta: float = 5e5,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    old_context_len: int = 8192,
) -> Tuple[jax.Array, jax.Array]:
    """Llama-3.1-style RoPE with piecewise NTK frequency scaling
    (reference ``apply_llama31_rope_pos_ids``)."""
    head_dim = q.shape[-1]
    rd = rotary_dim or head_dim
    base = _rope_freqs(rd, rope_theta, 1.0)
    freqs = _llama31_scale_freqs(
        base, rope_scale, low_freq_factor, high_freq_factor, old_context_len
    )
    angles = pos_ids.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (
        _apply_rotary(q, cos, sin, rd, interleave),
        _apply_rotary(k, cos, sin, rd, interleave),
    )


def _pos_ids_from_indptr(indptr: jax.Array, offsets: jax.Array, nnz: int) -> jax.Array:
    """Per-token positions for ragged batches: token i of request r gets
    ``offsets[r] + i`` (reference indptr/offset form, rope.py)."""
    req = jnp.searchsorted(indptr, jnp.arange(nnz), side="right") - 1
    return (jnp.arange(nnz) - indptr[req] + offsets[req]).astype(jnp.int32)


@flashinfer_api
def apply_rope(
    q: jax.Array,
    k: jax.Array,
    indptr: jax.Array,
    offsets: jax.Array,
    rotary_dim: Optional[int] = None,
    interleave: bool = False,
    rope_scale: float = 1.0,
    rope_theta: float = 1e4,
) -> Tuple[jax.Array, jax.Array]:
    """Ragged-batch RoPE (reference ``apply_rope``): ``indptr`` delimits
    requests in the flattened token axis, ``offsets`` gives each request's
    starting position."""
    pos_ids = _pos_ids_from_indptr(indptr, offsets, q.shape[0])
    return apply_rope_pos_ids(
        q, k, pos_ids, rotary_dim, interleave, rope_scale, rope_theta
    )


@flashinfer_api
def apply_llama31_rope(
    q: jax.Array,
    k: jax.Array,
    indptr: jax.Array,
    offsets: jax.Array,
    rotary_dim: Optional[int] = None,
    interleave: bool = False,
    rope_scale: float = 8.0,
    rope_theta: float = 5e5,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    old_context_len: int = 8192,
) -> Tuple[jax.Array, jax.Array]:
    pos_ids = _pos_ids_from_indptr(indptr, offsets, q.shape[0])
    return apply_llama31_rope_pos_ids(
        q, k, pos_ids, rotary_dim, interleave, rope_scale, rope_theta,
        low_freq_factor, high_freq_factor, old_context_len,
    )


@functools.partial(jax.jit, static_argnames=("interleave",))
def apply_rope_with_cos_sin_cache(
    q: jax.Array,
    k: jax.Array,
    cos_sin_cache: jax.Array,  # [max_pos, rotary_dim] = [cos || sin] halves
    pos_ids: jax.Array,
    interleave: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """RoPE from a precomputed cos/sin cache (vLLM layout: ``cos_sin_cache``
    rows are ``[cos(rotary_dim/2) || sin(rotary_dim/2)]``; reference
    ``apply_rope_with_cos_sin_cache``, flashinfer/rope.py)."""
    rotary_dim = cos_sin_cache.shape[-1]
    half = rotary_dim // 2
    entry = cos_sin_cache[pos_ids].astype(jnp.float32)
    cos, sin = entry[:, :half], entry[:, half:]
    return (
        _apply_rotary(q, cos, sin, rotary_dim, interleave),
        _apply_rotary(k, cos, sin, rotary_dim, interleave),
    )


def generate_cos_sin_cache(
    max_position: int,
    rotary_dim: int,
    rope_theta: float = 1e4,
    rope_scale: float = 1.0,
    dtype=jnp.float32,
) -> jax.Array:
    """Build the [max_pos, rotary_dim] cos/sin cache in vLLM layout."""
    freqs = _rope_freqs(rotary_dim, rope_theta, rope_scale)
    angles = jnp.arange(max_position, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(angles), jnp.sin(angles)], axis=-1).astype(dtype)


def _rope_2d_guard(x):
    """MLA tensors may arrive 2-D [T, dim] (no head axis, reference
    rope.py:1286 layout); lift to 3-D and remember to squeeze back."""
    if x is None:
        return None, False
    if x.ndim == 2:
        return x[:, None, :], True
    return x, False


def _fp8_static(x, scale, dtype=jnp.float8_e4m3fn):
    """Static-scale fp8 cast (reference quant_scale semantics:
    fp8_value = high_precision * scale, saturating)."""
    finfo = jnp.finfo(dtype)
    return jnp.clip(
        x.astype(jnp.float32) * scale, float(finfo.min), float(finfo.max)
    ).astype(dtype)


@flashinfer_api
def rope_quantize_fp8(
    q_rope: jax.Array,  # [T, Hq, rotary_dim] (or [T, rotary_dim] MLA form)
    k_rope: jax.Array,  # [T, Hk, rotary_dim] / [T, rotary_dim]
    q_nope: Optional[jax.Array],  # [T, Hq, d_nope] / [T, d_nope]
    k_nope: Optional[jax.Array],
    cos_sin_cache: jax.Array,
    pos_ids: jax.Array,
    is_neox: bool = True,
    quant_scale_q: float = 1.0,
    quant_scale_kv: float = 1.0,
):
    """RoPE the rotary halves, fp8-quantize rotary and nope parts with
    static scales (reference ``rope_quantize_fp8``, flashinfer/rope.py:1364
    — the pre-attention quantized-QK path).

    Matches the reference contract: ``is_neox=True`` is the split-half
    (non-interleaved) rotation; returns the 4-tuple
    ``(q_rope_fp8, k_rope_fp8, q_nope_fp8, k_nope_fp8)`` (``None``
    entries pass through as ``None``) so MLA callers can route kpe/ckv to
    their separate caches; dequantize with ``1/scale``.  2-D MLA-layout
    tensors (no head axis) are accepted."""
    (qr3, q2d), (kr3, k2d) = _rope_2d_guard(q_rope), _rope_2d_guard(k_rope)
    qo, ko = apply_rope_with_cos_sin_cache(
        qr3, kr3, cos_sin_cache, pos_ids, interleave=not is_neox
    )
    if q2d:
        qo = qo[:, 0]
    if k2d:
        ko = ko[:, 0]
    return (
        _fp8_static(qo, quant_scale_q),
        _fp8_static(ko, quant_scale_kv),
        None if q_nope is None else _fp8_static(q_nope, quant_scale_q),
        None if k_nope is None else _fp8_static(k_nope, quant_scale_kv),
    )


@flashinfer_api
def mla_rope_quantize_fp8(q_rope, k_rope, q_nope, k_nope, cos_sin_cache,
                          pos_ids, is_neox: bool = True,
                          quant_scale_q: float = 1.0,
                          quant_scale_kv: float = 1.0):
    """MLA variant of :func:`rope_quantize_fp8` (reference rope.py:1286):
    the same op over the MLA split — 2-D ``k_rope`` (kpe, shared across
    heads) and ``k_nope`` (ckv) are the expected layout."""
    return rope_quantize_fp8(
        q_rope, k_rope, q_nope, k_nope, cos_sin_cache, pos_ids,
        is_neox=is_neox, quant_scale_q=quant_scale_q,
        quant_scale_kv=quant_scale_kv,
    )


def _ingest_append_runs(batch_indices, positions, pos_ids, page_size):
    """Host-side geometry gate for the fused-ingest append reroute:
    concrete arrays forming ascending per-request runs with contiguous
    append positions covering WHOLE pages (page-aligned start AND end)
    and contiguous rope positions.  Returns ``(B_runs, req_ids,
    append_lens, pos0s, rope_pos0s)`` or None when the geometry (or
    tracing context) rules the fused path out.

    The end-alignment requirement is a correctness gate, not a
    convenience: the ingest kernel writes back whole pages and zeroes
    a last partial page's rows past the run, while the composed append
    preserves whatever the cache held there — on an interior re-append
    (a request whose cached sequence extends past this run) those rows
    are LIVE KV.  This call cannot know the sequence length, so only
    runs that never produce a partial page reroute; the composed tier
    serves every tail chunk."""
    import numpy as np

    try:
        bi = np.asarray(batch_indices)
        pos = np.asarray(positions)
        rp = np.asarray(pos_ids)
    except Exception:  # noqa: BLE001 - tracers: stay on the composed tier
        return None
    if bi.ndim != 1 or bi.size == 0 or pos.shape != bi.shape \
            or rp.shape != bi.shape:
        return None
    if np.any(np.diff(bi) < 0):
        return None  # runs must be request-ascending (flat-concat order)
    req_ids, starts = np.unique(bi, return_index=True)
    ends = np.append(starts[1:], bi.size)
    lens = ends - starts
    for s, e in zip(starts, ends):
        if np.any(np.diff(pos[s:e]) != 1) or np.any(np.diff(rp[s:e]) != 1):
            return None  # non-contiguous run
        if int(pos[s]) % page_size != 0:
            return None  # mid-page start would need a sub-page merge
        if int(pos[e - 1] + 1) % page_size != 0:
            return None  # mid-page end: the whole-page write-back
            #              would zero rows the composed append keeps
    return req_ids, starts, lens, pos[starts], rp[starts]


@functools.lru_cache(maxsize=8)
def _default_csc_np(max_pos: int, rot_dim: int):
    """Host copy of the analytically-default cos/sin cache, built once
    per geometry (the reroute's equality reference)."""
    import numpy as np

    return np.asarray(generate_cos_sin_cache(max_pos, rot_dim))


# id(cos_sin_cache) -> (weakref-to-it, verdict).  The weakref guards
# against id reuse after GC; the memo makes the per-call cost of the
# default-cache check one dict hit on the serving path (per layer,
# per step) instead of a device sync + O(max_pos*rd) compare.
_INGEST_CSC_OK: dict = {}
# run-geometry key -> (device plan, statics): the host planner's
# output is pure in its inputs, and serving calls repeat the same
# geometry every layer of every step.
_INGEST_PLAN_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_INGEST_PLAN_CAP = 64


def _fused_ingest_append(
    k_rope, v, cos_sin_cache, pos_ids, paged_kv_cache,
    kv_indices, kv_indptr, batch_indices, positions,
    is_neox: bool, quant_scale_kv: float,
):
    """The ISSUE 14 reroute: run the K/V half of the rope-quantize-
    append through :func:`~flashinfer_tpu.ops.paged_prefill.
    fused_paged_prefill_ingest` (append-only form) — one raw read + one
    quantized-page write instead of the composed three passes.  Returns
    the updated caches, or None when geometry keeps the composed tier:
    HND fp8 caches, full-head rotary, an analytically-default cos/sin
    cache (the kernel recomputes the rotation in-register — only a
    ``generate_cos_sin_cache``-default cache is bitwise reproducible),
    concrete whole-page append runs (page-aligned start AND end — see
    :func:`_ingest_append_runs` for why a partial last page is a
    correctness hazard, not a missed optimisation), and a resolved
    pallas backend (off-TPU auto stays composed;
    FLASHINFER_TPU_BACKEND=pallas forces, the fused-prefill
    precedent)."""
    import numpy as np

    from flashinfer_tpu.utils import resolve_backend

    if resolve_backend("auto", "rope_quantize_ingest") != "pallas":
        return None
    if not isinstance(paged_kv_cache, tuple):
        return None
    k_cache, v_cache = paged_kv_cache
    if k_cache.dtype not in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        return None
    if k_rope.ndim != 3 or k_rope.shape[-1] != k_cache.shape[-1]:
        return None  # partial rotary / MLA 2-D layouts stay composed
    page_size = int(k_cache.shape[2])
    runs = _ingest_append_runs(batch_indices, positions, pos_ids,
                               page_size)
    if runs is None:
        return None
    req_ids, _starts, lens, pos0s, rope0s = runs
    rd = int(cos_sin_cache.shape[-1])
    if rd != k_rope.shape[-1]:
        return None
    # the kernel recomputes cos/sin analytically: only the default
    # Llama cache (theta 1e4, scale 1 — generate_cos_sin_cache's
    # constant-base pow, bitwise the kernel's) reroutes.  Verdict
    # memoized by object identity — the serving path passes the same
    # cache array every layer of every step.
    memo = _INGEST_CSC_OK.get(id(cos_sin_cache))
    if memo is not None and memo[0]() is cos_sin_cache:
        csc_ok = memo[1]
    else:
        try:
            csc = np.asarray(cos_sin_cache)
            ref = weakref.ref(cos_sin_cache)
        except Exception:  # noqa: BLE001 - tracers: stay composed
            return None
        csc_ok = np.array_equal(csc, _default_csc_np(csc.shape[0], rd))
        _INGEST_CSC_OK[id(cos_sin_cache)] = (ref, csc_ok)
        if len(_INGEST_CSC_OK) > 4 * _INGEST_PLAN_CAP:
            _INGEST_CSC_OK.clear()  # dead-id hygiene, verdicts are cheap
    if not csc_ok:
        return None
    try:
        kvi = np.asarray(kv_indices)
        kvp = np.asarray(kv_indptr)
    except Exception:  # noqa: BLE001
        return None
    from flashinfer_tpu.ops.paged_prefill import (
        build_prefill_ingest_units, fused_paged_prefill_ingest,
        ingest_pages_per_chunk,
    )
    from flashinfer_tpu.utils import cdiv

    # per-run page tables sliced to the APPEND region (chunk 0 starts
    # at the run's first page; pos0 is page-aligned by the gate)
    pages: list = []
    pi = [0]
    for r, p0, ln in zip(req_ids, pos0s, lens):
        p0 = int(p0)
        lo = int(kvp[r]) + p0 // page_size
        hi = int(kvp[r]) + cdiv(p0 + int(ln), page_size)
        pages.extend(kvi[lo:hi])
        pi.append(len(pages))
    B = len(req_ids)
    plan_key = (page_size, tuple(int(x) for x in lens),
                tuple(int(x) for x in rope0s),
                tuple(int(x) for x in pages))
    cached = _INGEST_PLAN_CACHE.get(plan_key)
    if cached is not None:
        _INGEST_PLAN_CACHE.move_to_end(plan_key)
        plan, statics = cached
    else:
        ppc = ingest_pages_per_chunk(page_size)
        plan_np = build_prefill_ingest_units(
            np.arange(B + 1, dtype=np.int64), np.asarray(pi, np.int64),
            np.asarray(pages, np.int64), np.asarray(lens, np.int64),
            block_q=8, pages_per_chunk=ppc, page_size=page_size,
            causal=False, prune=False,
            fused_ingest={"pos_offsets": np.asarray(rope0s, np.int64)},
        )
        statics = dict(
            num_units=plan_np.pop("num_units"),
            block_q=plan_np.pop("block_q"),
            pages_per_chunk=plan_np.pop("pages_per_chunk"),
            kv_aligned=plan_np.pop("stats")["ingest_kv_aligned"],
        )
        plan = {k: jnp.asarray(a) for k, a in plan_np.items()}
        _INGEST_PLAN_CACHE[plan_key] = (plan, statics)
        if len(_INGEST_PLAN_CACHE) > _INGEST_PLAN_CAP:
            _INGEST_PLAN_CACHE.popitem(last=False)
    if not statics["kv_aligned"]:
        return None  # whole-page runs of a page narrower than the tile
    scale = 1.0 / max(quant_scale_kv, 1e-12)
    return fused_paged_prefill_ingest(
        None, k_rope, v, k_cache, v_cache, plan,
        causal=False, attend=False, kv_quant="fp8",
        k_scale=scale, v_scale=scale,
        rope_interleave=not is_neox, **statics,
    )


@flashinfer_api
def rope_quantize_fp8_append_paged_kv_cache(
    q_rope, k_rope, q_nope, k_nope, v,
    cos_sin_cache, pos_ids,
    paged_kv_cache, kv_indices, kv_indptr,
    batch_indices, positions,
    kv_layout: str = "NHD",
    is_neox: bool = True,
    quant_scale_q: float = 1.0,
    quant_scale_kv: float = 1.0,
):
    """RoPE + fp8 quantize + quantizing paged append in one call
    (reference rope.py:1504, GQA/MHA form).  Returns
    ``(q_fp8 [T, Hq, rd(+dn)], (k_cache, v_cache))`` with the caches
    updated (functional JAX: new arrays; in-place under jit donation).

    When geometry allows (HND fp8 caches, full-head rotary with the
    default cos/sin cache, page-aligned contiguous append runs, pallas
    backend resolved) the K/V half REROUTES onto the fused-ingest
    work-unit kernel — one raw read + one quantized-page write, cache
    bits identical (tests/test_prefill_ingest.py pins fused == composed
    bit-for-bit; rows past each run's end in its last partial page are
    deterministically zeroed, see ``fused_paged_prefill_ingest``).  The
    separate-op composition below stays as the oracle tier and serves
    every other geometry.

    MLA (``v is None``) is not fused here — BY CONTRACT it exits before
    the reroute is ever considered: MLA appends target the split
    ckv/kpe caches — use :func:`mla_rope_quantize_fp8` +
    ``page.append_paged_mla_kv_cache``."""
    if v is None:
        raise NotImplementedError(
            "MLA form (v=None): use mla_rope_quantize_fp8 + "
            "page.append_paged_mla_kv_cache (split ckv/kpe caches)"
        )
    from flashinfer_tpu.page import append_paged_kv_cache_quant_fp8
    from flashinfer_tpu.utils import TensorLayout, check_kv_layout

    caches = None
    if k_nope is None and check_kv_layout(kv_layout) == TensorLayout.HND:
        caches = _fused_ingest_append(
            k_rope, v, cos_sin_cache, pos_ids, paged_kv_cache,
            kv_indices, kv_indptr, batch_indices, positions,
            is_neox, quant_scale_kv,
        )
    if caches is not None:
        qr, _ = apply_rope_with_cos_sin_cache(
            q_rope, q_rope, cos_sin_cache, pos_ids,
            interleave=not is_neox
        )
        q_hp = qr if q_nope is None else jnp.concatenate([qr, q_nope], -1)
        return _fp8_static(q_hp, quant_scale_q), caches

    qr, kr = apply_rope_with_cos_sin_cache(
        q_rope, k_rope, cos_sin_cache, pos_ids, interleave=not is_neox
    )
    q_hp = qr if q_nope is None else jnp.concatenate([qr, q_nope], -1)
    k_hp = kr if k_nope is None else jnp.concatenate([kr, k_nope], -1)
    qq = _fp8_static(q_hp, quant_scale_q)
    # the quantizing append owns the k/v fp8 conversion (scale semantics:
    # high_precision = fp8 * scale, so the append scale is 1/quant_scale)
    caches = append_paged_kv_cache_quant_fp8(
        k_hp, v, batch_indices, positions, paged_kv_cache,
        kv_indices, kv_indptr,
        jnp.float32(1.0 / max(quant_scale_kv, 1e-12)),
        jnp.float32(1.0 / max(quant_scale_kv, 1e-12)),
        kv_layout,
    )
    return qq, caches
