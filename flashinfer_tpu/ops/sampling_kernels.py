"""Sorting-free top-k / top-p selection Pallas kernel.

TPU re-design of the reference's sorting-free sampling kernels
(``include/flashinfer/sampling.cuh:293-1519`` — dual-pivot rejection over
rounds of global-memory traffic).  The TPU version exploits VMEM capacity:
a full 128k-vocab f32 row is only 512 KB, so the whole distribution is
loaded into VMEM *once* and the threshold search (value-space bisection on
the kept count / kept mass) runs entirely on-chip — one HBM read + one
write per row, versus O(log V) passes for a sort or multi-round rejection.
Tie semantics match the reference's threshold-based kernels (all tokens at
the threshold value are kept), not the arbitrary tie-cut of a sort.

Modes:
- ``top_k``: keep the k largest probs, renormalize.
- ``top_p``: keep the smallest value-threshold set with mass >= p, renorm.
- ``top_k_top_p_seq``: top-k first, then top-p measured on the
  renormalized survivor mass (reference ``filter_apply_order="top_k_first"``).
- ``top_k_top_p_joint``: both constraints measured on the original
  distribution (reference ``"joint"``).
- ``top_k_logits``: mask all but the top-k logits to -inf (no renorm).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import round_up, use_interpret

_BISECT_ITERS = 32
_NEG_INF = -1e30
# values at or below this are treated as masked-out (-inf class): they can
# never be selected, and letting them into the bisection range would either
# poison it (lo0 = -inf -> mid stays -inf forever) or stretch it so wide
# (1e30) that 32 halvings leave ~1e20 resolution
_FINITE_FLOOR = -1e20


def _bisect(p, valid, target_fn, lo, hi):
    """Largest threshold t with ``target_fn(mask(p >= t)) >= target`` via
    value-space bisection; p stays resident in VMEM across iterations."""

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        ge = valid & (p >= mid)
        ok = target_fn(ge)  # [rows, 1] bool: constraint still satisfied
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    return lo


def _bisect_prologue(p, vocab):
    """Shared range setup for the bisection kernels: the valid mask
    (in-vocab, not pre-masked to the -inf class), the (lo0, hi0) search
    range, and the all-masked-row collapse (see _FINITE_FLOOR note)."""
    valid = (
        jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) < vocab
    ) & (p > _FINITE_FLOOR)  # pre-masked (-inf class) tokens never selected
    lo0 = jnp.min(jnp.where(valid, p, jnp.inf), axis=1, keepdims=True) - 1e-6
    hi0 = jnp.max(jnp.where(valid, p, -jnp.inf), axis=1, keepdims=True)
    # all-masked row: collapse to an empty kept set instead of nan/inf math
    any_valid = jnp.isfinite(hi0)
    lo0 = jnp.where(any_valid, lo0, 0.0)
    hi0 = jnp.where(any_valid, hi0, 1.0)
    return valid, lo0, hi0, any_valid


def _count_ge_target(a):
    def count_ge(ge):
        return jnp.sum(ge.astype(jnp.float32), axis=1, keepdims=True) >= a

    return count_ge


def _threshold_kernel(
    p_ref,  # [rb, Vpad] f32
    a_ref,  # [rb, 1] f32 (k as float, or top_p)
    b_ref,  # [rb, 1] f32 (top_p for the combined modes; unused otherwise)
    o_ref,  # [rb, Vpad]
    *,
    vocab: int,
    mode: str,
):
    p = p_ref[...]
    valid, lo0, hi0, _ = _bisect_prologue(p, vocab)
    pv = jnp.where(valid, p, 0.0)
    a = a_ref[...]
    count_ge = _count_ge_target(a)

    def mass_ge_target(target):
        def f(ge):
            return (
                jnp.sum(jnp.where(ge, pv, 0.0), axis=1, keepdims=True)
                >= target
            )
        return f

    if mode == "top_k" or mode == "top_k_logits":
        t = _bisect(p, valid, count_ge, lo0, hi0)
    elif mode == "top_p":
        t = _bisect(p, valid, mass_ge_target(a), lo0, hi0)
    elif mode in ("top_k_top_p_seq", "top_k_top_p_joint"):
        tp = b_ref[...]
        tk = _bisect(p, valid, count_ge, lo0, hi0)
        if mode == "top_k_top_p_seq":
            # top-p measured on the mass surviving the top-k filter
            mass_k = jnp.sum(
                jnp.where(valid & (p >= tk), pv, 0.0), axis=1, keepdims=True
            )
            tpv = _bisect(p, valid, mass_ge_target(tp * mass_k), tk, hi0)
        else:
            tpv = _bisect(p, valid, mass_ge_target(tp), lo0, hi0)
        t = jnp.maximum(tk, tpv)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    keep = valid & (p >= t)
    if mode == "top_k_logits":
        o_ref[...] = jnp.where(keep, p, _NEG_INF)
    else:
        kept = jnp.where(keep, pv, 0.0)
        s = jnp.sum(kept, axis=1, keepdims=True)
        o_ref[...] = kept / jnp.maximum(s, 1e-30)


def _f32_sort_key(p):
    """Order-isomorphic int32 key of an f32 array (the radix-sort float
    transform): key comparisons == value comparisons, including -0.0/+0.0
    adjacency and +/-inf extremes."""
    i = jax.lax.bitcast_convert_type(p, jnp.int32)
    return i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))


def _key_to_f32(key):
    i = jnp.where(key >= 0, key, key ^ jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(i, jnp.float32)


def key_ge(scores, t):
    """Order-key comparisons ``scores >= t`` / ``scores > t`` computed in
    int32 key space -> (ge, gt) bool arrays.

    Float comparisons flush subnormals to zero under XLA (CPU and TPU),
    which breaks top-k selection for subnormal-scale scores; the key
    compare is exact and matches the bisection kernel's own ordering.
    NaN scores are excluded from both results (a NaN key would otherwise
    sort above +inf)."""
    ks = _f32_sort_key(scores.astype(jnp.float32))
    kt = _f32_sort_key(t.astype(jnp.float32))
    if kt.ndim == ks.ndim - 1:
        kt = kt[..., None]
    ok = ~jnp.isnan(scores)
    return (ks >= kt) & ok, (ks > kt) & ok


def _threshold_only_kernel(
    p_ref,  # [rb, Vpad] f32
    a_ref,  # [rb, 1] f32 (k as float)
    o_ref,  # [rb, 128] f32 (threshold, lane-broadcast)
    *,
    vocab: int,
):
    """EXACT k-th-largest threshold via bit-space bisection.

    Value-space bisection (``_bisect``) cannot converge over wide dynamic
    ranges — one ``-1e15`` "effectively -inf" entry leaves the interval
    ~1e15 * 2^-32 wide after 32 halvings, misranking thousands of entries.
    Bisecting on the order-isomorphic int32 KEY instead (the same trick as
    the reference's radix top-k, ``include/flashinfer/topk.cuh``) halves
    an integer interval < 2^32 wide, so 32 iterations pin the threshold to
    the exact k-th value regardless of magnitudes."""
    p = p_ref[...]
    valid, _, _, any_valid = _bisect_prologue(p, vocab)
    keys = _f32_sort_key(p)
    imax = jnp.int32(0x7FFFFFFF)
    lo = jnp.min(jnp.where(valid, keys, imax), axis=1, keepdims=True)
    hi = jnp.max(jnp.where(valid, keys, -imax - 1), axis=1, keepdims=True)
    a = a_ref[...]

    def body(_, carry):
        lo, hi = carry
        # overflow-safe midpoint of two int32s (lo+hi can exceed int32)
        mid = (lo >> 1) + (hi >> 1) + (lo & hi & 1)
        mid = jnp.maximum(mid, lo + 1)  # progress when hi == lo + 1
        ge = valid & (keys >= mid)
        ok = jnp.sum(ge.astype(jnp.float32), axis=1, keepdims=True) >= a
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    # 33, not 32: the key interval can span up to ~2^32 (negative to
    # positive keys), and ceil-halving a >2^31 interval 32 times can end
    # with hi - lo == 1 and hi untested (reviewer-simulated: 53/4000 rows
    # one ULP low at 32 iters, 0/4000 at 33)
    lo, hi = jax.lax.fori_loop(0, 33, body, (lo, hi))
    # lo is the exact key of the k-th largest (or the row min when the row
    # has fewer than k valid entries — keeps everything, short-row rule)
    t = _key_to_f32(lo)[:, :1]
    t = jnp.where(any_valid, t, jnp.inf)  # all-masked row keeps nothing
    o_ref[...] = jnp.broadcast_to(t, o_ref.shape)


def _launch_bisect(kernel, x, scalars, out_cols, block_rows):
    """Shared pad-and-launch scaffold for the row-wise bisection kernels:
    f32 cast, 128-lane vocab pad, row pad to the block, per-row scalar
    operands padded with a harmless 1.0, one grid dim over row blocks.
    ``out_cols=None`` means a full-width [rpad, vpad] output."""
    x = x.astype(jnp.float32)
    batch, vocab = x.shape
    vpad = round_up(vocab, 128)
    rpad = round_up(batch, block_rows)
    if vpad != vocab or rpad != batch:
        x = jnp.pad(x, ((0, rpad - batch), (0, vpad - vocab)))
    ops = [x] + [
        jnp.pad(
            jnp.asarray(s, jnp.float32).reshape(-1, 1),
            ((0, rpad - batch), (0, 0)), constant_values=1.0,
        )
        for s in scalars
    ]
    oc = vpad if out_cols is None else out_cols
    out = pl.pallas_call(
        kernel,
        grid=(rpad // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, vpad), lambda i: (i, 0))]
        + [
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
            for _ in scalars
        ],
        out_specs=pl.BlockSpec((block_rows, oc), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rpad, oc), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=use_interpret(),
    )(*ops)
    return out, batch, vocab


@functools.partial(jax.jit, static_argnames=("block_rows",))
def top_k_thresholds(
    scores: jax.Array,  # [batch, vocab] f32 (logits or probs)
    k: jax.Array,  # [batch] int/float per-row k
    *,
    block_rows: int = 8,
) -> jax.Array:
    """Per-row EXACT k-th-largest value via bit-space bisection -> [batch].

    The index-free half of the sorting-free top-k (reference
    ``include/flashinfer/topk.cuh`` radix threshold pass, re-designed for
    VMEM residency): one HBM read of the row and a [rows, 1] write —
    2x less traffic than :func:`threshold_select`, which writes the
    filtered row back.  The returned threshold is the exact k-th-largest
    value (bit-space bisection, see kernel docstring), so
    ``scores >= t`` keeps >= k entries where the excess is exactly the
    equality tie class at t; callers trim ties to exactly k
    (``flashinfer_tpu.topk``).  Rows with fewer than k selectable entries
    get their row minimum (keep-all); all-masked rows get +inf."""
    out, batch, _ = _launch_bisect(
        functools.partial(_threshold_only_kernel, vocab=scores.shape[1]),
        scores, [k], 128, block_rows,
    )
    return out[:batch, 0]


@functools.partial(jax.jit, static_argnames=("mode", "block_rows"))
def threshold_select(
    probs_or_logits: jax.Array,  # [batch, vocab] f32
    a: jax.Array,  # [batch] k (as float) or top_p
    b: jax.Array,  # [batch] top_p for combined modes (ignored otherwise)
    *,
    mode: str,
    block_rows: int = 8,
):
    """Threshold-based top-k/top-p filtering (see module docstring for modes).

    Epsilon-tie semantics: the value-space bisection runs in f32, so the
    threshold resolves to at best ``~range * 2**-_BISECT_ITERS`` (f32 also
    caps effective resolution near ``range * 2**-24``).  Every token within
    float resolution of the cut is treated as tied and KEPT — on
    near-uniform tails (e.g. flat logits at 128k vocab) the kept set can
    therefore exceed k (or the top-p mass) beyond true exact ties, where a
    sort-based oracle would cut arbitrarily among equals.  This is the
    library's documented tie contract (reference threshold kernels share
    it, ``sampling.cuh:293``); callers needing strict-k must post-trim.
    ``tests/test_sampling.py::test_threshold_near_uniform_ties`` bounds the
    deviation."""
    out, batch, vocab = _launch_bisect(
        functools.partial(
            _threshold_kernel, vocab=probs_or_logits.shape[1], mode=mode
        ),
        probs_or_logits, [a, b], None, block_rows,
    )
    return out[:batch, :vocab]
