"""Testing & perf-measurement utilities.

TPU re-design of the reference's ``flashinfer/testing/utils.py`` — the eager
reference attention used by every correctness test, tolerance helpers, the
FLOPs/bytes calculators (testing/utils.py:456-751), and a device-time
benchmark timer (testing/utils.py:774-1546; cold-L2 rotation is replaced by
buffer donation + ``block_until_ready`` median timing, the TPU-appropriate
protocol per BASELINE.md).
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Reference attention (pure jnp, fp32 accumulation)
# ---------------------------------------------------------------------------


def attention_ref(
    q: jax.Array,  # [qo_len, num_qo_heads, head_dim]
    k: jax.Array,  # [kv_len, num_kv_heads, head_dim]
    v: jax.Array,  # [kv_len, num_kv_heads, head_dim_vo]
    causal: bool = False,
    sm_scale: Optional[float] = None,
    logits_soft_cap: float = 0.0,
    window_left: int = -1,
    custom_mask: Optional[jax.Array] = None,  # [qo_len, kv_len] bool
    return_lse: bool = False,
):
    """Eager attention reference with GQA head-group broadcast.

    Matches the semantics of the reference's test helper attention
    (e.g. tests/attention/test_batch_prefill_kernels.py): causal alignment is
    bottom-right (query i attends to kv <= kv_len - qo_len + i), ALiBi and
    soft-cap applied pre-softmax, LSE returned in natural log units.
    """
    qo_len, num_qo_heads, head_dim = q.shape
    kv_len, num_kv_heads, _ = k.shape
    group = num_qo_heads // num_kv_heads
    if sm_scale is None:
        sm_scale = 1.0 / float(head_dim) ** 0.5

    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)

    # [heads, qo, kv]
    logits = jnp.einsum("qhd,khd->hqk", qf, kf) * sm_scale
    if logits_soft_cap > 0.0:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)

    mask = jnp.ones((qo_len, kv_len), dtype=bool)
    if causal:
        qi = jnp.arange(qo_len)[:, None]
        ki = jnp.arange(kv_len)[None, :]
        mask = mask & (ki <= qi + (kv_len - qo_len))
    if window_left >= 0:
        qi = jnp.arange(qo_len)[:, None]
        ki = jnp.arange(kv_len)[None, :]
        mask = mask & (ki >= qi + (kv_len - qo_len) - window_left)
    if custom_mask is not None:
        mask = mask & custom_mask

    logits = jnp.where(mask[None], logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)  # [heads, qo]
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(logits, axis=-1), vf)
    out = out.astype(q.dtype)
    if return_lse:
        return out, jnp.transpose(lse)  # [qo, heads]
    return out


def assert_close(actual, expected, rtol=1e-3, atol=1e-3, name=""):
    np.testing.assert_allclose(
        np.asarray(actual, dtype=np.float32),
        np.asarray(expected, dtype=np.float32),
        rtol=rtol,
        atol=atol,
        err_msg=name,
    )


# ---------------------------------------------------------------------------
# FLOPs / bytes calculators (reference testing/utils.py:456-751)
# ---------------------------------------------------------------------------


def attention_flops(
    qo_len: int, kv_len: int, num_qo_heads: int, head_dim_qk: int,
    head_dim_vo: int, causal: bool = False,
) -> float:
    """Total attention FLOPs (QK^T + PV) for one request."""
    if causal and qo_len > 1:
        # each query i sees kv_len - qo_len + i + 1 keys
        attended = qo_len * (kv_len - qo_len) + (qo_len * (qo_len + 1)) // 2
    else:
        attended = qo_len * kv_len
    return 2.0 * attended * num_qo_heads * (head_dim_qk + head_dim_vo)


def attention_bytes(
    qo_len: int, kv_len: int, num_qo_heads: int, num_kv_heads: int,
    head_dim_qk: int, head_dim_vo: int, dtype_bytes: int = 2,
) -> float:
    """HBM bytes moved by one attention call (q+k+v+o), decode-bound metric."""
    q = qo_len * num_qo_heads * head_dim_qk
    k = kv_len * num_kv_heads * head_dim_qk
    v = kv_len * num_kv_heads * head_dim_vo
    o = qo_len * num_qo_heads * head_dim_vo
    return float((q + k + v + o) * dtype_bytes)


# ---------------------------------------------------------------------------
# Benchmark timer
# ---------------------------------------------------------------------------


def bench_fn(
    fn: Callable,
    *args,
    warmup: int = 3,
    iters: int = 20,
    **kwargs,
) -> float:
    """Median wall time per call in seconds, device-synchronized.

    TPU analogue of ``bench_gpu_time`` (reference testing/utils.py:774):
    compile+warm first, then time each iteration with ``block_until_ready``.

    .. warning:: Per-call dispatch overhead is inside this number; for a
       microsecond kernel it dwarfs kernel time.  Use
       :func:`bench_fn_device` for the kernel alone.
    """
    out = fn(*args, **kwargs)  # compile
    for _ in range(max(warmup - 1, 0)):
        out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_fn_device(
    fn: Callable,
    x: jax.Array,
    *rest,
    iters_low: int = 8,
    iters_high: int = 40,
    repeats: int = 3,
) -> float:
    """Device-honest per-call time via an in-jit iteration loop + slope fit.

    Runs ``fn`` ``iters`` times inside one jitted ``lax.fori_loop``, with a
    data-dependency chain that defeats both loop hoisting and dead-code
    elimination:

    * the input ``x`` is perturbed by ``carry * 1e-30`` so iteration *i*
      depends on iteration *i-1* (no cross-iteration parallelism / CSE);
    * the carry is a full-output reduction, so XLA must compute every
      element of ``fn``'s output (slicing the carry from one element lets
      XLA dead-code-eliminate the rest of the computation).

    Per-iteration time is the **slope** ``(t(iters_high) - t(iters_low)) /
    (iters_high - iters_low)``, which cancels fixed dispatch/transfer
    overhead exactly.

    ``fn`` takes the (perturbed) ``x`` plus ``rest`` and returns an array or
    pytree.  Every large operand MUST be passed through ``rest`` (or ``x``),
    never closed over: jit embeds closure-captured device arrays as HLO
    constants, and a GB-scale KV cache serialized into the HLO blows up the
    compile.  Reduction traffic is fused and adds no HBM round-trip
    for the dominant input reads.
    """
    @jax.jit
    def _timed(n, x, *rest):
        # n is TRACED: one compiled while-loop serves every iteration
        # count (one compile per bench call instead of two per
        # escalation stage), and the lo/hi measurements of a pair are
        # guaranteed to run the SAME executable
        def body(i, carry):
            # cast keeps x's dtype (bf16 + f32 would silently promote
            # and benchmark an f32 kernel variant)
            out = fn(x + (carry * 1e-30).astype(x.dtype), *rest)
            leaves = jax.tree_util.tree_leaves(out)
            return sum(
                jnp.sum(leaf.astype(jnp.float32)) for leaf in leaves
            ) * 1e-30
        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    # Two defenses against dispatch jitter and against stalls on a host
    # whose CPU cores are shared (a stall inside one stage reads as a
    # phantom per-iteration cost):
    #
    # 1. ESCALATION: the slope numerator (t_hi - t_lo) must clear the
    #    dispatch jitter by a wide margin; for microsecond ops 32 extra
    #    iterations (~0.5 ms) is far below the +-5 ms floor, so iteration
    #    counts escalate x8 until the numerator >= 25 ms or the cap.
    # 2. FLOORS + CROSS-SCALE CONFIRMATION: within a stage, mins over
    #    1 + `repeats` (lo, hi) cycles reject stalls shorter than the
    #    stage; a degraded window swallowing a WHOLE low stage is caught
    #    by re-measuring at the next scale up and keeping the smaller
    #    positive slope (the true slope is scale-invariant and the
    #    poison is positive-only).
    _MIN_NUMERATOR_S = 0.025
    _SCALES = (1, 8, 64, 512, 4096)

    def _time_once(n):
        t0 = time.perf_counter()
        float(_timed(n, x, *rest))
        return time.perf_counter() - t0

    def _stage(scale, cycles):
        """Floored (t_lo, t_hi, slope) over `cycles` (lo, hi) pairs."""
        n_lo, n_hi = iters_low * scale, iters_high * scale
        t_lo_min = float("inf")
        t_hi_min = float("inf")
        for _ in range(cycles):
            t_lo_min = min(t_lo_min, _time_once(n_lo))
            t_hi_min = min(t_hi_min, _time_once(n_hi))
        return t_lo_min, t_hi_min, (t_hi_min - t_lo_min) / (n_hi - n_lo)

    float(_timed(iters_low, x, *rest))  # the one compile, before timing
    for idx, scale in enumerate(_SCALES):
        # probe: one (lo, hi) cycle decides whether this scale's delta
        # can clear the jitter floor at all
        t_lo_min, t_hi_min, slope = _stage(scale, 1)
        if (t_hi_min - t_lo_min) < _MIN_NUMERATOR_S and scale != _SCALES[-1]:
            continue
        # full measurement: floors over `repeats` more cycles (a min is
        # immune to positive stalls); acceptance is judged on the FLOORED
        # numerator, so a stall inflating the probe alone cannot lock in
        # an under-escalated scale
        t_lo2, t_hi2, slope = _stage(scale, repeats)
        t_lo_min, t_hi_min = min(t_lo_min, t_lo2), min(t_hi_min, t_hi2)
        slope = (t_hi_min - t_lo_min) / (iters_high - iters_low) / scale
        if (t_hi_min - t_lo_min) >= _MIN_NUMERATOR_S or scale == _SCALES[-1]:
            # CROSS-SCALE CONFIRMATION: a degraded window spanning this
            # whole stage (~1 s at low scales, shorter than the observed
            # windows) would pass the floored check with a ~100x-inflated
            # slope.  The true slope is scale-invariant and the poison is
            # positive-only, so re-measure once at the next scale up and
            # keep the smaller positive slope — a window rarely spans
            # both stages, and floors at each stage reject stalls within
            # it.
            if scale != _SCALES[-1] and slope > 0:
                _, _, slope_c = _stage(_SCALES[idx + 1], max(repeats // 2, 1))
                if 0 < slope_c < slope:
                    slope = slope_c
            break
    if slope <= 0:
        # kernel faster than dispatch jitter even at the escalation cap:
        # report the amortized upper bound rather than nonsense throughput
        return t_hi_min / (iters_high * scale)
    return slope


def bench_steps_device(
    make_loop: Callable,
    *args,
    iters_low: int = 4,
    iters_high: int = 12,
    repeats: int = 3,
) -> float:
    """Slope-timed per-step cost of a loop that CARRIES its state.

    ``make_loop(n)`` must return a jitted callable running ``n`` dependent
    steps with mutable state threaded through a ``lax.scan`` /
    ``while_loop`` carry and a scalar-reducible output.  Use this instead
    of :func:`bench_fn_device` for stateful step benchmarks (serving
    loops with KV caches): ``bench_fn_device`` re-feeds identical inputs
    every iteration, so any buffer the step updates is loop-invariant and
    the update degenerates into a full-buffer copy per iteration — an
    artifact a donation-based serving loop never pays.  A carry lets
    XLA's while-body input/output aliasing update the state in place, so
    the measured step includes only the writes the real loop performs.

    Per-step time is the ``(t(hi) - t(lo)) / (hi - lo)`` slope, which
    cancels fixed dispatch/compile-cache/transfer overhead (see
    :func:`bench_fn_device`; ``float()`` on the result is the execution
    fence).
    """
    lo, hi = make_loop(iters_low), make_loop(iters_high)
    float(lo(*args))  # compile both before timing
    float(hi(*args))
    slopes = []
    t_hi_min = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(lo(*args))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(hi(*args))
        t_hi = time.perf_counter() - t0
        t_hi_min = min(t_hi_min, t_hi)
        slopes.append((t_hi - t_lo) / (iters_high - iters_low))
    slope = float(np.median(slopes))
    if slope <= 0:
        return t_hi_min / iters_high
    return slope
