"""Fused MoE pipeline: permute -> grouped GEMM -> act -> grouped GEMM -> finalize.

Re-design of ``cutlass_fused_moe`` (reference fused_moe/core.py:873): the
five CUDA stages map to

1. permute: stable argsort of the flattened (token, expert-choice) pairs by
   expert id (the reference's expert-major permutation);
2/4. grouped GEMMs: ``jax.lax.ragged_dot`` over the expert-sorted rows
   (megablox-style — group offsets come from a bincount, no capacity
   padding, no wasted MXU work on empty experts);
3. activation: silu_and_mul on the gate|up halves;
5. finalize: inverse-permute + weighted sum over each token's k choices.

Weight layout: ``w_gate_up [E, hidden, 2*inter]`` ([gate | up] columns),
``w_down [E, inter, hidden]`` — the reference's reorder_rows_for_gated_act
shuffling (core.py:245) is unnecessary because XLA owns the layout.

Expert parallelism (``fused_moe_ep``): the reference's moe_ep subsystem
(SURVEY §2.3 — NCCL-EP / NIXL-RDMA dispatch+combine) maps to the
allgather-dispatch / psum-combine pattern over a mesh axis: every rank
computes its local experts for the full (gathered) token set and the
partial outputs sum over the axis.  An all_to_all dispatch variant is a
later optimization for large EP degrees.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from flashinfer_tpu.activation import silu_and_mul


def _act(h1: jax.Array, activation: str) -> jax.Array:
    if activation == "silu":
        return silu_and_mul(h1)
    if activation == "gelu":
        d = h1.shape[-1] // 2
        return (
            jax.nn.gelu(h1[..., :d].astype(jnp.float32))
            * h1[..., d:].astype(jnp.float32)
        ).astype(h1.dtype)
    raise ValueError(f"unknown activation {activation!r}")


def _quant_rows_int8(x: jax.Array):
    """Dynamic symmetric per-row int8 quantization (activation side)."""
    from flashinfer_tpu.quantization import quantize_int8

    return quantize_int8(x, axis=-1)


def _gmm_tileable(hidden_dim: int, inter2: int) -> bool:
    # gmm picks tk as the largest power-of-two divisor >= 128, so 128
    # alignment of every contraction/output dim is the whole requirement
    return hidden_dim % 128 == 0 and inter2 % 128 == 0 and inter2 // 2 % 128 == 0


def fused_moe(
    hidden: jax.Array,
    w_gate_up: jax.Array,
    w_down: jax.Array,
    topk_weights: jax.Array,
    topk_ids: jax.Array,
    num_experts: int,
    activation: str = "silu",
    w1_scale: Optional[jax.Array] = None,
    w2_scale: Optional[jax.Array] = None,
    backend: str = "auto",
    gather_variant: str = "auto",
    gmm_tiles=None,
) -> jax.Array:
    """Single-device fused MoE forward -> [T, hidden].

    Backends (reference analogue: cutlass vs trtllm-gen backend dispatch,
    fused_moe/core.py:873):

    - ``"gmm"``: Pallas grouped-matmul pipeline (``ops/moe_gmm.py``) — the
      first GEMM gathers token rows straight from the unsorted ``hidden``
      (no ``[T*K, hidden]`` sorted copy in HBM), the second runs over the
      already-grouped activation rows; int8 variants quantize per-token
      BEFORE routing (T rows, not T*K) and fold all scales into the store
      epilogues.
    - ``"ragged"``: ``jax.lax.ragged_dot`` over materialized sorted rows
      (the XLA fallback, and the oracle for tests).
    - ``"auto"``: env ``FLASHINFER_TPU_MOE_BACKEND`` if set, else
      ``"gmm"`` on hardware BY MEASUREMENT: with tuned tile shapes the
      sorted-gather GMM kernel beats ragged_dot at every banked v5e point
      (BENCH_BANKED.md 2026-07-31, Mixtral 8x7B: T=1024 int8
      132 vs 76 TFLOP/s, bf16 85 vs 53; T=256 int8 68 vs 33, bf16
      39 vs 20 — the round-4 "ragged wins 2.6-2.9x" verdict was an
      artifact of the stock (128, 128, 512) tiles, re-banked).  Interpret
      mode (CPU tests) and non-128-aligned shapes stay ragged.

    Backend resolution happens outside the jitted body so the env var is
    re-read on every *eager* call; a caller that wraps fused_moe in its own
    jax.jit pins the trace-time value in that outer cache.
    """
    tileable = _gmm_tileable(hidden.shape[1], w_gate_up.shape[2])
    if backend == "auto":
        import os

        from flashinfer_tpu.utils import use_interpret

        default = "ragged" if use_interpret() else "gmm"
        backend = os.environ.get("FLASHINFER_TPU_MOE_BACKEND", default)
        if backend == "gmm" and not tileable:
            backend = "ragged"  # auto falls back; explicit "gmm" raises
    if backend not in ("gmm", "ragged"):
        raise ValueError(f"unknown fused_moe backend {backend!r}")
    if backend == "gmm" and not tileable:
        raise ValueError(
            "gmm backend requires 128-aligned hidden/inter dims, got "
            f"hidden={hidden.shape[1]} 2*inter={w_gate_up.shape[2]}"
        )
    if backend == "gmm":
        gmm_tiles = _resolve_gmm_tiles(
            gmm_tiles, hidden, w_gate_up, w_down, topk_ids
        )
    else:
        gmm_tiles = None
    return _fused_moe_impl(
        hidden, w_gate_up, w_down, topk_weights, topk_ids, num_experts,
        activation, w1_scale, w2_scale, backend, gather_variant, gmm_tiles,
    )


# Grouped-GEMM tile-shape selection.  The megablox-form kernel's HBM
# traffic scales as tiles_n * M * K (lhs re-streaming across the n sweep)
# + group_visits * K * N (expert-weight streaming), so both shrink with
# bigger tiles: the pre-PR-1 v5e sweep (2026-07-31, Mixtral 8x7B;
# script and record since deleted) has the stock (128, 128, 512)
# blocks at 20-27 TFLOP/s vs (256, 2048, 1024) at 85 bf16 / 132 int8 —
# a 3-4x swing on tile shape alone.  The heuristic below picks
# largest-that-fits tiles; tuning_configs/ ships measured per-shape
# winners and a user autotune() overrides both.
_GMM_VMEM_BUDGET = 13 * 1024 * 1024  # double-buffered blocks + f32 acc


def _heuristic_gmm_tiles(m, k, n, itemsize, out_itemsize=2):
    """Largest (tm, tn, tk) whose double-buffered block footprint fits the
    VMEM budget, with tn an exact divisor of n and tk of k (both stay
    128-aligned; callers validated 128-alignment)."""
    from flashinfer_tpu.ops.moe_gmm import tile_footprint

    def _div_cap(x, cap):
        # largest 128-multiple divisor of x that is <= cap (x is
        # 128-aligned, so d == 128 always succeeds)
        d = (min(cap, x) // 128) * 128
        while d > 128 and x % d:
            d -= 128
        return max(d, 128)

    tm = 256 if m >= 256 else 128
    tn, tk = _div_cap(n, 2048), _div_cap(k, 1024)
    while True:
        footprint = tile_footprint(tm, tn, tk, itemsize, out_itemsize)
        if footprint <= _GMM_VMEM_BUDGET or (tn <= 128 and tk <= 128):
            return (tm, tn, tk)
        # shrink the dominant block first
        if tk * tn >= tm * tn and tn > 128:
            tn = _div_cap(n, tn - 128)
        elif tk > 128:
            tk = _div_cap(k, tk - 128)
        else:
            tn = _div_cap(n, tn - 128)


def _resolve_gmm_tiles(gmm_tiles, hidden, w_gate_up, w_down, topk_ids):
    """Normalize to ((tm1, tn1, tk1), (tm2, tn2, tk2)) for the two grouped
    GEMMs; None consults the autotuner cache keyed by each GEMM's
    (M, K, N, dtype), falling back to the VMEM-bounded heuristic."""
    if gmm_tiles is not None:
        gmm_tiles = tuple(map(tuple, gmm_tiles)) if isinstance(
            gmm_tiles[0], (tuple, list)
        ) else (tuple(gmm_tiles),) * 2
        if len(gmm_tiles) != 2 or any(len(t) != 3 for t in gmm_tiles):
            raise ValueError(
                f"gmm_tiles must be (tm, tn, tk) or a pair of them, got "
                f"{gmm_tiles!r}"
            )
        return gmm_tiles
    from flashinfer_tpu.autotuner import AutoTuner

    tuner = AutoTuner.get()
    m = topk_ids.shape[0] * topk_ids.shape[1]
    h, n1 = w_gate_up.shape[1], w_gate_up.shape[2]
    esz = w_gate_up.dtype.itemsize
    dt = w_gate_up.dtype
    # per-GEMM epilogue output dtypes (must match _fused_moe_impl): the
    # int8 first GEMM stores bf16 directly, the second stores f32 for the
    # combine; bf16 path stores bf16 everywhere
    o1 = jnp.bfloat16 if esz == 1 else dt
    o2 = jnp.float32 if esz == 1 else dt
    h1_def = _heuristic_gmm_tiles(m, h, n1, esz, jnp.dtype(o1).itemsize)
    h2_def = _heuristic_gmm_tiles(
        m, w_down.shape[1], h, esz, jnp.dtype(o2).itemsize
    )
    gemm1, gemm2 = (m, h, n1), (m, w_down.shape[1], h)
    if tuner.tuning_enabled:
        # autotune() context: profile candidates per GEMM geometry with
        # the standalone kernel (writes the same cache keys lookup reads)
        from flashinfer_tpu.ops.moe_gmm import tune_tiles

        t1 = tune_tiles(*gemm1, dt, h1_def, out_dtype=o1)
        t2 = tune_tiles(*gemm2, dt, h2_def, out_dtype=o2)
    else:
        t1 = tuner.lookup("moe_gmm.tiles", (*gemm1, dt), default=h1_def)
        t2 = tuner.lookup("moe_gmm.tiles", (*gemm2, dt), default=h2_def)
    return (tuple(t1), tuple(t2))


@functools.partial(
    jax.jit,
    static_argnames=("num_experts", "activation", "backend",
                     "gather_variant", "gmm_tiles"),
)
def _fused_moe_impl(
    hidden: jax.Array,  # [T, hidden]
    w_gate_up: jax.Array,  # [E, hidden, 2*inter] bf16 OR int8
    w_down: jax.Array,  # [E, inter, hidden]
    topk_weights: jax.Array,  # [T, K] f32
    topk_ids: jax.Array,  # [T, K] int32
    num_experts: int,
    activation: str = "silu",
    w1_scale: Optional[jax.Array] = None,  # [E, 1, 2*inter] (int8 weights)
    w2_scale: Optional[jax.Array] = None,  # [E, 1, hidden]
    backend: str = "ragged",
    gather_variant: str = "auto",
    gmm_tiles=None,
) -> jax.Array:
    """Jitted body of :func:`fused_moe` (backend already resolved).

    With int8 weights (+ per-channel scales), both grouped GEMMs run on the
    native int8 MXU path (int8 x int8 -> int32, the v5e low-precision
    story) with dynamic per-row activation quantization — weights cross
    HBM at half width and the MXU runs at its doubled int8 rate.
    """
    T, K = topk_ids.shape
    dtype = hidden.dtype
    quantized = w_gate_up.dtype == jnp.int8

    flat_expert = topk_ids.reshape(-1)  # [T*K]
    order = jnp.argsort(flat_expert, stable=True)
    inv_token = order // K  # source token of each sorted row
    group_sizes = jnp.bincount(flat_expert, length=num_experts).astype(jnp.int32)

    if backend == "gmm":
        from flashinfer_tpu.ops.moe_gmm import gather_gmm, gmm

        assert gmm_tiles is not None  # resolved by fused_moe for gmm
        (tm1, tn1, tk1), (tm2, tn2, tk2) = gmm_tiles
        if quantized:
            assert w1_scale is not None and w2_scale is not None
            xq, xs = _quant_rows_int8(hidden)  # per-TOKEN: T rows, not T*K
            # out_dtype=dtype: the scaled epilogue stores bf16 directly —
            # writing f32 and casting after costs an extra [M, 2I] f32
            # round-trip (235 MB at Mixtral T=1024) for precision the
            # activation immediately discards
            h1 = gather_gmm(
                xq, inv_token, w_gate_up, group_sizes,
                xs[:, 0], w1_scale.reshape(num_experts, -1),
                variant=gather_variant, tm=tm1, tn=tn1, tk=tk1,
                out_dtype=dtype,
            )
            a = _act(h1, activation)
            aq, as_ = _quant_rows_int8(a)
            h2 = gmm(
                aq, w_down, group_sizes,
                as_[:, 0], w2_scale.reshape(num_experts, -1),
                tm=tm2, tn=tn2, tk=tk2,
            )
        else:
            h1 = gather_gmm(hidden, inv_token, w_gate_up, group_sizes,
                            variant=gather_variant, tm=tm1, tn=tn1, tk=tk1)
            a = _act(h1, activation)
            h2 = gmm(a, w_down, group_sizes, tm=tm2, tn=tn2, tk=tk2)
    elif quantized:
        assert w1_scale is not None and w2_scale is not None
        x_sorted = hidden[inv_token]  # [T*K, hidden]
        expert_sorted = flat_expert[order]  # [T*K]
        xq, xs = _quant_rows_int8(x_sorted)
        h1i = jax.lax.ragged_dot(
            xq, w_gate_up, group_sizes, preferred_element_type=jnp.int32
        )
        ws1 = w1_scale.reshape(num_experts, -1)[expert_sorted]  # [T*K, 2I]
        h1 = (h1i.astype(jnp.float32) * xs * ws1).astype(dtype)
        a = _act(h1, activation)
        aq, as_ = _quant_rows_int8(a)
        h2i = jax.lax.ragged_dot(
            aq, w_down, group_sizes, preferred_element_type=jnp.int32
        )
        ws2 = w2_scale.reshape(num_experts, -1)[expert_sorted]  # [T*K, H]
        h2 = h2i.astype(jnp.float32) * as_ * ws2
    else:
        x_sorted = hidden[inv_token]  # [T*K, hidden]
        h1 = jax.lax.ragged_dot(x_sorted, w_gate_up, group_sizes)  # [T*K, 2I]
        a = _act(h1, activation)
        h2 = jax.lax.ragged_dot(a, w_down, group_sizes)  # [T*K, hidden]

    # finalize: route each sorted row back to (token, choice) and weight-sum
    w_sorted = topk_weights.reshape(-1)[order].astype(jnp.float32)
    contrib = h2.astype(jnp.float32) * w_sorted[:, None]
    out = jnp.zeros((T, hidden.shape[1]), jnp.float32).at[inv_token].add(contrib)
    return out.astype(dtype)


def fused_moe_ep(
    hidden: jax.Array,  # [T_local, hidden] (this rank's tokens)
    w_gate_up: jax.Array,  # [E_local, hidden, 2*inter] (this rank's experts)
    w_down: jax.Array,  # [E_local, inter, hidden]
    topk_weights: jax.Array,  # [T_local, K]
    topk_ids: jax.Array,  # [T_local, K] GLOBAL expert ids
    num_experts: int,
    axis: str = "tp",
    activation: str = "silu",
    dispatch: str = "allgather",
    capacity_factor: float = 2.0,
    return_dropped: bool = False,
):
    """Expert-parallel fused MoE (call inside shard_map).

    Experts are contiguously sharded over ``axis`` (rank r owns
    ``[r*E_local, (r+1)*E_local)``, the Mapping.ep_experts partition).

    Three dispatch modes mirroring the reference moe_ep design space:
    - ``"allgather"``: all_gather tokens + psum_scatter combine — minimal
      latency at small world sizes, bandwidth O(T_global * hidden);
    - ``"alltoall"``: capacity-bucketed token exchange (the reference's
      split-mode NCCL/NIXL dispatch+combine as ``lax.all_to_all``) —
      bandwidth O(T_local * K * hidden), the scalable bounded-latency
      mode.  Tokens beyond ``capacity_factor * T_local * K / ep`` per
      destination are dropped (standard capacity semantics): a dropped
      (token, choice) route contributes ZERO to that token's output, so
      under-capacity routing silently degrades quality rather than
      erroring.
    - ``"alltoall_exact"``: NO-DROP token exchange — parity with the
      reference EP, which delivers every routed token by sizing NCCL
      transfers from an exchanged size tensor
      (moe_ep/modes/split_layer.py:52).  XLA buffers are static-shaped,
      so the TPU-native equivalent runs the same capacity-bucketed
      exchange in ROUNDS under a ``lax.while_loop`` whose trip count all
      ranks agree on via a pmax of the max destination load: balanced
      routing costs exactly one round (identical traffic to
      ``"alltoall"`` plus one scalar pmax), pathological routing costs
      extra rounds instead of dropped tokens.  Latency is data-dependent;
      use ``"alltoall"`` when bounded step time matters more than exact
      delivery.

    Mode selection is backed by a CPU skew study of round counts (round
    5; its script went with `bench.py`): at balanced routing exact
    delivery is FREE (1 round, same bytes/time as capacity mode), so
    ``alltoall_exact`` is the right default for load-balanced routers;
    at zipf-1.5 skew capacity mode silently zeroes ~31% of routes while
    exact pays ~3 rounds (~2.5x step time, 3x bytes) — pick per your
    router's balance and step-time budget.  ``allgather`` stays the
    small-world/latency option (bandwidth O(T_global * hidden),
    skew-insensitive).

    With ``return_dropped=True`` returns ``(out, dropped)`` where
    ``dropped`` is a shape-``[1]`` int32 count of this rank's (token,
    choice) routes that exceeded a destination bucket — the observability
    hook for the capacity-drop semantics (reference analogue: per-split
    token accounting, moe_ep/modes/split_layer.py:52).  Shaped ``[1]`` so
    a shard_map ``out_specs=P(axis)`` concatenates it into per-rank
    counts.  Always 0 for ``"allgather"`` and ``"alltoall_exact"``
    (those modes never drop).
    """
    if dispatch == "allgather":
        ep = jax.lax.axis_size(axis)
        rank = jax.lax.axis_index(axis)
        e_local = w_gate_up.shape[0]

        xg = jax.lax.all_gather(hidden, axis, tiled=True)  # [T_global, hidden]
        wg = jax.lax.all_gather(topk_weights, axis, tiled=True)
        idg = jax.lax.all_gather(topk_ids, axis, tiled=True)

        lo = rank * e_local
        local = (idg >= lo) & (idg < lo + e_local)
        # non-local choices route to a local dummy slot with zero weight
        ids_local = jnp.where(local, idg - lo, 0).astype(jnp.int32)
        w_local = jnp.where(local, wg, 0.0)

        partial = fused_moe(
            xg, w_gate_up, w_down, w_local, ids_local, e_local, activation
        )
        # combine: sum partials, then take this rank's token slice
        out = jax.lax.psum_scatter(partial, axis, tiled=True)
        return (out, jnp.zeros((1,), jnp.int32)) if return_dropped else out
    if dispatch == "alltoall":
        _record_ep_a2a_bytes(hidden, topk_ids, axis, capacity_factor,
                             dispatch)
        out, dropped = _fused_moe_ep_alltoall(
            hidden, w_gate_up, w_down, topk_weights, topk_ids, num_experts,
            axis, activation, capacity_factor,
        )
        # obs wiring for the capacity-drop semantics: a no-op while
        # `dropped` is a tracer (the shard_map/jit steady state — there
        # the caller reads it via return_dropped=True and may feed the
        # concrete per-rank counts to obs.record_dropped_tokens itself)
        from flashinfer_tpu import obs

        obs.record_dropped_tokens(dropped, dispatch)
        return (out, dropped) if return_dropped else out
    if dispatch == "alltoall_exact":
        _record_ep_a2a_bytes(hidden, topk_ids, axis, capacity_factor,
                             dispatch)
        out, dropped = _fused_moe_ep_alltoall_exact(
            hidden, w_gate_up, w_down, topk_weights, topk_ids, num_experts,
            axis, activation, capacity_factor,
        )
        return (out, dropped) if return_dropped else out
    raise ValueError(f"unknown dispatch {dispatch!r}")


def _record_ep_a2a_bytes(hidden, topk_ids, axis, capacity_factor,
                         dispatch: str) -> None:
    """Count this call site's all_to_all payload (dispatch + combine
    activation buffers, ``2 * ep * cap * H`` elements at the hidden
    dtype; eid/valid sideband excluded — noise against H-wide rows).

    Shapes are static even under trace, so this runs host-side at
    TRACE time: the counter is per-call traffic of the compiled
    program (per-ROUND for alltoall_exact, whose round count is
    data-dependent).  obs catalog ``moe.ep_a2a_bytes``; zero-overhead
    with the gate off (default, pinned)."""
    from flashinfer_tpu import obs

    if not obs.metrics_enabled():
        return
    ep = jax.lax.axis_size(axis)
    if not isinstance(ep, int):  # outside shard_map (tests call eager)
        return
    T, K = topk_ids.shape
    cap = _bucket_capacity(T * K, ep, capacity_factor)
    nbytes = 2 * ep * cap * hidden.shape[1] * hidden.dtype.itemsize
    obs.counter_inc("moe.ep_a2a_bytes", int(nbytes), dispatch=dispatch)


def _bucket_capacity(routes: int, ep: int, capacity_factor: float) -> int:
    """Per-destination bucket capacity of the all_to_all dispatch —
    THE capacity rule (shared by :func:`_route_buckets` and the
    ``moe.ep_a2a_bytes`` telemetry so the counted buffer sizes can
    never drift from the exchanged ones)."""
    import math

    return max(1, int(math.ceil(routes / ep * capacity_factor)))


def _route_buckets(topk_ids, e_local, ep, capacity_factor):
    """Shared all_to_all routing prologue.

    Stable-sorts this rank's (token, choice) routes by destination rank
    and returns ``(cap, order, sd, stok, eid, within)``: the bucket
    capacity, the sort permutation, sorted destination ranks, source
    token of each sorted route, destination-LOCAL expert ids, and each
    route's rank within its destination bucket (the capacity/round
    coordinate).  Both the capacity-drop and the exact dispatch build on
    exactly this decomposition — keep them in lockstep.
    """
    T, K = topk_ids.shape
    TK = T * K
    cap = _bucket_capacity(TK, ep, capacity_factor)
    flat_ids = topk_ids.reshape(-1)
    dst = (flat_ids // e_local).astype(jnp.int32)
    order = jnp.argsort(dst, stable=True)
    sd = dst[order]  # sorted destinations
    stok = order // K  # source token of each sorted entry
    eid = (flat_ids[order] % e_local).astype(jnp.int32)
    # index within each destination bucket
    first = jnp.searchsorted(sd, sd, side="left")
    within = jnp.arange(TK) - first
    return cap, order, sd, stok, eid, within


def _fused_moe_ep_alltoall(
    hidden, w_gate_up, w_down, topk_weights, topk_ids, num_experts,
    axis, activation, capacity_factor,
):
    ep = jax.lax.axis_size(axis)
    e_local = w_gate_up.shape[0]
    T, K = topk_ids.shape
    H = hidden.shape[1]
    TK = T * K
    cap, order, sd, stok, eid, within = _route_buckets(
        topk_ids, e_local, ep, capacity_factor
    )

    # capacity-bucketed send buffers; overflow (within >= cap) drops
    send_x = jnp.zeros((ep, cap, H), hidden.dtype).at[sd, within].set(
        hidden[stok], mode="drop"
    )
    send_eid = jnp.zeros((ep, cap), jnp.int32).at[sd, within].set(
        eid, mode="drop"
    )
    send_valid = jnp.zeros((ep, cap), jnp.float32).at[sd, within].set(
        1.0, mode="drop"
    )

    # dispatch: entry j of the received buffer came from rank j
    recv_x = jax.lax.all_to_all(send_x, axis, 0, 0)
    recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0)
    recv_valid = jax.lax.all_to_all(send_valid, axis, 0, 0)

    out = fused_moe(
        recv_x.reshape(ep * cap, H), w_gate_up, w_down,
        recv_valid.reshape(ep * cap, 1),  # weight 1 for valid, 0 for empty
        recv_eid.reshape(ep * cap, 1), e_local, activation,
    )

    # combine: send results back along the same routes
    back = jax.lax.all_to_all(out.reshape(ep, cap, H), axis, 0, 0)
    kept = (within < cap)[:, None].astype(jnp.float32)
    gathered = back[sd, jnp.minimum(within, cap - 1)] * kept  # sorted order
    contrib = jnp.zeros((TK, H), jnp.float32).at[order].set(
        gathered.astype(jnp.float32)
    )
    combined = (
        contrib.reshape(T, K, H)
        * topk_weights.astype(jnp.float32)[..., None]
    ).sum(1)
    dropped = jnp.sum((within >= cap).astype(jnp.int32)).reshape(1)
    return combined.astype(hidden.dtype), dropped


def _fused_moe_ep_alltoall_exact(
    hidden, w_gate_up, w_down, topk_weights, topk_ids, num_experts,
    axis, activation, capacity_factor,
):
    """Exact (no-drop) all_to_all EP dispatch: rounds under a while_loop.

    The reference sizes its dispatch transfer from an exchanged size
    tensor, so every routed token is delivered
    (moe_ep/modes/split_layer.py:52).  XLA cannot size a buffer
    dynamically, so delivery-exactness is bought with TIME instead of
    SHAPE: round ``r`` exchanges the routes whose per-destination rank
    ``within`` falls in ``[r*cap, (r+1)*cap)``, and the loop runs
    ``ceil(max destination load / cap)`` rounds — a traced scalar every
    rank derives from the same ``pmax``, keeping the SPMD program
    uniform.  Every (token, choice) route is exchanged in exactly one
    round, so the combined output is the same weighted sum the
    single-device oracle computes — bit-for-bit in f32 at K=2 (per-route
    expert rows are row-independent dots, and two-addend float sums are
    order-free); at K>2 the K-way addition order can differ from the
    oracle's expert-sorted scatter-add by an ulp.
    """
    ep = jax.lax.axis_size(axis)
    e_local = w_gate_up.shape[0]
    T, K = topk_ids.shape
    H = hidden.shape[1]
    TK = T * K
    cap, order, sd, stok, eid_src, within = _route_buckets(
        topk_ids, e_local, ep, capacity_factor
    )

    # all ranks agree on the round count: ceil(max bucket load / cap)
    counts = jnp.bincount(sd, length=ep)
    rounds = jax.lax.pmax(
        ((counts.max() + cap - 1) // cap).astype(jnp.int32), axis
    )

    x_src = hidden[stok]  # [TK, H] route payloads, sorted order

    def round_body(state):
        r, contrib = state
        lo = r * cap
        in_round = (within >= lo) & (within < lo + cap)
        # routes outside this round park in a spill slot that the final
        # slice discards — keeps the scatter mask-free and in-bounds
        slot = jnp.where(in_round, within - lo, cap)
        send_x = (
            jnp.zeros((ep, cap + 1, H), hidden.dtype)
            .at[sd, slot].set(x_src)[:, :cap]
        )
        send_eid = (
            jnp.zeros((ep, cap + 1), jnp.int32)
            .at[sd, slot].set(eid_src)[:, :cap]
        )
        send_valid = (
            jnp.zeros((ep, cap + 1), jnp.float32)
            .at[sd, slot].set(in_round.astype(jnp.float32))[:, :cap]
        )

        recv_x = jax.lax.all_to_all(send_x, axis, 0, 0)
        recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0)
        recv_valid = jax.lax.all_to_all(send_valid, axis, 0, 0)

        out = fused_moe(
            recv_x.reshape(ep * cap, H), w_gate_up, w_down,
            recv_valid.reshape(ep * cap, 1),  # weight 1 valid, 0 empty
            recv_eid.reshape(ep * cap, 1), e_local, activation,
        )

        back = jax.lax.all_to_all(out.reshape(ep, cap, H), axis, 0, 0)
        got = back[sd, jnp.clip(within - lo, 0, cap - 1)]
        got = got * in_round[:, None].astype(got.dtype)
        return r + 1, contrib + got.astype(jnp.float32)

    _, contrib_sorted = jax.lax.while_loop(
        lambda s: s[0] < rounds,
        round_body,
        (jnp.int32(0), jnp.zeros((TK, H), jnp.float32)),
    )
    contrib = jnp.zeros((TK, H), jnp.float32).at[order].set(contrib_sorted)
    combined = (
        contrib.reshape(T, K, H)
        * topk_weights.astype(jnp.float32)[..., None]
    ).sum(1)
    return combined.astype(hidden.dtype), jnp.zeros((1,), jnp.int32)
