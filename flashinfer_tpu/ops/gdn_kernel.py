"""Fused chunked gated-delta-rule (GDN) prefill Pallas kernel.

TPU re-design of the reference's GDN prefill kernels
(``flashinfer/gdn_kernels/`` — ~30k-LoC Blackwell DSL implementing the
WY/UT-transform chunked form).  The XLA form (``gdn.gdn_chunk_prefill``)
materializes per-chunk [Q, Q] coupling/decay matrices and the solved
write tensors in HBM; this kernel keeps the ENTIRE per-chunk computation
in VMEM — inputs are read once (q/k/v + a tiny per-token scalar slab),
the output written once, and the boundary state rides VMEM scratch across
the sequential chunk sweep:

- grid ``(B, H, nC)`` with the chunk dim innermost/sequential; state
  ``S [dk, dv]`` f32 lives in scratch, seeded from ``initial_state`` at
  ``c == 0`` and emitted at ``c == nC - 1``;
- the decay-ratio matrix ``R[i,j] = exp(min(acum_i - acum_j, 0))`` is
  built in-register from the per-token log-decay cumsum: the column form
  comes straight from the scalar slab, the row form via a contraction
  with the identity (``acum^T @ I`` — sublane->lane move as an MXU dot,
  Mosaic has no lane-dim reshape);
- the unit-lower-triangular solve ``(I + C) U = rhs`` uses the nilpotent
  inverse-by-doubling: with ``N = -C`` strictly lower triangular,
  ``(I - N)^{-1} = sum_{i<Q} N^i`` accumulated in ``log2(Q)`` rounds of
  ``(S, T) <- (S + T @ S, T @ T)`` — 2 MXU matmuls per round, no
  sequential row solve;
- chunk size is 128 so every [Q, Q] matrix is lane-aligned.

**Stability domain**: the doubling inverse materializes the explicit
Neumann series, which is exact-and-stable in the delta rule's operating
regime — normalized keys (QK-norm, as GDN models apply), so the strict
couplings ``beta_i R (k_i . k_j)`` are O(1/sqrt(dk)) off-diagonal and
the series terms decay.  For adversarial unnormalized keys (coupling
magnitudes >> 1 — a regime where the underlying delta-rule recurrence
itself diverges) the intermediate powers ``C^(2^r)`` can overflow f32,
so such callers must pass ``backend="xla"`` for the back-substituting
``solve_triangular`` path.

Validated against the exact recurrence (``gdn.gdn_prefill``) in
interpret mode (5e-7 max err at L=256, nonzero initial state) and on
hardware (2026-07-31 hw tier).  DEFAULT for eligible shapes since the
banked 1.41x win over the XLA form (BENCH_BANKED.md 2026-07-31);
``gdn.gdn_chunk_prefill``'s docstring carries the caller-facing
domain note.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import use_interpret

_CHUNK = 128  # lane-aligned [Q, Q] matrices; log2(Q) = 7 doubling rounds


def eligible(q, v) -> bool:
    """True when (q, v) shapes fit these kernels (the ONE shape
    predicate — dispatchers and bench call it)."""
    return (
        q.shape[1] % _CHUNK == 0
        and q.shape[-1] % 128 == 0
        and v.shape[-1] % 128 == 0
    )


def _masks(Q):
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return (
        (rows > cols).astype(jnp.float32),
        (rows >= cols).astype(jnp.float32),
        (rows == cols).astype(jnp.float32),
    )


def _neumann_inv(C, eye):
    """(I + C)^{-1} for strictly-lower-triangular C via nilpotent
    doubling: S_0 = I, T_0 = -C; (S, T) <- (S + T S, T^2) gives
    S_r = sum_{i < 2^r} (-C)^i — 7 rounds cover Q = 128."""

    def body(_, carry):
        inv, t = carry
        return inv + jax.lax.dot_general(
            t, inv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ), jax.lax.dot_general(
            t, t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    inv, _ = jax.lax.fori_loop(0, 7, body, (eye, -C))
    return inv


def _gdn_chunk_kernel(
    q_ref,  # [Q, dk] input dtype
    k_ref,
    v_ref,  # [Q, dv]
    scal_ref,  # [Q, 8] f32: lane 0 = acum (log-decay cumsum), lane 1 = beta
    init_ref,  # [dk, dv] f32 initial state (read at c == 0)
    o_ref,  # [Q, dv] out (input dtype)
    sfinal_ref,  # [dk, dv] f32 out (written at c == nC - 1)
    s_ref,  # scratch [dk, dv] f32: the carried boundary state
    *,
    num_chunks: int,
):
    c = pl.program_id(2)
    Q = q_ref.shape[0]

    @pl.when(c == 0)
    def _seed():
        s_ref[...] = init_ref[...]

    qf = q_ref[...].astype(jnp.float32)
    kf = k_ref[...].astype(jnp.float32)
    vf = v_ref[...].astype(jnp.float32)
    acum = scal_ref[...][:, 0:1]  # [Q, 1] log D_i
    beta = scal_ref[...][:, 1:2]

    # row-broadcast of acum without a lane reshape: acum^T @ I -> [1, Q]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    ).astype(jnp.float32)
    acum_row = jax.lax.dot_general(
        acum, eye, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        # HIGHEST: carries log-decay exponents — a default bf16 MXU pass
        # rounds them before the exp (see ops/mamba_kernel.row, banked
        # 2026-07-31); Q*Q FLOPs, free
        precision=jax.lax.Precision.HIGHEST,
    )  # [1, Q]
    # R[i, j] = exp(min(acum_i - acum_j, 0)) — the used (lower) triangle
    # has non-positive exponents; the clamp kills upper-triangle overflow
    R = jnp.exp(jnp.minimum(acum - jnp.broadcast_to(acum_row, (Q, Q)), 0.0))

    strict, causal, _ = _masks(Q)

    kk = jax.lax.dot_general(
        kf, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # KK[i, j] = k_i . k_j
    C = strict * beta * R * kk  # [Q(i), Q(j)]
    ainv = _neumann_inv(C, eye)

    D = jnp.exp(acum)  # [Q, 1]
    s0 = s_ref[...]
    uv = jax.lax.dot_general(
        ainv, beta * vf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Q, dv]
    us = jax.lax.dot_general(
        ainv, beta * D * kf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Q, dk]
    u = uv - jax.lax.dot_general(
        us, s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Q, dv]

    qk = jax.lax.dot_general(
        qf, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # qk[i, j] = q_i . k_j
    P = causal * R * qk
    o = jax.lax.dot_general(
        D * qf, s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        P, u, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = o.astype(o_ref.dtype)

    # boundary state: S' = Dtot S + sum_j (Dtot / D_j) k_j u_j^T
    ratio = jnp.exp(
        jnp.broadcast_to(acum[Q - 1 : Q, 0:1], (Q, 1)) - acum
    )  # [Q, 1] = Dtot / D_j  (non-positive exponents: j <= last)
    wk = ratio * kf  # [Q, dk]
    # two-stage broadcast of the [1, 1] Dtot: (1,1)->(dk,1) sublane-only,
    # then the multiply lane-broadcasts against (dk,dv) -- Mosaic has no
    # fused sublane+lane broadcast ("Not implemented: Broadcast in both
    # sublanes and lanes", banked 2026-07-31)
    dtot_col = jnp.exp(
        jnp.broadcast_to(acum[Q - 1 : Q, 0:1], (s0.shape[0], 1))
    )
    s_new = dtot_col * s0 + jax.lax.dot_general(
        wk, u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ref[...] = s_new

    @pl.when(c == num_chunks - 1)
    def _emit():
        sfinal_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def gdn_chunk_prefill_pallas(
    q: jax.Array,  # [B, L, H, dk]
    k: jax.Array,
    v: jax.Array,  # [B, L, H, dv]
    alpha: jax.Array,  # [B, L, H] decay in (0, 1]
    beta: jax.Array,  # [B, L, H]
    initial_state: Optional[jax.Array] = None,  # [B, H, dk, dv]
    chunk_size: int = _CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """Fused GDN chunked prefill -> (o [B, L, H, dv], final [B, H, dk, dv]).

    Requires ``L % chunk_size == 0`` and 128-aligned dk/dv (the model
    dims GDN serves); use ``gdn.gdn_chunk_prefill`` for other shapes."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    Q = chunk_size
    if Q != _CHUNK:
        # the doubling inverse runs exactly log2(128) rounds and the
        # [Q, Q] tiles are lane-aligned only at 128
        raise ValueError(f"gdn pallas kernel supports chunk_size={_CHUNK} "
                         f"only, got {Q}")
    if L % Q or dk % 128 or dv % 128:
        raise ValueError(
            f"gdn pallas kernel needs L % {Q} == 0 and 128-aligned dk/dv, "
            f"got L={L} dk={dk} dv={dv}"
        )
    nC = L // Q
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)

    # [B, H, nC, Q, d] layout: the kernel's (b, h, c) block indexing
    def bh(x, d):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B, H, nC, Q, d)

    qb, kb = bh(q, dk), bh(k, dk)
    vb = bh(v, dv)
    loga = jnp.log(jnp.maximum(alpha.astype(jnp.float32), 1e-30))
    acum = jnp.cumsum(
        jnp.transpose(loga, (0, 2, 1)).reshape(B, H, nC, Q), axis=-1
    )
    scal = jnp.stack(
        [acum, jnp.transpose(beta.astype(jnp.float32), (0, 2, 1))
         .reshape(B, H, nC, Q)],
        axis=-1,
    )  # [B, H, nC, Q, 2]
    scal = jnp.pad(scal, ((0, 0),) * 4 + ((0, 6),))  # lane-pad to 8

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B, H, nC),
        in_specs=[
            pl.BlockSpec((None, None, None, Q, dk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, dk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, dv),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, 8),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, Q, dv),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    o, sfinal = pl.pallas_call(
        functools.partial(_gdn_chunk_kernel, num_chunks=nC),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nC, Q, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=use_interpret(),
    )(qb, kb, vb, scal, initial_state.astype(jnp.float32))
    o = jnp.transpose(o.reshape(B, H, L, dv), (0, 2, 1, 3))
    return o, sfinal


_KDA_SB = 16  # block-row height for the pair-score assembly
# Per-factor exponent clamp.  Sized so the dk-SUMMED masked-garbage dot
# stays finite, not just the per-factor product: worst masked entry is
# sum_c k_i[c] k_j[c] e^{2*CLAMP}, so 2*CLAMP + ln(dk * max|k_i k_j|)
# must stay under f32's ~88.7 — CLAMP=36 leaves ln headroom ~11.8 for
# dk=128 times per-channel key products up to ~250.  Exactness floor:
# alpha >= exp(-2*CLAMP/SB) ~= 0.011.
_KDA_CLAMP = 36.0


def _kda_pair_scores(qf0, kf0, acum, Q, dk):
    """[Q, Q] decay-weighted pair scores for per-channel decay:
    ``A[i, j] = sum_c x_i[c] k_j[c] exp(acum_i[c] - acum_j[c])`` for
    ``x in {k, q}`` (the coupling and attention matrices), assembled from
    ``_KDA_SB``-row blocks so NO factor or masked-garbage entry can
    overflow f32:

    - **history block-pairs** (cols strictly before the row block) factor
      around the block's LEFT BOUNDARY decay: monotone per-channel acum
      puts the boundary between i and j, so BOTH factors are <= 1 — safe
      at ANY decay rate, underflow only where the true value underflows;
    - **diagonal blocks** factor around the block midpoint: true factor
      exponents span <= SB/2 tokens, and a +-``_KDA_CLAMP`` clamp keeps
      the (masked-away) garbage entries finite instead of inf*0 = NaN.

    Exactness domain: per-token per-channel log-decay * SB/2 within the
    clamp, i.e. alpha >= exp(-2*_KDA_CLAMP/_KDA_SB) ~= 0.011 — nearly an order
    of magnitude below the ~0.02 aggressive-decay regime real KDA models
    use (reference kda_kernels/recurrent_kda.py covers the same range by
    never forming cross-token ratios).  Below that, clamped diagonal
    entries degrade gracefully (absolute error <= the true coupling,
    which is itself < e^-40)."""
    SB = _KDA_SB
    rows_kk, rows_qk = [], []
    for b in range(Q // SB):
        sl = slice(b * SB, (b + 1) * SB)
        a_r = acum[sl, :]  # [SB, dk]
        k_r = kf0[sl, :]
        q_r = qf0[sl, :]
        col = jax.lax.broadcasted_iota(jnp.int32, (SB, Q), 1)

        # diagonal block: midpoint reference, clamped factors
        m_d = acum[b * SB + SB // 2 : b * SB + SB // 2 + 1, :]  # [1, dk]
        f_d = jnp.exp(jnp.clip(a_r - m_d, -_KDA_CLAMP, _KDA_CLAMP))
        g_d = jnp.exp(jnp.clip(
            jnp.broadcast_to(m_d, (Q, dk)) - acum, -_KDA_CLAMP, _KDA_CLAMP
        ))
        in_blk = ((col >= b * SB) & (col < (b + 1) * SB)).astype(jnp.float32)
        kg_d = kf0 * g_d
        kk = in_blk * jax.lax.dot_general(
            k_r * f_d, kg_d, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        qk = in_blk * jax.lax.dot_general(
            q_r * f_d, kg_d, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        if b:
            # history: boundary reference -> both factors in [0, 1]
            m_h = acum[b * SB - 1 : b * SB, :]  # [1, dk]
            f_h = jnp.exp(jnp.minimum(a_r - m_h, 0.0))
            g_h = jnp.exp(jnp.minimum(
                jnp.broadcast_to(m_h, (Q, dk)) - acum, 0.0
            ))
            hist = (col < b * SB).astype(jnp.float32)
            kg_h = kf0 * g_h
            kk = kk + hist * jax.lax.dot_general(
                k_r * f_h, kg_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            qk = qk + hist * jax.lax.dot_general(
                q_r * f_h, kg_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        rows_kk.append(kk)
        rows_qk.append(qk)
    return (
        jnp.concatenate(rows_kk, axis=0),
        jnp.concatenate(rows_qk, axis=0),
    )


def _kda_chunk_kernel(
    q_ref,  # [Q, dk]
    k_ref,
    v_ref,  # [Q, dv]
    acum_ref,  # [Q, dk] f32 per-channel log-decay cumsum
    scal_ref,  # [Q, 8] f32: lane 0 = beta
    init_ref,  # [dk, dv] f32
    o_ref,  # [Q, dv]
    sfinal_ref,  # [dk, dv] f32 (last chunk)
    s_ref,  # scratch [dk, dv] f32
    *,
    num_chunks: int,
):
    """KDA: the GDN kernel with PER-CHANNEL decay.  Quadratic couplings
    come from :func:`_kda_pair_scores` — block-row assembly whose
    history factors are one-sided (<= 1, safe at any decay) and whose
    diagonal blocks factor over a 16-token span, so the usable per-token
    decay domain reaches alpha ~0.011 (vs ~0.3 for a whole-chunk
    midpoint factorization).  Reference semantics:
    kda_kernels/recurrent_kda.py."""
    c = pl.program_id(2)
    Q = q_ref.shape[0]
    dk = q_ref.shape[1]

    @pl.when(c == 0)
    def _seed():
        s_ref[...] = init_ref[...]

    qf0 = q_ref[...].astype(jnp.float32)
    kf0 = k_ref[...].astype(jnp.float32)
    vf = v_ref[...].astype(jnp.float32)
    acum = acum_ref[...]
    beta = scal_ref[...][:, 0:1]

    strict, causal, eye = _masks(Q)

    a_kk, a_qk = _kda_pair_scores(qf0, kf0, acum, Q, dk)
    C = strict * beta * a_kk
    ainv = _neumann_inv(C, eye)

    D = jnp.exp(acum)  # [Q, dk] elementwise <= 1
    s0 = s_ref[...]
    uv = jax.lax.dot_general(
        ainv, beta * vf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    us = jax.lax.dot_general(
        ainv, beta * D * kf0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    u = uv - jax.lax.dot_general(
        us, s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    P = causal * a_qk
    o = jax.lax.dot_general(
        D * qf0, s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        P, u, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = o.astype(o_ref.dtype)

    last = acum[Q - 1 : Q, :]  # [1, dk]
    wk = jnp.exp(jnp.broadcast_to(last, (Q, dk)) - acum) * kf0
    # per-channel total decay scales S0 ROWS: diag(Dtot) @ S0 (diagonal
    # built by masking — no lane/sublane transpose exists in Mosaic)
    eye_dk = (
        jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    ).astype(jnp.float32)
    diag_dtot = eye_dk * jnp.exp(jnp.broadcast_to(last, (dk, dk)))
    s_new = jax.lax.dot_general(
        diag_dtot, s0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        wk, u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ref[...] = s_new

    @pl.when(c == num_chunks - 1)
    def _emit():
        sfinal_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def kda_chunk_prefill_pallas(
    q: jax.Array,  # [B, L, H, dk]
    k: jax.Array,
    v: jax.Array,  # [B, L, H, dv]
    alpha: jax.Array,  # [B, L, H, dk] per-channel decay in (0, 1]
    beta: jax.Array,  # [B, L, H]
    initial_state: Optional[jax.Array] = None,
    chunk_size: int = _CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """Fused KDA chunked prefill -> (o, final); per-channel-decay twin of
    :func:`gdn_chunk_prefill_pallas` (same shape gates + stability
    domain, plus the midpoint-factorization decay-range note in the
    kernel docstring)."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    Q = chunk_size
    if Q != _CHUNK:
        raise ValueError(f"kda pallas kernel supports chunk_size={_CHUNK} "
                         f"only, got {Q}")
    if L % Q or dk % 128 or dv % 128:
        raise ValueError(
            f"kda pallas kernel needs L % {Q} == 0 and 128-aligned dk/dv, "
            f"got L={L} dk={dk} dv={dv}"
        )
    nC = L // Q
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)

    def bh(x, d):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(B, H, nC, Q, d)

    loga = jnp.log(jnp.maximum(alpha.astype(jnp.float32), 1e-30))
    acum = jnp.cumsum(bh(loga, dk), axis=3)  # per-chunk, per-channel
    scal = jnp.pad(
        jnp.transpose(beta.astype(jnp.float32), (0, 2, 1))
        .reshape(B, H, nC, Q, 1),
        ((0, 0),) * 4 + ((0, 7),),
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(B, H, nC),
        in_specs=[
            pl.BlockSpec((None, None, None, Q, dk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, dk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, dv),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, dk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, 8),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, Q, dv),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    o, sfinal = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, num_chunks=nC),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nC, Q, dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=use_interpret(),
    )(bh(q, dk), bh(k, dk), bh(v, dv), acum, scal,
      initial_state.astype(jnp.float32))
    o = jnp.transpose(o.reshape(B, H, L, dv), (0, 2, 1, 3))
    return o, sfinal
