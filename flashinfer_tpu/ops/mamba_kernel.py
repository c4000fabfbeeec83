"""Fused chunked Mamba-2 SSD prefill Pallas kernel.

TPU re-design of the reference's SSD chunked-scan kernels
(``flashinfer/mamba/`` combined/chunked scan).  Same shape as the GDN
kernel (``ops/gdn_kernel.py``) minus the triangular solve: the whole
per-chunk computation stays in VMEM — the XLA form
(``mamba.mamba_chunk_scan_combined``) materializes [Q, Q] decay/score
tensors and per-chunk states in HBM; here inputs are read once, the
output written once, and the boundary state ``S [dim, ds]`` rides VMEM
scratch across the sequential chunk sweep:

- grid ``(B, H, nC)``, chunk dim innermost/sequential;
- B/C projections are consumed in their GROUPED layout — the block index
  map computes ``h // rep``, so the head-repeat never materializes;
- per-token scalars (log-decay cumsum, dt) ride a [Q, 8] slab; their row
  forms come from identity contractions (no lane reshape in Mosaic);
- ``scores[i,j] = (C_i . B_j) exp(acum_i - acum_j) dt_j`` on the causal
  triangle, masked INSIDE the exponent (-inf -> 0) so the upper triangle
  stays finite without clamping real causal entries.  The rule and the
  identity contraction are :func:`causal_scores` and :func:`transposed`,
  written once here: ``_ssd_chunk_kernel`` calls them for the library's
  batch form, and the serving engine's slot-addressed launch
  (``ops/ssm_slot_kernel.py``, granite's Mamba-2 layers) for a unit of a
  scheduled segment.

Validated against ``mamba_chunk_scan_combined`` in interpret mode;
opt-in (``backend="pallas"``) until hardware-banked.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import use_interpret

_CHUNK = 128


def eligible(x, B) -> bool:
    """True when (x, B) shapes fit this kernel (the ONE shape predicate —
    the dispatcher and bench both call it)."""
    return (
        x.shape[1] % _CHUNK == 0
        and B.shape[-1] % 128 == 0
        and x.shape[-1] % 8 == 0
        and x.shape[2] % B.shape[2] == 0
    )


def transposed(cols):
    """``[Q, n] -> [n, Q]`` by an identity contraction (no lane reshape in
    Mosaic).  HIGHEST precision: the columns are LOG-DECAY EXPONENTS or
    step sizes -- the default bf16 MXU pass rounds |acum|~128 by up to ~0.5
    absolute, i.e. e^0.5 ~ 65% after the exp (2026-07-31 hw tier: 6% of SSD
    outputs off by up to 2.9).  [Q, n] x [Q, Q] is n*Q*Q FLOPs -- free."""
    Q = cols.shape[0]
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    ).astype(jnp.float32)
    return jax.lax.dot_general(
        cols, eye, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def causal_scores(cb, acum_col, acum_row, dt_row):
    """``scores[i, j] = (C_i . B_j) exp(acum_i - acum_j) dt_j`` on the causal
    triangle, 0 above it.  ``cb [Q, Q]``; ``acum_col [Q, 1]``; ``acum_row``,
    ``dt_row [1, Q]`` (what :func:`transposed` gives).  The mask goes INSIDE
    the exponent (-inf -> exp 0): the upper triangle stays finite without
    clamping real causal entries (dt can be negative with
    dt_softplus=False, making some causal exponents positive).  The one
    copy of this rule: ``_ssd_chunk_kernel`` here and the serving engine's
    slot kernel (``ops/ssm_slot_kernel.py``) both call it."""
    Q = cb.shape[0]
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    decay = jnp.exp(jnp.where(
        causal, acum_col - jnp.broadcast_to(acum_row, (Q, Q)), -jnp.inf))
    return decay * cb * jnp.broadcast_to(dt_row, (Q, Q))


def _ssd_chunk_kernel(
    x_ref,  # [Q, dim] input dtype
    b_ref,  # [Q, ds] (grouped: block index h // rep)
    c_ref,  # [Q, ds]
    scal_ref,  # [Q, 8] f32: lane 0 = acum (log-decay cumsum), lane 1 = dt
    init_ref,  # [dim, ds] f32
    y_ref,  # [Q, dim] out
    sfinal_ref,  # [dim, ds] f32 out (last chunk)
    s_ref,  # scratch [dim, ds] f32
    *,
    num_chunks: int,
):
    c = pl.program_id(2)
    Q = x_ref.shape[0]

    @pl.when(c == 0)
    def _seed():
        s_ref[...] = init_ref[...]

    xf = x_ref[...].astype(jnp.float32)
    bf = b_ref[...].astype(jnp.float32)
    cf = c_ref[...].astype(jnp.float32)
    acum = scal_ref[...][:, 0:1]  # [Q, 1]
    dt = scal_ref[...][:, 1:2]

    cb = jax.lax.dot_general(
        cf, bf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Q(i), Q(j)] = C_i . B_j
    scores = causal_scores(cb, acum, transposed(acum), transposed(dt))

    s0 = s_ref[...]
    # y = scores @ x + exp(acum) * C @ S0^T
    y = jax.lax.dot_general(
        scores, xf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        jnp.exp(acum) * cf, s0, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[...] = y.astype(y_ref.dtype)

    # state: S' = exp(a_total) S0 + sum_j w_j x_j B_j^T,
    # w_j = exp(a_total - acum_j) dt_j   (non-positive exponents)
    a_total = acum[Q - 1 : Q, 0:1]  # [1, 1]
    w = jnp.exp(jnp.broadcast_to(a_total, (Q, 1)) - acum) * dt  # [Q, 1]
    # two-stage broadcast of the [1, 1] total decay: (1,1)->(dim,1) is a
    # sublane-only broadcast and the multiply lane-broadcasts (dim,1)
    # against (dim,ds) -- Mosaic has no fused sublane+lane broadcast
    # ("Not implemented: Broadcast in both sublanes and lanes", banked
    # 2026-07-31)
    dtot_col = jnp.exp(jnp.broadcast_to(a_total, (s0.shape[0], 1)))
    s_new = dtot_col * s0 + jax.lax.dot_general(
        w * xf, bf, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s_ref[...] = s_new

    @pl.when(c == num_chunks - 1)
    def _emit():
        sfinal_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("chunk_size", "dt_softplus"))
def mamba_chunk_scan_pallas(
    x: jax.Array,  # [B, L, H, dim]
    dt: jax.Array,  # [B, L, H]
    A: jax.Array,  # [H] negative decay rates
    B: jax.Array,  # [B, L, G, ds]
    C: jax.Array,  # [B, L, G, ds]
    chunk_size: int = _CHUNK,
    D: Optional[jax.Array] = None,  # [H]
    z: Optional[jax.Array] = None,  # [B, L, H, dim]
    dt_bias: Optional[jax.Array] = None,  # [H]
    dt_softplus: bool = False,
    initial_state: Optional[jax.Array] = None,  # [B, H, dim, ds]
) -> Tuple[jax.Array, jax.Array]:
    """Fused SSD chunked scan -> (y [B, L, H, dim], final [B, H, dim, ds]).

    Requires ``L % 128 == 0``, 128-aligned ``ds``, and 8-aligned ``dim``;
    use ``mamba.mamba_chunk_scan_combined`` for other shapes.  The D
    residual and z gating are applied outside the kernel (elementwise,
    XLA-fused)."""
    Bsz, L, H, dim = x.shape
    G, ds = B.shape[2], B.shape[3]
    Q = chunk_size
    if Q != _CHUNK:
        raise ValueError(f"ssd pallas kernel supports chunk_size={_CHUNK} "
                         f"only, got {Q}")
    if L % Q or ds % 128 or dim % 8 or H % G:
        raise ValueError(
            f"ssd pallas kernel needs L % {Q} == 0, 128-aligned ds, "
            f"8-aligned dim, H % G == 0; got L={L} ds={ds} dim={dim} "
            f"H={H} G={G}"
        )
    rep = H // G
    nC = L // Q
    if initial_state is None:
        initial_state = jnp.zeros((Bsz, H, dim, ds), jnp.float32)

    dtf = dt.astype(jnp.float32)
    if dt_bias is not None:
        dtf = dtf + dt_bias.astype(jnp.float32)[None, None]
    if dt_softplus:
        dtf = jax.nn.softplus(dtf)
    a = dtf * A.astype(jnp.float32)[None, None, :]  # [B, L, H] log-decay
    acum = jnp.cumsum(
        jnp.transpose(a, (0, 2, 1)).reshape(Bsz, H, nC, Q), axis=-1
    )
    scal = jnp.stack(
        [acum,
         jnp.transpose(dtf, (0, 2, 1)).reshape(Bsz, H, nC, Q)], axis=-1
    )
    scal = jnp.pad(scal, ((0, 0),) * 4 + ((0, 6),))  # [B,H,nC,Q,8]

    xb = jnp.transpose(x, (0, 2, 1, 3)).reshape(Bsz, H, nC, Q, dim)
    bb = jnp.transpose(B, (0, 2, 1, 3)).reshape(Bsz, G, nC, Q, ds)
    cb = jnp.transpose(C, (0, 2, 1, 3)).reshape(Bsz, G, nC, Q, ds)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(Bsz, H, nC),
        in_specs=[
            pl.BlockSpec((None, None, None, Q, dim),
                         lambda b, h, c: (b, h, c, 0, 0)),
            # grouped B/C: the index map folds the head repeat
            pl.BlockSpec((None, None, None, Q, ds),
                         lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, ds),
                         lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((None, None, None, Q, 8),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, dim, ds),
                         lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, Q, dim),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, dim, ds),
                         lambda b, h, c: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((dim, ds), jnp.float32)],
    )
    y, sfinal = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, num_chunks=nC),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, nC, Q, dim), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, dim, ds), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=use_interpret(),
    )(xb, bb, cb, scal, initial_state.astype(jnp.float32))
    y = jnp.transpose(y.reshape(Bsz, H, L, dim), (0, 2, 1, 3))
    yf = y.astype(jnp.float32)
    if D is not None:
        yf = yf + D.astype(jnp.float32)[None, None, :, None] * x.astype(
            jnp.float32
        )
    if z is not None:
        yf = yf * jax.nn.silu(z.astype(jnp.float32))
    return yf.astype(x.dtype), sfinal
