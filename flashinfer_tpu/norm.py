"""RMSNorm family.

TPU-native re-design of the reference norm ops (``flashinfer/norm/``,
``include/flashinfer/norm.cuh:37-686``): ``rmsnorm``, ``fused_add_rmsnorm``,
``gemma_rmsnorm``, ``gemma_fused_add_rmsnorm``, ``layernorm``.

Differences from the CUDA reference, by design:
- Functional semantics: the reference mutates ``input``/``residual`` in place;
  on TPU we return new arrays (XLA donation makes this zero-copy under jit).
- One Pallas kernel serves the whole family (residual add and the Gemma
  ``weight + 1`` convention are closure specializations — the TPU analogue of
  the reference's jinja-specialized kernel instantiations).
- fp32 accumulation regardless of IO dtype, matching norm.cuh behavior.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from flashinfer_tpu.api_logging import flashinfer_api
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flashinfer_tpu.utils import cdiv, resolve_backend, use_interpret

_ROW_BLOCK = 256
# row-block tactic space: bandwidth-bound kernel, the knob trades grid
# parallelism against per-invocation DMA size
_ROW_BLOCK_CANDIDATES = (128, 256, 512, 1024)


_row_block_memo: dict = {}


def _tuned_row_block(n: int, hidden: int, dtype, op: str, runner) -> int:
    """Autotuned Pallas row-block (reference tactic selection analogue);
    shipped-config/default outside an autotune() context.  Resolved values
    are memoized per (op, shape, dtype): rmsnorm is a microsecond-scale op
    called once per layer per step, so the hot path must not pay the
    tuner's lock + key-string + blocklist machinery every call."""
    from flashinfer_tpu.autotuner import AutoTuner

    memo_key = (op, n, hidden, str(dtype))
    tuner = AutoTuner.get()
    if not tuner.tuning_enabled:
        rb = _row_block_memo.get(memo_key)
        if rb is not None:
            return rb
    import flashinfer_tpu.norm as _norm_module

    rb = tuner.choose_one(
        f"{op}.row_block",
        (n, hidden, str(dtype)),
        [c for c in _ROW_BLOCK_CANDIDATES if c <= max(n, 128)],
        runner,
        default=_ROW_BLOCK,
        module=_norm_module,
    )
    rb = _whole_blocks(min(int(rb), n), n)
    _row_block_memo[memo_key] = rb
    return rb


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float, weight_bias: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32) + weight_bias
    o_ref[...] = (y * w[None, :]).astype(o_ref.dtype)


def _fused_add_rms_kernel(
    x_ref, r_ref, w_ref, o_ref, res_ref, *, eps: float, weight_bias: float
):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    res_ref[...] = s.astype(res_ref.dtype)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    y = s * jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32) + weight_bias
    o_ref[...] = (y * w[None, :]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eps", "weight_bias", "backend", "row_block")
)
def _rmsnorm_impl(
    x, weight, eps: float, weight_bias: float, backend: str,
    row_block: Optional[int] = None,
):
    orig_shape = x.shape
    hidden = orig_shape[-1]
    x2 = x.reshape(-1, hidden)
    n = x2.shape[0]
    if backend == "xla" or n < 8:
        xf = x2.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        out = (y * (weight.astype(jnp.float32) + weight_bias)).astype(x.dtype)
        return out.reshape(orig_shape)
    rb = min(row_block or _ROW_BLOCK, n)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps, weight_bias=weight_bias),
        grid=(cdiv(n, rb),),
        in_specs=[
            pl.BlockSpec((rb, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((rb, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, hidden), x.dtype),
        interpret=use_interpret(),
    )(x2, weight)
    return out.reshape(orig_shape)


@functools.partial(
    jax.jit, static_argnames=("eps", "weight_bias", "backend", "row_block")
)
def _fused_add_rmsnorm_impl(
    x, residual, weight, eps, weight_bias, backend,
    row_block: Optional[int] = None,
):
    orig_shape = x.shape
    hidden = orig_shape[-1]
    x2 = x.reshape(-1, hidden)
    r2 = residual.reshape(-1, hidden)
    n = x2.shape[0]
    if backend == "xla" or n < 8:
        s = x2.astype(jnp.float32) + r2.astype(jnp.float32)
        var = jnp.mean(s * s, axis=-1, keepdims=True)
        y = s * jax.lax.rsqrt(var + eps)
        out = (y * (weight.astype(jnp.float32) + weight_bias)).astype(x.dtype)
        return out.reshape(orig_shape), s.astype(residual.dtype).reshape(orig_shape)
    rb = min(row_block or _ROW_BLOCK, n)
    out, res = pl.pallas_call(
        functools.partial(_fused_add_rms_kernel, eps=eps, weight_bias=weight_bias),
        grid=(cdiv(n, rb),),
        in_specs=[
            pl.BlockSpec((rb, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((hidden,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rb, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, hidden), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, hidden), x.dtype),
            jax.ShapeDtypeStruct((n, hidden), residual.dtype),
        ],
        interpret=use_interpret(),
    )(x2, r2, weight)
    return out.reshape(orig_shape), res.reshape(orig_shape)


def _norm_parity_kw(name, out, enable_pdl):
    """Reference-surface kwargs shared by the norm family: ``enable_pdl``
    is a CUDA launch knob (inert on TPU); ``out=`` preallocation is
    loudly rejected (functional arrays + donation, docs/migration.md)."""
    del enable_pdl  # programmatic-dependent-launch: no TPU meaning
    if out is not None:
        raise ValueError(
            f"TPU backend: {name} out= pre-allocated outputs are not "
            "supported (functional arrays; jit donation replaces "
            "preallocation)"
        )


@flashinfer_api
def rmsnorm(
    x: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    out=None,
    enable_pdl=None,
    backend: str = "auto",
) -> jax.Array:
    r"""Root-mean-square normalization: ``out = x / sqrt(mean(x^2)+eps) * w``.

    Reference: ``flashinfer.norm.rmsnorm`` (flashinfer/norm/, norm.cuh:37).
    """
    _norm_parity_kw("rmsnorm", out, enable_pdl)
    be = resolve_backend(backend, "rmsnorm")
    rb = _tuned_row_block(
        x.size // x.shape[-1], x.shape[-1], x.dtype, "rmsnorm",
        lambda c: (lambda: _rmsnorm_impl(x, weight, eps, 0.0, be, c)),
    )
    return _rmsnorm_impl(x, weight, eps, 0.0, be, rb)


@flashinfer_api
def gemma_rmsnorm(
    x: jax.Array, weight: jax.Array, eps: float = 1e-6, out=None,
    enable_pdl=None, backend: str = "auto",
) -> jax.Array:
    """Gemma-style RMSNorm: scales by ``(weight + 1)`` (norm.cuh Gemma family)."""
    _norm_parity_kw("gemma_rmsnorm", out, enable_pdl)
    return _rmsnorm_impl(x, weight, eps, 1.0, resolve_backend(backend, "gemma_rmsnorm"))


@flashinfer_api
def fused_add_rmsnorm(
    x: jax.Array,
    residual: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    enable_pdl=None,
    backend: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Fused residual-add + RMSNorm.

    Returns ``(normed, new_residual)`` where ``new_residual = x + residual``
    — the functional form of the reference's in-place
    ``fused_add_rmsnorm`` (norm.cuh FusedAddRMSNorm).
    """
    _norm_parity_kw("fused_add_rmsnorm", None, enable_pdl)
    be = resolve_backend(backend, "fused_add_rmsnorm")
    rb = _tuned_row_block(
        x.size // x.shape[-1], x.shape[-1], x.dtype, "fused_add_rmsnorm",
        lambda c: (
            lambda: _fused_add_rmsnorm_impl(x, residual, weight, eps, 0.0, be, c)
        ),
    )
    return _fused_add_rmsnorm_impl(x, residual, weight, eps, 0.0, be, rb)


@flashinfer_api
def gemma_fused_add_rmsnorm(
    x: jax.Array,
    residual: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    enable_pdl=None,
    backend: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    _norm_parity_kw("gemma_fused_add_rmsnorm", None, enable_pdl)
    return _fused_add_rmsnorm_impl(
        x, residual, weight, eps, 1.0,
        resolve_backend(backend, "gemma_fused_add_rmsnorm"),
    )


@functools.partial(jax.jit, static_argnames=("eps",))
def layernorm(
    x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5
) -> jax.Array:
    """Plain LayerNorm (reference ``flashinfer/norm/`` layernorm)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def qk_rmsnorm(
    q: jax.Array,  # [..., num_q_heads, head_dim]
    k: jax.Array,  # [..., num_k_heads, head_dim]
    q_weight: jax.Array,  # [head_dim]
    k_weight: jax.Array,  # [head_dim]
    eps: float = 1e-6,
) -> Tuple[jax.Array, jax.Array]:
    """Per-head RMSNorm of q and k over head_dim (reference QK-RMSNorm
    family, flashinfer/norm/ — used by Qwen3/Gemma-style attention)."""

    def _norm(x, w):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(
            x.dtype
        )

    return _norm(q, q_weight), _norm(k, k_weight)


@functools.partial(jax.jit, static_argnames=("eps",))
def rmsnorm_silu(
    x: jax.Array, weight: jax.Array, gate: jax.Array, eps: float = 1e-6
) -> jax.Array:
    """Fused RMSNorm + SiLU gating: ``rmsnorm(x) * silu(gate)`` (reference
    ``csrc/rmsnorm_silu.cu``)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return (y * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def layernorm_scale_shift(
    x: jax.Array,  # [tokens, hidden]
    scale: jax.Array,  # [hidden] or [tokens, hidden] adaLN modulation
    shift: jax.Array,
    eps: float = 1e-6,
) -> jax.Array:
    """DiT adaLN: ``layernorm(x, affine=False) * (1 + scale) + shift``
    (reference DiT layernorm family, flashinfer/norm/)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    t = shift.astype(jnp.float32)
    if s.ndim == 1:
        s, t = s[None], t[None]
    return (y * (1.0 + s) + t).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "quant_dtype"))
def rmsnorm_quant_fp8(
    x: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    quant_dtype=jnp.float8_e4m3fn,
) -> Tuple[jax.Array, jax.Array]:
    """Fused RMSNorm + per-tensor fp8 quantize -> (values, scale)
    (reference quantizing-norm variants, flashinfer/norm/ FP8-out family)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    finfo = jnp.finfo(quant_dtype)
    amax = jnp.max(jnp.abs(y))
    scale = jnp.maximum(amax / float(finfo.max), 1e-12)
    q = jnp.clip(y / scale, float(finfo.min), float(finfo.max)).astype(quant_dtype)
    return q, scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "quant_dtype"))
def fused_add_rmsnorm_quant_fp8(
    x: jax.Array,
    residual: jax.Array,
    weight: jax.Array,
    eps: float = 1e-6,
    quant_dtype=jnp.float8_e4m3fn,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused residual-add + RMSNorm + fp8 quantize -> (values, scale,
    new_residual) — the AR-free half of the reference's
    AllReduceFusionPattern quantizing epilogues."""
    s = x.astype(jnp.float32) + residual.astype(jnp.float32)
    new_residual = s.astype(x.dtype)
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    y = s * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    finfo = jnp.finfo(quant_dtype)
    amax = jnp.max(jnp.abs(y))
    scale = jnp.maximum(amax / float(finfo.max), 1e-12)
    q = jnp.clip(y / scale, float(finfo.min), float(finfo.max)).astype(quant_dtype)
    return q, scale.astype(jnp.float32), new_residual


@jax.jit
def gate_residual(
    residual: jax.Array, gate: jax.Array, x: jax.Array
) -> jax.Array:
    """DiT gated residual add: ``residual + gate * x``."""
    g = gate.astype(jnp.float32)
    if g.ndim == 1:
        g = g[None]
    return (residual.astype(jnp.float32) + g * x.astype(jnp.float32)).astype(
        residual.dtype
    )


def select_knobs(*_, **__):
    """Reference norm.select_knobs picks CUDA launch knobs per shape; the
    TPU row-block choice lives in the autotuner (rmsnorm.row_block
    tactics), so there is nothing to select here."""
    return {}


def _whole_blocks(rb: int, n: int) -> int:
    """``rb``, or where it leaves ``n`` rows a ragged last block, the
    largest smaller candidate that divides them (none: ``rb``).  A ragged
    block takes kernel stack beside the blocks' own buffers: ``[640, 8192]``
    bf16 at 256 rows passes the compiler's VMEM limit inside a step where
    ``[1024, 8192]`` just fits, and a rung is a multiple of 128.  (Last in
    the file so that the kernels above keep the lines their compiled
    programs are cached under.)"""
    if n % rb == 0:
        return rb
    return next((c for c in reversed(_ROW_BLOCK_CANDIDATES)
                 if c < rb and n % c == 0), rb)
