"""Shared utilities: enums, layouts, rounding, backend gating.

TPU-native re-design of the reference's ``flashinfer/utils.py`` (enums and
layout canonicalization at utils.py:281, backend gating decorators at
utils.py:1070-1153).  Nothing CUDA-specific survives: "compute capability"
gates become TPU-generation gates, and torch custom-op registration is
unnecessary (jit/abstract-eval come free with JAX).
"""

from __future__ import annotations

import enum
import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp


class PosEncodingMode(enum.IntEnum):
    """Positional encoding applied inside attention kernels.

    Mirrors the reference enum (``flashinfer/utils.py:281``)."""

    NONE = 0
    ROPE_LLAMA = 1
    ALIBI = 2


class MaskMode(enum.IntEnum):
    """Attention mask mode (reference ``flashinfer/utils.py``)."""

    NON_CAUSAL = 0
    CAUSAL = 1
    CUSTOM = 2


class TensorLayout(enum.IntEnum):
    """KV tensor layout: NHD = [seq, heads, dim], HND = [heads, seq, dim]."""

    NHD = 0
    HND = 1


def atomic_write_text(path, text: str) -> None:
    """Write-then-rename so concurrent readers of shared cache files
    (autotuner tactics, quarantine list, compile-status registry) never see
    a torn write — the TPU-side analogue of the reference's compile-cache
    race protections (tests/utils/test_load_cubin_compile_race_condition.py)."""
    import os
    import tempfile
    from pathlib import Path

    import contextlib

    import time

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    # opportunistic sweep: a SIGKILL between mkstemp and os.replace leaks
    # the temp file; age-gate so a concurrent writer's live temp survives
    with contextlib.suppress(OSError):
        cutoff = time.time() - 3600.0
        for stale in path.parent.glob(path.name + ".tmp*"):
            with contextlib.suppress(OSError):
                if stale.stat().st_mtime < cutoff:
                    stale.unlink()
    fd, tmp = tempfile.mkstemp(dir=path.parent, prefix=path.name + ".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            fh.write(text)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def check_kv_layout(kv_layout: str) -> TensorLayout:
    if kv_layout not in ("NHD", "HND"):
        raise KeyError(f"Invalid kv_layout {kv_layout!r}, expected 'NHD' or 'HND'")
    return TensorLayout[kv_layout]


def check_pos_encoding_mode(pos_encoding_mode: str) -> PosEncodingMode:
    if pos_encoding_mode not in PosEncodingMode.__members__:
        raise KeyError(
            f"Invalid pos_encoding_mode {pos_encoding_mode!r}, expected one of "
            f"{list(PosEncodingMode.__members__)}"
        )
    return PosEncodingMode[pos_encoding_mode]


# ---------------------------------------------------------------------------
# Rounding / shape helpers
# ---------------------------------------------------------------------------


def cdiv(a: int, b: int) -> int:
    """Ceiling division."""
    return -(a // -b)


def round_up(a: int, b: int) -> int:
    """Round ``a`` up to a multiple of ``b``."""
    return cdiv(a, b) * b


def next_power_of_two(x: int) -> int:
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


def min_sublane(dtype: Any) -> int:
    """Minimum second-to-last tile dim for a dtype on TPU (lane dim is 128)."""
    itemsize = jnp.dtype(dtype).itemsize
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


LANE = 128


# ---------------------------------------------------------------------------
# Platform / backend gating
# ---------------------------------------------------------------------------


@functools.cache
def is_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.cache
def tpu_generation() -> int:
    """TPU generation number (4, 5, 6, ...); -1 when not running on TPU.

    The TPU analogue of the reference's compute-capability gates
    (``flashinfer/utils.py:1070``)."""
    if not is_tpu():
        return -1
    kind = jax.devices()[0].device_kind.lower()
    for tok in kind.replace("v", " v").split():
        if tok.startswith("v") and tok[1:2].isdigit():
            return int(tok[1])
    return 4


def use_interpret() -> bool:
    """Whether Pallas kernels should run in interpreter mode.

    True off-TPU (CPU CI — the stand-in for the reference's fake backends,
    SURVEY §4) or when FLASHINFER_TPU_INTERPRET=1."""
    from flashinfer_tpu import env

    return env.force_interpret() or not is_tpu()


# Reference attention-backend names (``flashinfer/utils.py:522``
# determine_attention_backend picks fa2/fa3/trtllm-gen/... per CUDA arch;
# wrapper ctors accept them verbatim, e.g. mla/_core.py:1397 backend=).
# They select CUDA codegen variants with no TPU meaning and are
# numerics-neutral, so a verbatim reference call resolves like "auto" —
# the north-star contract of a tpu backend registered alongside the
# reference's backend names.
_REFERENCE_BACKEND_NAMES = frozenset({
    "fa2", "fa3", "fa2_tc", "trtllm-gen", "trtllm-gen-native", "trtllm",
    "cutlass", "cudnn", "xqa", "cute-dsl", "cute_dsl", "tpu",
})


def normalize_backend(backend: str) -> str:
    """Map reference CUDA backend names to "auto"; leave TPU-native
    choices ("auto"/"pallas"/"xla"/"pallas_fused") untouched."""
    if isinstance(backend, str) and backend.lower() in _REFERENCE_BACKEND_NAMES:
        return "auto"
    return backend


def resolve_backend(backend: str, op: str = "") -> str:
    """Resolve a per-op backend choice, honoring the global override.

    Mirrors the reference's ``determine_attention_backend``
    (``flashinfer/utils.py:522``) collapsed to the TPU world: "pallas"
    (primary, Mosaic kernels) or "xla" (pure-jnp reference/fallback).
    Reference backend names (fa2/fa3/trtllm-gen/...) are accepted and
    resolve like "auto".
    """
    from flashinfer_tpu import env

    backend = normalize_backend(backend)
    override = env.backend_override()
    if backend == "auto":
        if override != "auto":
            return override
        # off-TPU, interpret-mode Pallas is a debugger, not a backend:
        # auto picks the compiled XLA path there
        return "pallas" if is_tpu() else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"Unknown backend {backend!r} for op {op or '<unnamed>'}")
    return backend


class GenerationRequirementError(RuntimeError):
    pass


def tpu_requirement(min_generation: int) -> Callable:
    """Declarative hardware gate, mirroring ``@supported_compute_capability``
    (``flashinfer/utils.py:1070``): raises unless running on TPU >= gen or
    off-TPU (interpret/testing mode)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if is_tpu() and tpu_generation() < min_generation:
                raise GenerationRequirementError(
                    f"{fn.__name__} requires TPU v{min_generation}+, "
                    f"running on v{tpu_generation()}"
                )
            return wrapper.__wrapped__(*args, **kwargs)

        return wrapper

    return deco


# ---------------------------------------------------------------------------
# dtype helpers
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "half": jnp.float16,
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "fp8_e4m3": jnp.float8_e4m3fn,
    "fp8_e5m2": jnp.float8_e5m2,
    "int8": jnp.int8,
    "int32": jnp.int32,
}


def canonicalize_dtype(dtype: Any) -> jnp.dtype:
    """Canonicalize a dtype spec (string alias or jnp dtype) to jnp.dtype.

    Reference: ``flashinfer/utils.py`` dtype canonicalization."""
    if isinstance(dtype, str):
        if dtype not in _DTYPE_ALIASES:
            raise KeyError(f"Unknown dtype alias {dtype!r}")
        return jnp.dtype(_DTYPE_ALIASES[dtype])
    return jnp.dtype(dtype)


def reject_unsupported(name: str, **kw) -> None:
    """Shared loud-rejection helper for reference-surface adapters: any
    kwarg that arrived non-None/non-False names a semantic this backend
    does not implement — never silently dropped.  (compat_calls.py keeps
    its own numerics-specific variant with a richer message; both exist
    to enforce the same no-silent-drops policy.)"""
    for k, v in kw.items():
        if v is not None and v is not False:
            raise ValueError(
                f"TPU backend: {name} does not implement {k}; see the "
                "docstring for the supported surface and alternatives"
            )


def fold_scalar_scale(x, name: str) -> Optional[float]:
    """Fold a float-or-single-element-tensor scale to a Python float;
    non-scalar tensors (per-head / per-block fp8 scale factors) are a
    different numerics regime and are rejected loudly.  Shared by the
    pre-compiled attention entries (aliases.py) and
    single_prefill_with_kv_cache's reference scale kwargs."""
    if x is None:
        return None
    if isinstance(x, (int, float)):
        return float(x)
    import numpy as np

    arr = np.asarray(x)
    if arr.size != 1:
        raise ValueError(
            f"TPU backend: {name} must be a float or single-element "
            f"tensor; got shape {arr.shape}. Per-head/per-block scale "
            "factors are not folded here — dequantize the cache "
            "explicitly or use the fp8/int8 decode path "
            "(BatchDecodeWithPagedKVCacheWrapper kv dtypes)"
        )
    return float(arr.reshape(()))


def get_sm_scale(head_dim: int, sm_scale: Optional[float]) -> float:
    return sm_scale if sm_scale is not None else 1.0 / float(head_dim) ** 0.5


def to_nhd(x: jax.Array, kv_layout: str) -> jax.Array:
    """Convert a [.., H, N, D] ("HND") array to [.., N, H, D] ("NHD")."""
    if check_kv_layout(kv_layout) == TensorLayout.HND:
        return jnp.swapaxes(x, -3, -2)
    return x


# the NHD<->HND swap is an involution, so the inverse is the same transform
from_nhd = to_nhd


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------


def get_seq_lens(
    kv_indptr: jax.Array, kv_last_page_len: jax.Array, page_size: int
) -> jax.Array:
    """Per-request KV sequence lengths from paged indptr + last-page lengths.

    Reference: ``flashinfer/page.py`` ``get_seq_lens``."""
    pages = kv_indptr[1:] - kv_indptr[:-1]
    return jnp.where(
        pages > 0, (pages - 1) * page_size + kv_last_page_len, jnp.zeros_like(pages)
    )


def expand_dims_to(x: jax.Array, ndim: int) -> jax.Array:
    while x.ndim < ndim:
        x = x[..., None]
    return x


# ---------------------------------------------------------------------------
# Reference utils.py surface (flashinfer/utils.py): small helpers, enums,
# error classes, and the hardware/backend predicate family mapped to TPU
# truth.  test_compat_surface.py machine-checks these names.
# ---------------------------------------------------------------------------

import enum as _enum
import logging as _logging
import math as _math


class LogLevel(_enum.IntEnum):
    """Reference logging levels (utils.py LogLevel)."""

    DEBUG = _logging.DEBUG
    INFO = _logging.INFO
    WARNING = _logging.WARNING
    ERROR = _logging.ERROR


def set_log_level(level) -> None:
    """Set the library logger level (reference set_log_level)."""
    if isinstance(level, str):
        level = getattr(_logging, level.upper())
    _logging.getLogger("flashinfer_tpu").setLevel(int(level))


def get_logging_module():
    return _logging.getLogger("flashinfer_tpu")


class LibraryError(RuntimeError):
    """Base library error (reference LibraryError)."""


class BackendSupportedError(LibraryError):
    """Requested backend unsupported on this hardware."""


class GPUArchitectureError(BackendSupportedError):
    """Reference name; on TPU raised when a CUDA-only path is requested."""


def ceil_div(a: int, b: int) -> int:
    return cdiv(a, b)


def last_positive_power_of_2(x: int) -> int:
    """Largest power of two <= x (reference utils.py:129)."""
    n = next_power_of_two(x)
    return n if n == x else n // 2


def get_indptr(lens):
    """Lengths -> exclusive-prefix indptr (reference get_indptr)."""
    import numpy as _np

    lens = _np.asarray(lens, _np.int64)
    out = _np.zeros(len(lens) + 1, _np.int64)
    out[1:] = _np.cumsum(lens)
    return out


def get_alibi_slopes(n_heads: int, device=None):
    """ALiBi slope vector (reference utils.py:209, same recurrence)."""
    import numpy as _np

    n = 2 ** int(_math.floor(_math.log2(n_heads)))
    m = (2.0 ** (-8.0 / n)) ** _np.arange(1, 1 + n, dtype=_np.float64)
    if n < n_heads:
        m_hat = (2.0 ** (-4.0 / n)) ** _np.arange(
            1, 1 + 2 * (n_heads - n), 2, dtype=_np.float64
        )
        m = _np.concatenate([m, m_hat])
    import jax.numpy as _jnp

    return _jnp.asarray(m, _jnp.float32)


def calculate_tile_tokens_dim(
    num_tokens: int, num_experts: int, top_k: int,
    max_tile_tokens_dim: int = 128,
) -> int:
    """Expert-imbalance-aware tile size for grouped MoE GEMMs (reference
    utils.py:141 heuristic, used to pick the gmm m-tile)."""
    imbalance = 3 if num_tokens * top_k > num_experts else 1
    per_expert = cdiv(num_tokens * top_k, num_experts) * imbalance
    return min(max(next_power_of_two(max(per_expert, 8)), 8),
               max_tile_tokens_dim)


def version_at_least(version: str, base_version: str) -> bool:
    import re as _re

    def parse(v):
        # "2.6.0a0+git1234" -> (2, 6, 0): leading digits of each of the
        # first three dot components (pre-release suffixes compare equal
        # to their base, a fine approximation for gating)
        parts = []
        for p in v.split("+")[0].split(".")[:3]:
            m = _re.match(r"\d+", p)
            parts.append(int(m.group()) if m else 0)
        return tuple(parts)

    return parse(version) >= parse(base_version)


def is_float8(x) -> bool:
    import jax.numpy as _jnp

    return x.dtype in (_jnp.float8_e4m3fn, _jnp.float8_e5m2)


def get_native_fp4_dtype():
    """TPU has no native fp4 dtype; the storage form is packed int8
    nibbles (quantization.quantize_fp4)."""
    import jax.numpy as _jnp

    return _jnp.int8


class FP4Tensor:
    """Packed-fp4 carrier (reference utils.py:900): ``data`` holds two
    4-bit values per int8 along the last dim, ``scale`` the block scales
    (this library's quantize_fp4 output pair)."""

    def __init__(self, data, scale, scale_start_index: int = 0,
                 original_shape=None):
        self.data = data
        self.scale = scale
        self.scale_start_index = scale_start_index
        self.original_shape = original_shape or (
            *data.shape[:-1], data.shape[-1] * 2
        )

    def dequantize(self, block_size: int = 16):
        from flashinfer_tpu.quantization import dequantize_fp4

        return dequantize_fp4(self.data, self.scale, block_size)


# --- hardware/backend predicates: TPU truth for CUDA-world questions ---

def get_compute_capability(device=None):
    """No CUDA compute capability on TPU; returns (0, 0) so reference
    callers' >= checks route away from SM-gated paths."""
    return (0, 0)


def get_device_index(device=None) -> int:
    import jax

    return 0 if device is None else jax.devices().index(device)


def get_device_sm_count(device=None) -> int:
    """Closest TPU analogue: one MXU-owning core per chip (v5e)."""
    return 1


def get_gpu_memory_bandwidth(device=None) -> float:
    """HBM peak in GB/s for the attached chip (reference queries CUDA;
    here obs/hwspec.py's table is the one source, and a TPU that is not
    in it raises)."""
    from flashinfer_tpu.obs import hwspec

    return hwspec.current_spec().hbm_tbps * 1000.0


def get_cuda_python_version():
    return None  # no CUDA runtime in this build


def has_cuda_cudart() -> bool:
    return False


def is_confidential_compute() -> bool:
    return False


def device_support_pdl(device=None) -> bool:
    return False  # programmatic dependent launch is a CUDA concept


def _cuda_backend_predicate(*_, **__) -> bool:
    """CUDA-arch gates are uniformly False on TPU; resolve_backend picks
    between 'pallas' and 'xla' instead."""
    return False


is_sm90a_supported = _cuda_backend_predicate
is_sm100a_supported = _cuda_backend_predicate
is_sm100f_supported = _cuda_backend_predicate
is_sm110a_supported = _cuda_backend_predicate
is_sm120a_supported = _cuda_backend_predicate
is_sm120f_supported = _cuda_backend_predicate
is_sm121a_supported = _cuda_backend_predicate
is_sm12x_supported = _cuda_backend_predicate
is_fa3_backend_supported = _cuda_backend_predicate
is_fa3_prefill_head_dim_supported = _cuda_backend_predicate
is_cutlass_backend_supported = _cuda_backend_predicate
is_cvt_rs_supported = _cuda_backend_predicate


def supported_compute_capability(*_, **__):
    """Decorator form in the reference (gates ops on SM version); here a
    pass-through — TPU gating happens in resolve_backend."""
    def deco(fn):
        return fn

    return deco


def backend_requirement(*_, **__):
    def deco(fn):
        return fn

    return deco


def determine_attention_backend(*_, **__) -> str:
    return "pallas" if is_tpu() else "xla"


def determine_gemm_backend(*_, **__) -> str:
    return "xla"  # XLA's MXU emitter is the GEMM backend


def determine_mla_backend(*_, **__) -> str:
    return "pallas" if is_tpu() else "xla"


def canonicalize_torch_dtype(dtype):
    """Map a torch-style dtype (or its string) to the jnp equivalent."""
    return canonicalize_dtype(dtype)


def check_shape_dtype_device(x, shape=None, dtype=None, device=None,
                             name: str = "tensor") -> None:
    if shape is not None and tuple(x.shape) != tuple(shape):
        raise ValueError(f"{name}: shape {x.shape} != {shape}")
    if dtype is not None and x.dtype != dtype:
        raise ValueError(f"{name}: dtype {x.dtype} != {dtype}")


def get_default_generators():
    """JAX randomness is explicit keys; no global generators exist."""
    return {}


# CUDA-kernel-layout helpers: identity/zero on TPU (XLA owns layout)
def get_shuffle_block_size(*_, **__) -> int:
    return 1


def get_shuffle_matrix_a_row_indices(w, *_, **__):
    import jax.numpy as _jnp

    return _jnp.arange(w.shape[0], dtype=_jnp.int32)


def get_shuffle_matrix_sf_a_row_indices(s, *_, **__):
    import jax.numpy as _jnp

    return _jnp.arange(s.shape[0], dtype=_jnp.int32)


def get_trtllm_gen_multi_ctas_kv_counter_bytes(*_, **__) -> int:
    return 0  # CTA coordination buffers do not exist on TPU


def get_shared_bytes_per_block_optin(*_, **__) -> int:
    return 0


def get_globaltimer_kernel(*_, **__):
    raise GPUArchitectureError(
        "globaltimer is a CUDA device intrinsic; use jax.profiler / the "
        "op timeline (flashinfer_tpu.profiler) on TPU"
    )


def prepare_jit_additional_args(*_, **__):
    return {}


# reference numeric/workspace constants (decode.py imports): the CUDA
# kernels compute softmax in base-2 (log2e folds into the scale) and
# allocate a fixed single-kernel scratch; TPU kernels use natural log and
# XLA owns scratch, so these exist for import parity and host-side math
log2e = 1.44269504088896340736
SINGLE_KERNEL_TMP_SIZE = 0


def determine_attention_backend(*_, **__) -> str:
    """Reference picks fa2/fa3/trtllm per arch; one answer here."""
    return "pallas"


class FP4Tensor:
    """Packed-fp4 tensor record (reference utils.FP4Tensor): data is the
    block-int4 packed array, scale the per-block f32 scales."""

    def __init__(self, data, scale, original_shape=None):
        self.data = data
        self.scale = scale
        self.original_shape = original_shape or getattr(data, "shape", None)
