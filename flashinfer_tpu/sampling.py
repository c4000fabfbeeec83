"""Sampling / renorm / mask ops and chain speculative sampling.

TPU-native re-design of the reference sampling family
(``flashinfer/sampling.py:737-1980``, ``include/flashinfer/sampling.cuh``).

API mapping notes:
- JAX is functional: every sampling op takes an explicit PRNG ``key`` instead
  of the reference's implicit ``generator``/``philox`` state.
- The reference's sorting-free dual-pivot rejection kernels
  (sampling.cuh:293-1519) exist to avoid GPU-global sorts.  The TPU
  equivalent is the single-HBM-pass VMEM-resident threshold-bisection
  kernel (``ops/sampling_kernels.py``) — the default (``backend="pallas"``)
  for the renorm/mask/filter family on TPU.  The sort-based XLA forms
  remain as the ``backend="xla"`` oracle.  Sampling itself is
  Gumbel-argmax (``jax.random.categorical``) — already sort-free.
  fp32 throughout.
- Threshold tie semantics: like the reference kernels, *all* tokens tied
  at the cut value are kept (a sort's arbitrary tie-cut differs only on
  exactly-equal probabilities).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from flashinfer_tpu.api_logging import flashinfer_api
from flashinfer_tpu.utils import resolve_backend

# plain float, not jnp.float32(): a module-level jnp scalar dispatches a
# device op at import time, which initializes the backend (importing a
# module should start nothing)
_NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=())
def softmax(
    logits: jax.Array, temperature: Optional[jax.Array] = None
) -> jax.Array:
    """Temperature-scaled softmax (reference ``flashinfer.sampling.softmax``)."""
    x = logits.astype(jnp.float32)
    if temperature is not None:
        t = jnp.asarray(temperature, jnp.float32)
        t = jnp.maximum(t, 1e-6)
        if t.ndim == 1:
            t = t[:, None]
        x = x / t
    return jax.nn.softmax(x, axis=-1)


@flashinfer_api
def sampling_from_probs(
    probs: jax.Array,  # [batch, vocab]
    key: jax.Array,
    indices: Optional[jax.Array] = None,
    deterministic: bool = True,  # parity arg; TPU sampling is deterministic per key
) -> jax.Array:
    """Categorical sampling from probabilities (reference
    ``sampling_from_probs``, sampling.py:737). ``indices`` selects a probs row
    per output (for shared distributions)."""
    if indices is not None:
        probs = probs[indices]
    logp = jnp.log(jnp.maximum(probs.astype(jnp.float32), 1e-30))
    return jax.random.categorical(key, logp, axis=-1).astype(jnp.int32)


@flashinfer_api
def sampling_from_logits(
    logits: jax.Array, key: jax.Array, indices: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    if indices is not None:
        logits = logits[indices]
    return jax.random.categorical(key, logits.astype(jnp.float32), axis=-1).astype(
        jnp.int32
    )


# ---------------------------------------------------------------------------
# Renorm / mask family
# ---------------------------------------------------------------------------


def _as_batch_param(p, batch: int) -> jax.Array:
    p = jnp.asarray(p)
    if p.ndim == 0:
        p = jnp.broadcast_to(p, (batch,))
    return p


def top_p_renorm_probs(probs: jax.Array, top_p, backend: str = "auto") -> jax.Array:
    """Renormalize to the smallest threshold set of probs whose mass
    reaches ``top_p``; everything else zeroed (reference
    ``top_p_renorm_probs``)."""
    if resolve_backend(backend, "top_p_renorm_probs") == "pallas":
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        tp = _as_batch_param(top_p, probs.shape[0]).astype(jnp.float32)
        return threshold_select(probs, tp, tp, mode="top_p")
    return _top_p_renorm_probs_xla(probs, top_p)


@jax.jit
def _top_p_renorm_probs_xla(probs: jax.Array, top_p) -> jax.Array:
    p = probs.astype(jnp.float32)
    tp = _as_batch_param(top_p, p.shape[0]).astype(jnp.float32)[:, None]
    sorted_p = jnp.sort(p, axis=-1)[:, ::-1]
    cum = jnp.cumsum(sorted_p, axis=-1)
    # keep entries whose preceding cumulative mass is < top_p; always keep
    # the top-1 token (top_p=0 means greedy, matching the reference kernels)
    rank0 = jnp.arange(p.shape[-1])[None, :] == 0
    keep_sorted = ((cum - sorted_p) < tp) | rank0
    # threshold = smallest kept probability
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_p, jnp.inf), axis=-1, keepdims=True
    )
    kept = jnp.where(p >= thresh, p, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


def top_k_renorm_probs(probs: jax.Array, top_k, backend: str = "auto") -> jax.Array:
    """Keep the top-k probs and renormalize (reference ``top_k_renorm_probs``)."""
    if resolve_backend(backend, "top_k_renorm_probs") == "pallas":
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        k = _as_batch_param(top_k, probs.shape[0]).astype(jnp.float32)
        return threshold_select(probs, k, k, mode="top_k")
    return _top_k_renorm_probs_xla(probs, top_k)


@jax.jit
def _top_k_renorm_probs_xla(probs: jax.Array, top_k) -> jax.Array:
    p = probs.astype(jnp.float32)
    batch, vocab = p.shape
    k = _as_batch_param(top_k, batch).astype(jnp.int32)
    sorted_p = jnp.sort(p, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_p, jnp.clip(k[:, None] - 1, 0, vocab - 1), axis=-1
    )
    kept = jnp.where(p >= kth, p, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


def top_k_mask_logits(logits: jax.Array, top_k, backend: str = "auto") -> jax.Array:
    """Mask all but the top-k logits to -inf (reference ``top_k_mask_logits``)."""
    if resolve_backend(backend, "top_k_mask_logits") == "pallas":
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        k = _as_batch_param(top_k, logits.shape[0]).astype(jnp.float32)
        return threshold_select(logits, k, k, mode="top_k_logits")
    return _top_k_mask_logits_xla(logits, top_k)


@jax.jit
def _top_k_mask_logits_xla(logits: jax.Array, top_k) -> jax.Array:
    x = logits.astype(jnp.float32)
    batch, vocab = x.shape
    k = _as_batch_param(top_k, batch).astype(jnp.int32)
    sorted_x = jnp.sort(x, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_x, jnp.clip(k[:, None] - 1, 0, vocab - 1), axis=-1
    )
    return jnp.where(x >= kth, x, _NEG_INF)


# ---------------------------------------------------------------------------
# Filtered sampling
# ---------------------------------------------------------------------------


@flashinfer_api
def top_p_sampling_from_probs(
    probs: jax.Array, key: jax.Array, top_p, indices: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    if indices is not None:
        probs = probs[indices]
    return sampling_from_probs(top_p_renorm_probs(probs, top_p), key)


@flashinfer_api
def top_k_sampling_from_probs(
    probs: jax.Array, key: jax.Array, top_k, indices: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    if indices is not None:
        probs = probs[indices]
    return sampling_from_probs(top_k_renorm_probs(probs, top_k), key)


@flashinfer_api
def min_p_sampling_from_probs(
    probs: jax.Array, key: jax.Array, min_p, indices: Optional[jax.Array] = None,
    deterministic: bool = True,
) -> jax.Array:
    """Sample keeping tokens with ``p >= min_p * max(p)`` (reference
    ``min_p_sampling_from_probs``)."""
    if indices is not None:
        probs = probs[indices]
    p = probs.astype(jnp.float32)
    mp = _as_batch_param(min_p, p.shape[0]).astype(jnp.float32)[:, None]
    thresh = mp * jnp.max(p, axis=-1, keepdims=True)
    kept = jnp.where(p >= thresh, p, 0.0)
    kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return sampling_from_probs(kept, key)


def _top_k_top_p_filter(probs: jax.Array, top_k, top_p, joint: bool) -> jax.Array:
    """Apply top-k and top-p filters.

    ``joint=False`` ("top_k_first", reference default): top-k renorm first,
    then top-p measured on the *renormalized* distribution.  ``joint=True``:
    both filters measured on the original distribution (reference
    flashinfer/sampling.py joint branch).  On TPU this runs the
    single-pass threshold kernel; off-TPU the one-shared-sort XLA form.
    """
    if resolve_backend("auto", "top_k_top_p_filter") == "pallas":
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        batch = probs.shape[0]
        k = _as_batch_param(top_k, batch).astype(jnp.float32)
        tp = _as_batch_param(top_p, batch).astype(jnp.float32)
        mode = "top_k_top_p_joint" if joint else "top_k_top_p_seq"
        return threshold_select(probs, k, tp, mode=mode)
    return _top_k_top_p_filter_xla(probs, top_k, top_p, joint)


@functools.partial(jax.jit, static_argnames=("joint",))
def _top_k_top_p_filter_xla(probs, top_k, top_p, joint: bool) -> jax.Array:
    p = probs.astype(jnp.float32)
    batch, vocab = p.shape
    k = _as_batch_param(top_k, batch).astype(jnp.int32)[:, None]
    tp = _as_batch_param(top_p, batch).astype(jnp.float32)[:, None]
    sorted_p = jnp.sort(p, axis=-1)[:, ::-1]
    rank = jnp.arange(vocab)[None, :]
    # always keep at least the top-1 token (top_k=0 / top_p=0 mean greedy)
    topk_mask_sorted = (rank < k) | (rank == 0)
    cum = jnp.cumsum(sorted_p, axis=-1)
    if joint:
        topp_mask_sorted = ((cum - sorted_p) < tp) | (rank == 0)
    else:
        topk_mass = jnp.sum(jnp.where(topk_mask_sorted, sorted_p, 0.0), axis=-1,
                            keepdims=True)
        cum_renormed = jnp.cumsum(
            jnp.where(topk_mask_sorted, sorted_p, 0.0), axis=-1
        ) / jnp.maximum(topk_mass, 1e-30)
        topp_mask_sorted = (
            (cum_renormed - sorted_p / jnp.maximum(topk_mass, 1e-30)) < tp
        ) | (rank == 0)
    keep_sorted = topk_mask_sorted & topp_mask_sorted
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_p, jnp.inf), axis=-1, keepdims=True
    )
    kept = jnp.where(p >= thresh, p, 0.0)
    return kept / jnp.sum(kept, axis=-1, keepdims=True)


def _check_filter_order(filter_apply_order: str) -> bool:
    if filter_apply_order not in ("top_k_first", "joint"):
        raise ValueError(
            f"unknown filter_apply_order {filter_apply_order!r}, "
            "expected 'top_k_first' or 'joint'"
        )
    return filter_apply_order == "joint"


@flashinfer_api
def top_k_top_p_sampling_from_probs(
    probs: jax.Array, key: jax.Array, top_k, top_p,
    indices: Optional[jax.Array] = None, deterministic: bool = True,
    filter_apply_order: str = "top_k_first",
) -> jax.Array:
    joint = _check_filter_order(filter_apply_order)
    if indices is not None:
        probs = probs[indices]
    return sampling_from_probs(_top_k_top_p_filter(probs, top_k, top_p, joint), key)


def top_k_top_p_sampling_from_logits(
    logits: jax.Array, key: jax.Array, top_k, top_p,
    indices: Optional[jax.Array] = None, deterministic: bool = True,
    filter_apply_order: str = "top_k_first",
) -> jax.Array:
    joint = _check_filter_order(filter_apply_order)
    if indices is not None:
        logits = logits[indices]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return sampling_from_probs(_top_k_top_p_filter(probs, top_k, top_p, joint), key)


# ---------------------------------------------------------------------------
# Chain speculative sampling
# ---------------------------------------------------------------------------


@jax.jit
def chain_speculative_sampling(
    draft_probs: jax.Array,  # [batch, num_spec, vocab]
    draft_token_ids: jax.Array,  # [batch, num_spec]
    target_probs: jax.Array,  # [batch, num_spec + 1, vocab]
    key: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rejection-based speculative verification (reference
    ``chain_speculative_sampling``, sampling.py / sampling.cuh:1519).

    Returns ``(output_token_ids [batch, num_spec+1] with -1 padding,
    accepted_counts [batch], emitted_counts [batch])``.  Count semantics match
    the reference (sampling.cuh ChainSpeculativeSampling epilogue):
    ``accepted`` counts every draft position whose independent accept test
    passes (even after the first rejection — an acceptance-rate telemetry
    number), while ``emitted`` counts the draft tokens actually emitted
    (the leading accepted run, excluding the bonus token).
    """
    batch, num_spec, vocab = draft_probs.shape
    ku, ks = jax.random.split(key)
    u = jax.random.uniform(ku, (batch, num_spec), dtype=jnp.float32)

    d = draft_probs.astype(jnp.float32)
    t = target_probs.astype(jnp.float32)
    tok = draft_token_ids
    bidx = jnp.arange(batch)[:, None]
    sidx = jnp.arange(num_spec)[None, :]
    p_draft = d[bidx, sidx, tok]
    p_target = t[bidx, sidx, tok]
    accept = u < jnp.minimum(1.0, p_target / jnp.maximum(p_draft, 1e-30))
    # leading accepted run = number of draft tokens emitted
    emitted = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1), axis=-1)
    # telemetry count: every position passing its independent test
    accepted = jnp.sum(accept.astype(jnp.int32), axis=-1)

    # residual distribution at the first rejected position (or bonus position)
    pos = emitted  # in [0, num_spec]
    t_at = t[jnp.arange(batch), pos]  # [batch, vocab]
    d_at = jnp.where(
        (pos < num_spec)[:, None],
        d[jnp.arange(batch), jnp.minimum(pos, num_spec - 1)],
        jnp.zeros_like(t_at),
    )
    resid = jnp.maximum(t_at - d_at, 0.0)
    resid_sum = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(resid_sum > 0, resid / jnp.maximum(resid_sum, 1e-30), t_at)
    extra = jax.random.categorical(
        ks, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1
    ).astype(jnp.int32)

    out_pos = jnp.arange(num_spec + 1)[None, :]
    out = jnp.where(
        out_pos < pos[:, None],
        jnp.pad(tok, ((0, 0), (0, 1))),
        jnp.where(out_pos == pos[:, None], extra[:, None], -1),
    ).astype(jnp.int32)
    return out, accepted.astype(jnp.int32), emitted.astype(jnp.int32)


def get_default_generators(*_, **__):
    """Reference returns per-device torch.Generators for the sampling
    kernels.  JAX sampling is functional — every entry takes an explicit
    ``key=jax.random.PRNGKey(...)`` — so there is no generator registry;
    returns an empty mapping for import parity."""
    return {}
