"""Sampling statics of a serving step and the epilogue built from them.

:class:`SamplingConfig` is what ``EngineConfig.sampling`` holds;
:func:`sample_next_tokens` is the LogitsPipe chain (Temperature ->
Softmax -> TopK -> TopP -> Sample) as one function of logits and a key.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Frozen sampling statics of a serving step.

    Mirrors the ``LogitsPipe([Temperature(), Softmax(), TopK(),
    TopP(), Sample()])`` chain op for op (``logits_processor``): the
    epilogue applies exactly the stages this config enables, so a pipe
    built with the same stages and parameters samples the SAME token
    from the same key.

    ``top_k=0`` disables the top-k stage, ``top_p>=1.0`` the top-p
    stage; temperature always applies (division by 1.0 is exact)."""

    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0


def sample_next_tokens(logits: jax.Array, key: jax.Array,
                       config: SamplingConfig) -> jax.Array:
    """The fused sampling epilogue: logits [B, V] -> tokens [B].

    Op-for-op the LogitsPipe chain (Temperature -> Softmax -> TopK ->
    TopP -> Sample) with the config's enabled stages, built from the
    same ``flashinfer_tpu.sampling`` kernels the pipe legalizes to —
    bit-parity with a matching pipe is the test contract
    (tests/test_sampling.py)."""
    from flashinfer_tpu import sampling as S

    t = jnp.maximum(jnp.asarray(config.temperature, jnp.float32), 1e-6)
    probs = jax.nn.softmax((logits / t).astype(jnp.float32), axis=-1)
    if config.top_k:
        probs = S.top_k_renorm_probs(probs, config.top_k)
    if config.top_p < 1.0:
        probs = S.top_p_renorm_probs(probs, config.top_p)
    return S.sampling_from_probs(probs, key)
