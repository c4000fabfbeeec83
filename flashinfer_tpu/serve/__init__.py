"""flashinfer_tpu.serve — the serving layer: one surface, :class:`ServingEngine`.

A continuous-batching engine whose every step (decode-only or mixed
chunked prefill + decode) is ONE donated-buffer XLA program per rung of
a small ladder of token counts, compiled once: ``jax.jit`` with
``donate_argnums`` on the KV caches, so XLA aliases the serving state in
place and a step never retraces across tokens.

- :mod:`~flashinfer_tpu.serve.engine` — :class:`ServingEngine`,
  :class:`EngineConfig`, :class:`EngineRequest`: ref-counted paged-KV
  block pool (:class:`BlockPool`), prefix-cache reuse via the cascade
  merge operator (:class:`PrefixCache`), scheduling on the pre-compiled
  rung ladder; the step of the Llama-style dense layer;
- :mod:`~flashinfer_tpu.serve.block_step` — the step of a model that
  brings its own layer (``engine_layer``), :mod:`~flashinfer_tpu.serve.
  recurrent` its recurrent and latent layers' work units,
  :mod:`~flashinfer_tpu.serve.kv_write` the in-place row write both step
  bodies (and a latent layer's ``append``) call;
- :mod:`~flashinfer_tpu.serve.engine_kernels` — the engine's KERNEL
  attention tier (``EngineConfig.attention_backend="kernel"``): the
  host planner that lowers each step's schedule onto the work-unit
  prefill mainloop + split-KV decode plan arrays, and the in-jit
  cascade-merged composition;
- :mod:`~flashinfer_tpu.serve.kv_tier` — the TIERED KV subsystem:
  :class:`HostKVStore` (host-RAM offload below the block pool —
  spill/restore with bit-exact resume) and :class:`DisaggServing`
  (prefill-pool -> decode-pool disaggregation joined by the
  ``kv_migrate`` handoff; docs/serving.md §"Tiered KV &
  disaggregation");
- :mod:`~flashinfer_tpu.serve.step` — :class:`SamplingConfig` and the
  sampling epilogue :func:`sample_next_tokens`;
- :mod:`~flashinfer_tpu.serve.shard` — the int8 shard step on one
  device, the unsharded oracle of ``parallel/plan.py``'s
  :class:`~flashinfer_tpu.parallel.plan.ShardedServingStep`.

See docs/serving.md for the engine.  ``perfbench/`` is the benchmark;
``PERF.md`` and ``PERF_LEDGER.jsonl`` are where a speed is stated.
"""

from flashinfer_tpu.serve.engine import (
    BlockPool,
    EngineConfig,
    EngineRequest,
    PrefixCache,
    ServingEngine,
)
from flashinfer_tpu.serve.kv_tier import (
    DisaggServing,
    HostKVStore,
    migrate_request,
)
from flashinfer_tpu.serve.step import SamplingConfig, sample_next_tokens

__all__ = [
    "BlockPool",
    "DisaggServing",
    "EngineConfig",
    "EngineRequest",
    "HostKVStore",
    "PrefixCache",
    "SamplingConfig",
    "ServingEngine",
    "migrate_request",
    "sample_next_tokens",
]
