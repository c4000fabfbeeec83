"""Compile-once donated-buffer serving step (plan/run lifecycle).

The fast_decode_plan / CUDAGraph analog (SURVEY: plan/run lifecycle,
amortized host scheduling): the serving loop's whole decode step —
rmsnorm -> qkv projections -> RoPE -> **paged KV append** -> paged
decode attention (int8-KV scale folding included) -> o/mlp GEMMs ->
lm_head -> sampling — compiled ONCE into a single XLA program with
``donate_argnums`` on the KV caches, page tables, and sampling key.
XLA's input/output aliasing then updates every serving buffer in
place, and the step never retraces across tokens (pinned by the
trace-counter test): per-step host cost collapses to ONE dispatch,
the honest fix for the 1.13-1.31x e2e-vs-slope overhead tax PR 2's
``overhead_decomposition`` measured on the per-op loop.

Two step shapes:

- :class:`ServingStep` — the decode mega-step over the Llama model
  family (``models/llama.py`` pytrees, bf16 or int8 weights, bf16 or
  int8 KV caches).  Numerics are the per-op loop's exactly: the traced
  body *is* ``llama_decode_step`` plus the fused sampling epilogue, so
  fused-vs-unfused bit-parity is structural, not approximate.
- :class:`MixedServingStep` — chunked prefill + decode in ONE step
  over the holistic BatchAttention machinery (the reference's
  ``TwoStageHolisticPlan`` / persistent mixed-batch kernel shape,
  ``persistent.cuh:682``): requests with ``qo_len > 1`` advance a
  prompt chunk, requests with ``qo_len == 1`` decode — one flattened
  token axis, one launch.  plan() closes the wrapper's frozen gather
  plan arrays (``BatchPrefillWithPagedKVCacheWrapper.plan_arrays``)
  into the step.

plan() freezes ALL statics host-side — layer count, shapes, page
geometry, quantization mode, sampling config, and the attention
backend (resolved EAGERLY, never inside the trace: the L003
staticness contract) — so the jitted body reads no configuration at
trace time beyond the frozen plan.  Donated state is threaded as an
explicit tuple; after ``run()`` the previous state's buffers are
invalid (aliased into the new state), exactly like the reference's
CUDAGraph-owned workspace.

See docs/performance.md ("Compile-once serving step") for lifecycle,
donation contract, and the retrace conditions.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flashinfer_tpu.api_logging import flashinfer_api


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Frozen sampling statics of a serving step.

    Mirrors the ``LogitsPipe([Temperature(), Softmax(), TopK(),
    TopP(), Sample()])`` chain op for op (``logits_processor``): the
    fused epilogue applies exactly the stages this config enables, so
    a pipe built with the same stages and parameters samples the SAME
    token from the same key (the examples/generate.py parity assert).

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
    (tests/test_serve_step.py)."""
    from flashinfer_tpu import sampling as S

    t = jnp.maximum(jnp.asarray(config.temperature, jnp.float32), 1e-6)
    probs = jax.nn.softmax((logits / t).astype(jnp.float32), axis=-1)
    if config.top_k:
        probs = S.top_k_renorm_probs(probs, config.top_k)
    if config.top_p < 1.0:
        probs = S.top_p_renorm_probs(probs, config.top_p)
    return S.sampling_from_probs(probs, key)


@dataclasses.dataclass(frozen=True)
class ServingStepPlan:
    """Every static the fused step closes over (the TPU analogue of
    the frozen ``DecodePlanInfo`` + CUDAGraph shape set): model
    geometry, page geometry, quantization mode, sampling config, and
    the eagerly-resolved backend flag.  A live plan never changes —
    re-planning builds a fresh jitted step (counted as a replan)."""

    cfg: object  # models.llama.LlamaConfig (frozen dataclass)
    batch_size: int
    num_pages: int
    pages_per_req: int
    page_size: int
    kv_dtype: str
    weights_int8: bool
    sampling: SamplingConfig
    use_pallas: bool
    donate: bool
    # mesh identity of a sharded plan ("" = single-device; "dp2.tp4" =
    # compiled under parallel.plan.ShardingPlan shardings) — rides onto
    # bench rows as configuration, never a measurement
    mesh_axes: str = ""


def _validate_state_geometry(plan: ServingStepPlan, caches, page_table,
                             kv_lens) -> None:
    cfg = plan.cfg
    if len(caches) != cfg.num_layers:
        raise ValueError(
            f"state has {len(caches)} layer caches; plan froze "
            f"{cfg.num_layers} layers")
    kc0 = caches[0][0]
    expect = (cfg.num_kv_heads, plan.page_size, cfg.head_dim)
    if tuple(kc0.shape[1:]) != expect or kc0.shape[0] < plan.num_pages:
        raise ValueError(
            f"KV cache shape {tuple(kc0.shape)} != planned HND "
            f"geometry (>={plan.num_pages}, {', '.join(map(str, expect))})")
    if str(kc0.dtype) != plan.kv_dtype:
        raise ValueError(
            f"KV cache dtype {kc0.dtype} != planned {plan.kv_dtype} "
            "(quantization mode is a frozen plan static)")
    if tuple(page_table.shape) != (plan.batch_size, plan.pages_per_req):
        raise ValueError(
            f"page_table shape {tuple(page_table.shape)} != planned "
            f"({plan.batch_size}, {plan.pages_per_req})")
    if kv_lens.shape != (plan.batch_size,):
        raise ValueError(
            f"kv_lens shape {kv_lens.shape} != ({plan.batch_size},)")


def _validate_logits_batch(plan: ServingStepPlan, logits) -> None:
    if logits.shape[0] != plan.batch_size:
        raise ValueError(
            f"logits batch {logits.shape[0]} != planned batch "
            f"{plan.batch_size}")


class ServingStep:
    """Compile-once fused decode step over the Llama model family.

    Lifecycle mirrors the batch wrappers (plan host-side once per
    geometry, run per token):

    >>> step = ServingStep()
    >>> step.plan(cfg, page_table=pt, kv_lens=lens,
    ...           sampling=SamplingConfig(0.8, 40, 0.95))
    >>> state = step.make_state(caches, pt, lens, prefill_logits, key)
    >>> for _ in range(n):
    ...     tokens, state = step.run(params, state)

    One jitted program serves every step: the state tuple ``(logits,
    kv_caches, page_table, kv_lens, key)`` is DONATED (KV caches, page
    table, lens, and PRNG key alias in place; re-using a consumed
    state raises jax's deleted-buffer error — thread the returned
    one).  ``num_traces`` exposes the compile count; the
    ``serve.step_retraces`` counter (obs catalog) records any trace
    beyond the first — with a stable plan there is exactly one.

    plan() can also seed its geometry straight from a planned
    ``BatchDecodeWithPagedKVCacheWrapper`` via ``decode_wrapper=``
    (the wrapper's ``plan_arrays`` export): the serving engine plans
    attention once and the fused step inherits the same frozen padded
    table."""

    # signed-argument component names for the flight recorder's trace
    # signature: a retrace-cause diff reports e.g. "logits" or
    # "caches[0][0]" instead of an opaque pytree path
    _STATE_NAMES = ("params", "logits", "caches", "page_table",
                    "kv_lens", "key")

    def __init__(self):
        self._plan: Optional[ServingStepPlan] = None
        self._step = None
        self._traces = 0
        self._last_sig = None  # obs.spans state signature of last run

    @property
    def num_traces(self) -> int:
        """How many times the live step function has traced (1 after
        the first run; still 1 after N steps — the compile-once pin)."""
        return self._traces

    @property
    def plan_statics(self) -> Optional[ServingStepPlan]:
        return self._plan

    def plan(
        self,
        cfg,  # models.llama.LlamaConfig
        *,
        page_table=None,  # [B, pages_per_req] int32
        kv_lens=None,  # [B] int32 (seeds; evolve in the donated state)
        decode_wrapper=None,  # planned BatchDecodeWithPagedKVCacheWrapper
        num_pages: Optional[int] = None,
        kv_dtype=None,  # cache dtype; default cfg.dtype
        weights_int8: Optional[bool] = None,
        sampling: SamplingConfig = SamplingConfig(),
        donate: bool = True,
        use_pallas: Optional[bool] = None,
        sharding_plan=None,  # parallel.plan.ShardingPlan
    ) -> None:
        """Freeze the step statics and build the jitted donated step.

        Backend resolution (``use_pallas``) happens HERE, eagerly —
        the traced body reads no environment (L003: the step closure
        is static).  ``decode_wrapper=`` imports the frozen attention
        plan (``plan_arrays``) instead of raw ``page_table``/
        ``kv_lens``; geometry mismatches against ``cfg`` raise.

        ``sharding_plan=`` compiles the SAME body under a mesh with
        explicit in/out shardings for every state leaf
        (``parallel.plan.llama_step_shardings``: TP weight table, dp
        batch state, dp-pages x tp-heads caches) — one sharded XLA
        program per step, donation preserved.  dp-only plans are
        tokens-bitwise with the unsharded step; tp>1 reorders the split
        f32 contractions (documented tolerance,
        tests/test_sharded_step.py)."""
        from flashinfer_tpu import obs
        from flashinfer_tpu.models.llama import llama_decode_step
        from flashinfer_tpu.utils import is_tpu

        replan = self._plan is not None
        if decode_wrapper is not None:
            arrays = decode_wrapper.plan_arrays
            if arrays["kv_layout"] != "HND":
                raise ValueError(
                    "ServingStep serves the HND paged layout only "
                    f"(wrapper planned {arrays['kv_layout']!r})")
            if (arrays["num_qo_heads"] != cfg.num_qo_heads
                    or arrays["num_kv_heads"] != cfg.num_kv_heads
                    or arrays["head_dim"] != cfg.head_dim):
                raise ValueError(
                    f"decode wrapper plan heads/dim "
                    f"({arrays['num_qo_heads']}, {arrays['num_kv_heads']}, "
                    f"{arrays['head_dim']}) != model cfg "
                    f"({cfg.num_qo_heads}, {cfg.num_kv_heads}, "
                    f"{cfg.head_dim})")
            if arrays["batch_size"] != arrays["page_table"].shape[0]:
                # the wrapper pow2-buckets its batch axis; the fused
                # step runs UNPADDED state tensors, so a padded plan
                # would crash at trace time with an opaque broadcast
                # error — fail here, with the fix in the message
                raise ValueError(
                    f"decode wrapper planned batch "
                    f"{arrays['batch_size']} padded to bucket "
                    f"{arrays['page_table'].shape[0]}; the fused step "
                    "needs a bucket-aligned batch — plan the wrapper "
                    "at a power-of-two batch (>= 8), or pass raw "
                    "page_table=/kv_lens= at the actual batch instead")
            page_table = arrays["page_table"]
            kv_lens = arrays["kv_lens"]
            page_size = arrays["page_size"]
        else:
            if page_table is None or kv_lens is None:
                raise ValueError(
                    "plan() needs page_table+kv_lens or decode_wrapper=")
            page_size = None  # derived from the cache at make_state
        page_table = jnp.asarray(page_table, jnp.int32)
        batch, pages_per_req = page_table.shape
        if use_pallas is None:
            use_pallas = is_tpu()  # resolved once, never in the trace
        kv_dtype = jnp.dtype(kv_dtype) if kv_dtype is not None \
            else jnp.dtype(cfg.dtype)
        self._plan = ServingStepPlan(
            cfg=cfg, batch_size=int(batch),
            num_pages=int(num_pages) if num_pages is not None
            else int(np.asarray(jnp.max(page_table)) + 1),
            pages_per_req=int(pages_per_req),
            page_size=int(page_size) if page_size else 0,
            kv_dtype=str(kv_dtype),
            weights_int8=bool(weights_int8) if weights_int8 is not None
            else False,
            sampling=sampling, use_pallas=bool(use_pallas),
            donate=bool(donate),
            mesh_axes=sharding_plan.mesh_axes if sharding_plan is not None
            else "",
        )
        plan = self._plan
        self._traces = 0
        step_self = self
        # the sampling chain must run REPLICATED under a mesh: this
        # jax's threefry is not partitionable, so random bits generated
        # over a sharded operand differ from the single-device stream.
        # Pinning the logits alone is not enough — GSPMD BACK-propagates
        # the embed-gather's dp sharding through the sampled tokens into
        # the RNG — so the tokens are pinned too, fencing the sampler
        # off from both sides (cost: one [B, vocab] f32 gather per step)
        sample_sharding = (sharding_plan.replicated
                           if sharding_plan is not None else None)

        def _body(params, logits, caches, page_table, kv_lens, key):
            # runs at TRACE time only: with a stable plan this counter
            # advances exactly once across the whole serving session
            step_self._traces += 1
            key, sk = jax.random.split(key)
            if sample_sharding is not None:
                logits = jax.lax.with_sharding_constraint(
                    logits, sample_sharding)
            tokens = sample_next_tokens(logits, sk, plan.sampling)
            if sample_sharding is not None:
                tokens = jax.lax.with_sharding_constraint(
                    tokens, sample_sharding)
            new_logits, new_caches = llama_decode_step(
                params, plan.cfg, tokens, kv_lens, caches, page_table,
                kv_lens, use_pallas=plan.use_pallas,
            )
            return (tokens, new_logits, new_caches, page_table,
                    kv_lens + 1, key)

        # donation: KV caches (2), page table (3), kv_lens (4), PRNG
        # key (5) alias input->output in place; params and logits stay
        # caller-owned (weights are shared across steps, logits feed
        # external parity/telemetry readers)
        donate_argnums = (2, 3, 4, 5) if donate else ()
        if sharding_plan is not None:
            from flashinfer_tpu.parallel.plan import (
                compile_step_with_plan, llama_step_shardings)

            in_sh, out_sh = llama_step_shardings(
                sharding_plan, cfg, weights_int8=self._plan.weights_int8)
            # out structure is (tokens, logits, caches, pt, lens, key);
            # llama_step_shardings' out matches positionally
            self._step = compile_step_with_plan(
                _body, sharding_plan, in_shardings=in_sh,
                out_shardings=out_sh, donate_argnums=donate_argnums)
        else:
            self._step = jax.jit(_body, donate_argnums=donate_argnums)
        self._last_sig = None  # a fresh plan resets run-state tracking
        # statics= hands the frozen plan to the flight recorder: a
        # replan whose statics moved records the retrace cause
        # (plan.retrace_cause{wrapper,key}) before the next run pays
        # it.  page_size stays OUT of the plan signature: raw-geometry
        # plans carry the 0 "derived at make_state" sentinel, so
        # signing it would diff sentinel-vs-frozen across replans of
        # identical geometry (a phantom cause); a REAL page-size move
        # is still attributed — it changes every cache's shape, which
        # the run-state signature covers
        statics = {f.name: getattr(self._plan, f.name)
                   for f in dataclasses.fields(self._plan)
                   if f.name != "page_size"}
        obs.record_plan(self, replan=replan, statics=statics)

    def make_state(self, kv_caches: List[Tuple[jax.Array, jax.Array]],
                   page_table: jax.Array, kv_lens: jax.Array,
                   logits: jax.Array, key: jax.Array):
        """Assemble (and geometry-check) the donated state tuple from
        the post-prefill serving buffers."""
        plan = self._plan
        if plan is None:
            raise RuntimeError("plan() must be called before make_state")
        page_table = jnp.asarray(page_table, jnp.int32)
        kv_lens = jnp.asarray(kv_lens, jnp.int32)
        if not plan.page_size:
            # raw-array plan: the page size is whatever the cache
            # carries; freeze it on first state assembly (the flight
            # recorder's plan signature deliberately excludes
            # page_size, so this late freeze never skews replan diffs)
            plan = dataclasses.replace(
                plan, page_size=int(kv_caches[0][0].shape[2]))
            self._plan = plan
        elif kv_caches[0][0].shape[2] != plan.page_size:
            raise ValueError(
                f"cache page_size {kv_caches[0][0].shape[2]} != planned "
                f"{plan.page_size}")
        _validate_state_geometry(plan, kv_caches, page_table, kv_lens)
        logits = jnp.asarray(logits)
        _validate_logits_batch(plan, logits)
        return (logits, list(kv_caches), page_table, kv_lens, key)

    @flashinfer_api(name="serve.step")
    def run(self, params, state):
        """One fused serving step: sample the carried logits, decode
        the sampled tokens, advance the donated state.  Returns
        ``(tokens, new_state)``; the input state's donated buffers are
        consumed."""
        from flashinfer_tpu import obs

        if self._step is None:
            raise RuntimeError("plan() must be called before run()")
        tick = obs.steploop_begin("ServingStep")
        logits, caches, page_table, kv_lens, key = state
        # flight recorder (FLASHINFER_TPU_SPANS): the trace signature
        # over EVERY jitted argument — params included, so a swapped
        # weight dtype/pytree attributes too (shape/dtype/structure
        # only, raw tuples: never a device transfer, no string work on
        # the hot path)
        signed = (params, logits, caches, page_table, kv_lens, key)
        sig = obs.state_signature(signed, names=self._STATE_NAMES)
        if tick is not None:
            tick.mark("signature")
        before = self._traces
        t0 = time.perf_counter() if sig is not None else 0.0
        out = self._step(params, logits, caches, page_table, kv_lens, key)
        if tick is not None:
            tick.dispatched()
        if self._traces > before:
            if sig is not None:
                # this dispatch paid a trace + XLA compile: give the
                # flight recorder the phase span (first trace is the
                # planned one; later ones are the retraces below)
                obs.record_span(f"{type(self).__name__}.trace_and_compile",
                                "compile", t0, time.perf_counter(),
                                wrapper=type(self).__name__,
                                trace_index=self._traces)
            if self._traces > 1:
                # a retrace under a live plan means a state pytree/
                # shape/dtype moved — the compile-once contract broke
                obs.counter_inc("serve.step_retraces",
                                wrapper=type(self).__name__)
                if sig is not None:
                    obs.record_retrace(
                        type(self).__name__,
                        obs.diff_state_sigs(self._last_sig, sig, signed))
        if sig is not None:
            self._last_sig = sig
        tokens, new_logits, new_caches, pt, lens, new_key = out
        if tick is not None:
            # completion probe (gate-ON measurement tax: one device
            # sync per step) — the OUTPUT blocks, never a donated input
            jax.block_until_ready(tokens)
            tick.done()
            tick.commit(tokens=int(tokens.shape[0]))
        return tokens, (new_logits, new_caches, pt, lens, new_key)


def mixed_chunk_tokens(batch_size: int, page_size: int, *,
                       default: int = 64) -> int:
    """Plan-time chunked-prefill chunk size (tokens advanced per mixed
    step by each prefilling request): the ``serve.mixed_chunk``
    autotune knob (KNOWN_KNOBS), shape-keyed on (batch, page_size).
    Larger chunks amortize the step launch over more prompt tokens;
    smaller chunks bound decode-request latency interference — the
    classic chunked-prefill trade, measured per chip generation."""
    from flashinfer_tpu.autotuner import AutoTuner

    return int(AutoTuner.get().lookup(
        "serve.mixed_chunk", (int(batch_size), int(page_size)),
        default=int(default)))


@dataclasses.dataclass(frozen=True)
class _MixedPlan:
    """Frozen statics + closed arrays of a mixed step (one chunk
    geometry; re-plan per scheduling decision, run per layer-sweep)."""

    cfg: object
    batch_size: int
    total_q: int
    page_size: int
    kv_dtype: str
    sampling: SamplingConfig
    donate: bool
    backend: str  # eagerly-resolved attention backend ("pallas"|"xla")
    # ISSUE 14: True = the step's per-layer RoPE + KV-quantize-append +
    # attention ride ONE fused-ingest launch (from-scratch prefill
    # steps only — every request at kv_before == 0); False = the
    # rope -> scatter-append -> gather-attend composition
    fused_ingest: bool = False


class MixedServingStep:
    """Chunked-prefill + decode in ONE jitted donated step.

    The holistic mixed-batch shape (reference ``TwoStageHolisticPlan``
    / ``persistent.cuh:682``): plan() takes per-request ``qo_lens``
    (prompt-chunk sizes; 1 for decoding requests) and the paged-KV
    geometry, builds the flattened token axis + per-token append
    targets host-side, plans the holistic attention through
    ``BatchAttention`` and closes its exported gather-plan arrays
    (``plan_arrays``) into the traced body.  run() embeds the flat
    token batch, appends every new K/V into the paged cache, attends
    causally over the post-append cache, and samples each request's
    last-token logits — one launch for the whole mixed batch.

    ``run_unfused`` executes the identical body eagerly (no jit, no
    donation) — the bit-parity oracle for the fused program."""

    _STATE_NAMES = ("params", "flat_tokens", "caches", "key")

    def __init__(self):
        self._plan: Optional[_MixedPlan] = None
        self._body = None
        self._step = None
        self._traces = 0
        self._last_sig = None

    @property
    def num_traces(self) -> int:
        return self._traces

    def plan(
        self,
        cfg,  # models.llama.LlamaConfig
        qo_lens,  # [B] host ints: tokens each request advances (>=1)
        kv_lens_before,  # [B] host ints: cache lens before this step
        kv_page_indptr,  # [B+1] host ints
        kv_page_indices,  # [total_pages] host ints
        page_size: int,
        *,
        kv_dtype=None,
        sampling: SamplingConfig = SamplingConfig(),
        donate: bool = True,
        backend: str = "auto",
        fused_ingest: Optional[bool] = None,
    ) -> None:
        from flashinfer_tpu import obs
        from flashinfer_tpu.attention import BatchAttention
        from flashinfer_tpu.utils import resolve_backend

        replan = self._plan is not None
        qo_lens = np.asarray(qo_lens, np.int64)
        kv0 = np.asarray(kv_lens_before, np.int64)
        if np.any(qo_lens < 1):
            raise ValueError("every request advances >= 1 token per "
                             "mixed step")
        # ISSUE 14 fused-ingest adoption: eligible iff this step is a
        # from-scratch prefill (every request at kv_before == 0 — the
        # first mixed step of a batch, where prefill cost concentrates);
        # None resolves via the prefill.fused_ingest knob -> cost-model
        # chooser (THE shared resolution point, prefill.py)
        ingest_eligible = bool(np.all(kv0 == 0)) and len(qo_lens) > 0
        if fused_ingest and not ingest_eligible:
            raise ValueError(
                "fused_ingest=True needs a from-scratch prefill step "
                "(every kv_lens_before == 0): chunked continuations "
                "attend cached prefixes the ingest kernel does not "
                "re-read — keep the composed step for them")
        B = len(qo_lens)
        qo_indptr = np.concatenate([[0], np.cumsum(qo_lens)]) \
            .astype(np.int32)
        total_q = int(qo_indptr[-1])
        seq_after = (kv0 + qo_lens).astype(np.int32)
        # per-token positions + paged append scatter targets (host math
        # once; frozen into the closure)
        tok_req = np.repeat(np.arange(B), qo_lens)
        tok_off = np.concatenate([np.arange(n) for n in qo_lens])
        positions = (kv0[tok_req] + tok_off).astype(np.int32)
        kvp_indptr = np.asarray(kv_page_indptr, np.int64)
        kvp_idx = np.asarray(kv_page_indices, np.int64)
        page_in_req = positions // page_size
        token_page = kvp_idx[kvp_indptr[tok_req] + page_in_req] \
            .astype(np.int32)
        token_slot = (positions % page_size).astype(np.int32)

        # the holistic attention plan over the POST-append cache; its
        # exported gather arrays are the closed attention schedule
        attn = BatchAttention(kv_layout="HND")
        attn.plan(
            qo_indptr, kv_page_indptr, kv_page_indices, seq_after,
            cfg.num_qo_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.head_dim, page_size, causal=True,
        )
        arrays = attn.plan_arrays
        last_idx = (qo_indptr[1:] - 1).astype(np.int32)

        # attention backend resolved EAGERLY (L003: the closure is
        # static); the gather + segment-flash form serves both — the
        # plan arrays ARE the gather schedule
        resolved = resolve_backend(
            "pallas" if backend == "pallas_fused" else backend,
            "batch_prefill_paged")
        if resolved == "pallas":
            from flashinfer_tpu.prefill import _tuned_flash as attn_fn
        else:
            from flashinfer_tpu.ops.xla_ref import (
                xla_ragged_attention as attn_fn)

        kv_dtype = jnp.dtype(kv_dtype) if kv_dtype is not None \
            else jnp.dtype(cfg.dtype)
        int8_kv = kv_dtype == jnp.int8
        sm_plain = float(arrays["sm_scale"])
        sm_scale = arrays["sm_scale"] * (cfg.kv_k_scale if int8_kv
                                         else 1.0)
        # fused-ingest resolution (ISSUE 14): an explicit request wins
        # (but must be eligible AND on the pallas tier — the ingest
        # kernel IS the work-unit mainloop); None routes through the
        # prefill.fused_ingest knob -> cost-model chooser, the same
        # single resolution point the wrapper uses (prefill.py)
        if fused_ingest is None:
            use_ingest = False
            from flashinfer_tpu.ops.paged_prefill import (
                ingest_pages_per_chunk, ingest_rows_aligned)

            # the raw rows are the flat token axis: bases = qo_indptr;
            # the compiled kernel needs them aligned (INGEST_KV_ALIGN)
            if ingest_eligible and resolved == "pallas" \
                    and ingest_rows_aligned(
                        qo_indptr[:-1],
                        ingest_pages_per_chunk(page_size) * page_size):
                from flashinfer_tpu.prefill import resolve_prefill_ingest

                fkey = (B, int(arrays["tq_pad"]), cfg.num_qo_heads,
                        cfg.num_kv_heads, cfg.head_dim, int(page_size))
                use_ingest = resolve_prefill_ingest(
                    fkey, total_q=total_q,
                    total_kv=int(seq_after.sum()),
                    num_qo_heads=cfg.num_qo_heads,
                    num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.head_dim,
                    cache_bytes=int(kv_dtype.itemsize))
        else:
            use_ingest = bool(fused_ingest)
            if use_ingest and resolved != "pallas":
                raise ValueError(
                    "fused_ingest=True needs the pallas attention tier "
                    f"(backend resolved to {resolved!r}) — the ingest "
                    "kernel is the work-unit prefill mainloop")
        ingest_plan = None
        ingest_statics = None
        if use_ingest:
            from flashinfer_tpu.ops.paged_prefill import (
                build_prefill_ingest_units, ingest_block_q,
                ingest_pages_per_chunk)

            ibq = ingest_block_q(total_q)
            ippc = ingest_pages_per_chunk(page_size)
            iplan_np = build_prefill_ingest_units(
                qo_indptr.astype(np.int64), kvp_indptr, kvp_idx,
                seq_after.astype(np.int64),
                block_q=ibq, pages_per_chunk=ippc,
                page_size=int(page_size), causal=True,
            )
            ingest_statics = dict(
                num_units=iplan_np.pop("num_units"),
                block_q=iplan_np.pop("block_q"),
                pages_per_chunk=iplan_np.pop("pages_per_chunk"),
                kv_aligned=iplan_np.pop("stats")["ingest_kv_aligned"],
            )
            ingest_plan = {k2: jnp.asarray(v2)
                           for k2, v2 in iplan_np.items()}
        self._plan = _MixedPlan(
            cfg=cfg, batch_size=B, total_q=total_q,
            page_size=int(page_size), kv_dtype=str(kv_dtype),
            sampling=sampling, donate=bool(donate), backend=resolved,
            fused_ingest=use_ingest,
        )
        plan = self._plan
        self._traces = 0
        step_self = self

        # closed device constants (small plan arrays only; caches ride
        # as donated ARGUMENTS — closure-captured arrays embed as HLO
        # constants, fine at plan-array scale, fatal at cache scale)
        j_positions = jnp.asarray(positions)
        j_token_page = jnp.asarray(token_page)
        j_token_slot = jnp.asarray(token_slot)
        j_last_idx = jnp.asarray(last_idx)
        q_seg, q_pos = arrays["q_seg"], arrays["q_pos"]
        kv_seg, kv_pos = arrays["kv_seg"], arrays["kv_pos"]
        gather_rows = arrays["kv_gather_rows"]
        tq_pad, causal = arrays["tq_pad"], arrays["causal"]
        window_left = arrays["window_left"]
        soft_cap = arrays["logits_soft_cap"]

        def _attend(q, kc, vc):
            # HND [pages, Hkv, PS, D] -> flat NHD rows -> planned gather
            kg = jnp.swapaxes(kc, 1, 2).reshape(
                -1, cfg.num_kv_heads, cfg.head_dim)[gather_rows]
            vg = jnp.swapaxes(vc, 1, 2).reshape(
                -1, cfg.num_kv_heads, cfg.head_dim)[gather_rows]
            if int8_kv:  # raw codes attend; scales folded (sm/v_scale)
                kg = kg.astype(q.dtype)
                vg = vg.astype(q.dtype)
            qp = jnp.pad(q, ((0, tq_pad - total_q), (0, 0), (0, 0))) \
                if total_q != tq_pad else q
            out = attn_fn(
                qp, kg, vg, q_seg, kv_seg, q_pos, kv_pos,
                causal=causal, sm_scale=sm_scale,
                logits_soft_cap=soft_cap, window_left=window_left,
                return_lse=False,
            )
            out = out[:total_q]
            if int8_kv:
                out = (out.astype(jnp.float32)
                       * cfg.kv_v_scale).astype(q.dtype)
            return out

        def _body(params, flat_tokens, caches, key):
            from flashinfer_tpu.activation import silu_and_mul
            from flashinfer_tpu.models.llama import _mm, _pre_quant
            from flashinfer_tpu.norm import rmsnorm
            from flashinfer_tpu.rope import apply_rope_pos_ids

            step_self._traces += 1
            x = params["embed"][flat_tokens].astype(cfg.dtype)
            new_caches = []
            for li, layer in enumerate(params["layers"]):
                h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
                pre = _pre_quant(h, layer)
                q = _mm(h, layer, "q_proj", pre).reshape(
                    total_q, cfg.num_qo_heads, cfg.head_dim)
                k = _mm(h, layer, "k_proj", pre).reshape(
                    total_q, cfg.num_kv_heads, cfg.head_dim)
                v = _mm(h, layer, "v_proj", pre).reshape(
                    total_q, cfg.num_kv_heads, cfg.head_dim)
                kc, vc = caches[li]
                if use_ingest:
                    # ISSUE 14 fused ingest: RoPE + quantize-append +
                    # attention in ONE work-unit launch over the RAW
                    # q/k/v — the scatter-append and the cache re-read
                    # below disappear.  The launcher owns the int8
                    # scale folding (k into sm, v on the output), so
                    # it gets the PLAIN sm_scale and the raw scales
                    from flashinfer_tpu.ops.paged_prefill import (
                        fused_paged_prefill_ingest)

                    attn, (kc, vc) = fused_paged_prefill_ingest(
                        q, k, v, kc, vc, ingest_plan,
                        sm_scale=sm_plain, causal=True,
                        rope_theta=float(cfg.rope_theta),
                        kv_quant="int8" if int8_kv else "none",
                        k_scale=float(cfg.kv_k_scale) if int8_kv
                        else 1.0,
                        v_scale=float(cfg.kv_v_scale) if int8_kv
                        else 1.0,
                        **ingest_statics,
                    )
                else:
                    q, k = apply_rope_pos_ids(q, k, j_positions,
                                              rope_theta=cfg.rope_theta)
                    if int8_kv:
                        from flashinfer_tpu.quantization import (
                            quantize_symmetric_int8)

                        k_w = quantize_symmetric_int8(k, cfg.kv_k_scale)
                        v_w = quantize_symmetric_int8(v, cfg.kv_v_scale)
                    else:
                        k_w = k.astype(kc.dtype)
                        v_w = v.astype(vc.dtype)
                    kc = kc.at[j_token_page, :, j_token_slot, :].set(k_w)
                    vc = vc.at[j_token_page, :, j_token_slot, :].set(v_w)
                    attn = _attend(q, kc, vc)
                new_caches.append((kc, vc))
                x = x + _mm(attn.reshape(total_q, -1), layer,
                            "o_proj").astype(cfg.dtype)
                h2 = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
                pre2 = _pre_quant(h2, layer, "gate_proj")
                mlp = jnp.concatenate(
                    [_mm(h2, layer, "gate_proj", pre2),
                     _mm(h2, layer, "up_proj", pre2)], -1)
                x = x + _mm(silu_and_mul(mlp), layer,
                            "down_proj").astype(cfg.dtype)
            x_last = x[j_last_idx]
            xf = rmsnorm(x_last, params["final_norm"], cfg.rms_eps)
            logits = _mm(xf, params, "lm_head").astype(jnp.float32)
            key, sk = jax.random.split(key)
            tokens = sample_next_tokens(logits, sk, plan.sampling)
            return tokens, logits, new_caches, key

        self._body = _body
        donate_argnums = (2, 3) if donate else ()  # caches + key
        self._step = jax.jit(_body, donate_argnums=donate_argnums)
        self._last_sig = None
        obs.record_plan(self, replan=replan, statics=self._plan)

    @flashinfer_api(name="serve.mixed_step")
    def run(self, params, flat_tokens, caches, key):
        """One fused mixed step -> ``(tokens [B], last_logits [B, V],
        new_caches, new_key)``.  ``caches`` and ``key`` are donated."""
        from flashinfer_tpu import obs

        if self._step is None:
            raise RuntimeError("plan() must be called before run()")
        tick = obs.steploop_begin("MixedServingStep")
        flat_tokens = jnp.asarray(flat_tokens, jnp.int32)
        signed = (params, flat_tokens, caches, key)
        sig = obs.state_signature(signed, names=self._STATE_NAMES)
        if tick is not None:
            tick.mark("signature")
        before = self._traces
        t0 = time.perf_counter() if sig is not None else 0.0
        out = self._step(params, flat_tokens, caches, key)
        if tick is not None:
            tick.dispatched()
        if self._traces > before:
            if sig is not None:
                obs.record_span(f"{type(self).__name__}.trace_and_compile",
                                "compile", t0, time.perf_counter(),
                                wrapper=type(self).__name__,
                                trace_index=self._traces)
            if self._traces > 1:
                obs.counter_inc("serve.step_retraces",
                                wrapper=type(self).__name__)
                if sig is not None:
                    obs.record_retrace(
                        type(self).__name__,
                        obs.diff_state_sigs(self._last_sig, sig, signed))
        if sig is not None:
            self._last_sig = sig
        if tick is not None:
            jax.block_until_ready(out[0])  # completion probe (gate-ON)
            tick.done()
            tick.commit(tokens=int(flat_tokens.shape[0]))
        return out

    def run_unfused(self, params, flat_tokens, caches, key):
        """The identical body, eager (no jit / no donation): the
        bit-parity oracle for the fused program — inputs stay valid.
        The trace counter is restored afterwards (an eager oracle run
        is not a trace of the compiled step)."""
        if self._body is None:
            raise RuntimeError("plan() must be called before run_unfused()")
        traces = self._traces
        try:
            return self._body(params, jnp.asarray(flat_tokens, jnp.int32),
                              caches, key)
        finally:
            self._traces = traces
