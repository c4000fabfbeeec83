"""Holistic mixed-batch attention, POD, and attention sinks.

TPU re-design of the reference's unified-attention layer:

- ``BatchAttention`` (reference ``flashinfer/attention/_core.py:44``): one
  wrapper serving a mixed prefill+decode batch.  The reference needs a
  two-stage cost-balanced plan (``TwoStageHolisticPlan`` scheduler.cuh:1241
  with a MinHeap) and a persistent kernel (persistent.cuh:682) to keep SMs
  busy; on TPU the segment flash kernel already *is* holistic — all
  requests (1-token decodes and long prefills alike) live on one flattened
  token axis, and a decode-heavy batch degenerates to "one q block reads
  each kv block once", which is the bandwidth-optimal schedule.  So this
  wrapper is the paged-prefill plan/run surface under the holistic name.

- ``PODWithPagedKVCacheWrapper`` (reference pod.py:61): Prefill-On-Decode
  fuses prefill and decode CTAs into one kernel for the same reason; on TPU
  it aliases the holistic path (documented design decision, SURVEY §7
  step 3).

- Attention sinks (reference ``BatchAttentionWithAttentionSinkWrapper``,
  attention/_core.py:330; StreamingLLM): a per-head learnable sink logit
  joins the softmax denominator.  With the (out, lse) pair this is a pure
  epilogue: ``out * exp(lse) / (exp(lse) + exp(sink))`` — the LSE algebra
  again, no kernel change.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from flashinfer_tpu.prefill import BatchPrefillWithPagedKVCacheWrapper


class BatchAttention(BatchPrefillWithPagedKVCacheWrapper):
    """Holistic mixed prefill+decode attention (reference
    flashinfer/attention/_core.py:44).  plan() takes the same geometry as
    the reference: per-request qo lens may mix 1 (decode) and many
    (prefill/append)."""

    def plan(
        self,
        qo_indptr,
        kv_indptr,
        kv_indices,
        kv_len_arr,
        num_qo_heads: int,
        num_kv_heads: int,
        head_dim_qk: int,
        head_dim_vo: int,
        page_size: int,
        causal: bool = False,
        sm_scale: Optional[float] = None,
        logits_soft_cap: Optional[float] = None,
        *,
        window_left: int = -1,
        q_data_type=jnp.bfloat16,
        kv_data_type=None,
        use_profiler: bool = False,
        **_unused,
    ) -> None:
        """Reference arity (attention/_core.py:95): both head dims are
        positional (DeepSeek-style qk 192 / vo 128 splits exist there);
        this build's paged path is square — asymmetric dims raise with
        the MLA alternative.

        ``window_left`` (a TPU-port extension) and everything after it
        are KEYWORD-ONLY: the reference plan has no window_left between
        logits_soft_cap and q_data_type, so a verbatim reference caller
        passing the dtypes positionally would silently bind a dtype
        into window_left (ADVICE.md round-5 item 2).  Reference
        positional calls past logits_soft_cap now raise TypeError —
        loud, never misbound.  The reference arity is recorded in the
        L002 signature bank (analysis/reference_signatures.json)."""
        import numpy as np

        if head_dim_qk != head_dim_vo:
            raise NotImplementedError(
                f"asymmetric head dims (qk {head_dim_qk} != vo "
                f"{head_dim_vo}) — use flashinfer_tpu.mla for the "
                "compressed-KV DeepSeek form")
        kv_len_arr = np.asarray(kv_len_arr)
        kv_indptr = np.asarray(kv_indptr)
        pages_per_req = kv_indptr[1:] - kv_indptr[:-1]
        # reconstruct last_page_len from token lengths
        last = kv_len_arr - (np.maximum(pages_per_req, 1) - 1) * page_size
        super().plan(
            qo_indptr, kv_indptr, kv_indices, last.astype(np.int32),
            num_qo_heads, num_kv_heads, head_dim_qk, page_size,
            causal=causal, sm_scale=sm_scale,
            logits_soft_cap=logits_soft_cap, window_left=window_left,
            q_data_type=q_data_type, kv_data_type=kv_data_type,
        )
        # record of the PLANNED cap (run() no longer validates against
        # it — a differing per-run cap rebinds the frozen plan instead);
        # set only once the plan is actually live so a failed re-plan
        # cannot desync it from the still-active previous plan
        self._plan_soft_cap = float(logits_soft_cap or 0.0)

    def run(self, q, paged_kv_cache, out=None, lse=None, k_scale=None,
            v_scale=None, logits_soft_cap: float = 0.0,
            profiler_buffer=None, *, kv_cache_sf=None, **kw):
        """Reference contract (attention/_core.py:216): ALWAYS returns
        ``(out, lse)``; ``k_scale`` folds into sm_scale for this call,
        ``v_scale`` scales the output.  ``logits_soft_cap``: the 0.0
        default INHERITS the planned cap; a non-zero value takes effect
        FOR THIS CALL (the reference forwards the run value to the
        kernel, attention/_core.py:250) — a value differing from the
        planned one rebinds the frozen plan for the call, the same
        mechanism as the per-run sm_scale rebind (a novel cap compiles
        a fresh kernel variant; counted via plan.soft_cap_rebinds).
        ``profiler_buffer`` is inert (op timelines come from
        flashinfer_tpu.profiler); ``out``/``lse``/``kv_cache_sf``
        prealloc/fp8-sf are rejected loudly; the scale/epilogue
        mechanics live in the base paged wrapper's run (one copy)."""
        if kv_cache_sf is not None:
            raise NotImplementedError(
                "kv_cache_sf fp8 scale factors: quantize the cache via "
                "flashinfer_tpu.page append helpers instead")
        if "return_lse" in kw:
            if not kw.pop("return_lse"):
                raise ValueError(
                    "BatchAttention.run always returns (out, lse) "
                    "(reference attention/_core.py:216); return_lse="
                    "False is not available — drop the kwarg")
        soft_cap = float(logits_soft_cap or 0.0)
        restore_plan = None
        if soft_cap != 0.0:
            # ADVICE r5 item 3: the verbatim reference caller varies the
            # cap per run; honor it instead of raising on the mismatch
            restore_plan = self._rebind_soft_cap(soft_cap)
        try:
            return super().run(
                q, paged_kv_cache, out=out, lse=lse, k_scale=k_scale,
                v_scale=v_scale, return_lse=True, **kw)
        finally:
            if restore_plan is not None:
                self._plan = restore_plan

    # rebind: the paged base class set `forward = run` to ITS run at
    # class-definition time; without this, forward() would skip the
    # (out, lse) holistic contract above (L001; ADVICE.md round-5 item 1)
    forward = run


class PODWithPagedKVCacheWrapper(BatchAttention):
    """Prefill-On-Decode (reference flashinfer/pod.py:61).  On TPU the
    holistic segment kernel already co-schedules prefill and decode work;
    this class exists for API parity and routes to BatchAttention (the
    reference POD run signature with separate prefill/decode operand
    sets is a CUDA-stream concept — documented alias, single-output
    run)."""

    def run(self, q, paged_kv_cache, *, return_lse: bool = False, **kw):
        out, lse = super().run(q, paged_kv_cache, **kw)
        return (out, lse) if return_lse else out

    # rebind so forward() honors THIS run's single-output contract
    # rather than the alias inherited from BatchAttention (L001)
    forward = run


def sink_epilogue(out, lse, sink, return_lse: bool):
    """Shared sink epilogue: renormalized output, and (optionally) the
    combined lse including the sink term — the ONE copy of this algebra
    (used by both the paged sink wrapper and the ragged custom-variant
    path)."""
    sink = jnp.asarray(sink)
    out = apply_attention_sink(out, lse, sink)
    if return_lse:
        lse_new = jnp.logaddexp(lse, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :], lse.shape))
        return out, lse_new
    return out


@jax.jit
def apply_attention_sink(
    out: jax.Array,  # [total_q, num_heads, head_dim]
    lse: jax.Array,  # [total_q, num_heads] natural-log LSE
    sink: jax.Array,  # [num_heads] per-head sink logits
) -> jax.Array:
    """Renormalize attention output as if a zero-value sink token with logit
    ``sink[h]`` participated in the softmax (StreamingLLM epilogue)."""
    lse32 = lse.astype(jnp.float32)
    sink32 = sink.astype(jnp.float32)[None, :]
    m = jnp.maximum(lse32, sink32)
    denom = jnp.exp(lse32 - m) + jnp.exp(sink32 - m)
    scale = jnp.exp(lse32 - m) / denom
    return (out.astype(jnp.float32) * scale[..., None]).astype(out.dtype)


class BatchAttentionWithAttentionSinkWrapper(
        BatchPrefillWithPagedKVCacheWrapper):
    """Paged attention + sink epilogue (reference attention/_core.py:330).

    Matches the reference's contract exactly: the class derives from the
    PAGED PREFILL wrapper (its plan's 4th positional is
    ``paged_kv_last_page_len``, NOT token lengths), the ctor accepts the
    reference kwargs (``q_data_type``/``kv_data_type``/``head_dim_qk``/
    ``head_dim_vo``/``window_left`` — window_left from the ctor is the
    plan default), and ``run`` accepts the custom-variant POSITIONAL
    extras in declared order: ``run(q, paged_kv_cache, sink, sm_scale)``
    (jit_args additional_tensor_names=["sink"],
    additional_scalar_names=["sm_scale"]).  A per-run ``sm_scale``
    rebinds the planned scale exactly (frozen-plan replace), mirroring
    the reference kernel's per-call scalar."""

    def __init__(self, float_workspace_buffer=None, kv_layout: str = "NHD",
                 use_cuda_graph: bool = False, backend: str = "auto",
                 q_data_type=None, kv_data_type=None,
                 head_dim_qk: int = 128, head_dim_vo: int = 128,
                 window_left: int = -1,
                 sink: Optional[jax.Array] = None, **kw):
        super().__init__(float_workspace_buffer, kv_layout, use_cuda_graph,
                         backend, **kw)
        self._sink = sink
        self._ctor_window_left = int(window_left)

    def set_sink(self, sink: jax.Array) -> None:
        self._sink = sink

    def plan(self, *args, window_left: Optional[int] = None, **kw):
        if window_left is None:
            window_left = self._ctor_window_left
        return super().plan(*args, window_left=window_left, **kw)

    def run(self, q, paged_kv_cache, *extra,
            sink: Optional[jax.Array] = None, sm_scale=None,
            out=None, lse=None, return_lse: bool = False, **kw):
        if extra:
            if sink is None:
                sink = extra[0]
            if len(extra) > 1 and sm_scale is None:
                sm_scale = extra[1]
            if len(extra) > 2:
                raise TypeError(
                    f"run() takes at most (sink, sm_scale) positional "
                    f"extras, got {len(extra)}")
        if out is not None or lse is not None:
            raise NotImplementedError(
                "pre-allocated out=/lse= buffers are not supported (XLA "
                "owns buffers; docs/migration.md) — drop the kwargs and "
                "use the returned arrays")
        s = sink if sink is not None else self._sink
        if s is None:
            raise ValueError("attention sink logits not provided")
        # per-call sm_scale (reference run scalar): the shared rebind
        # helper + the lazy-rebuild carry-over keep it alive on any path
        restore_plan = self._rebind_sm_scale(absolute=sm_scale)
        try:
            o, l = super().run(q, paged_kv_cache, return_lse=True, **kw)
        finally:
            if restore_plan is not None:
                self._plan = restore_plan
        return sink_epilogue(o, l, s, return_lse)

    # rebind: the base paged wrapper's `forward = run` alias was bound
    # to the BASE run at class-definition time — inherited as-is it
    # would silently skip the sink epilogue above (wrong numerics, no
    # error; the reference's deprecated forward dispatches through
    # self.run virtually, so ITS sink wrapper does apply the sink).
    # This was ADVICE.md round-5 item 1 / the motivating L001 shape.
    forward = run
