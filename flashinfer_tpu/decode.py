"""Decode attention: stateless single-request op + batch plan/run wrapper.

TPU-native re-design of the reference decode layer (``flashinfer/decode.py``):

- ``single_decode_with_kv_cache`` (reference decode.py:514)
- ``BatchDecodeWithPagedKVCacheWrapper`` (reference decode.py:710) with the
  canonical **plan()/run() lifecycle** (SURVEY §3.2): plan() runs host-side
  once per batch geometry and produces *padded, bucketed* index arrays (the
  TPU replacement for the reference's int-workspace offset arrays +
  CUDAGraph frozen shapes); run() is a pure jitted function over those
  arrays, so step-to-step replay never recompiles as long as the geometry
  bucket is stable.

Design notes vs the reference:
- No 128MB float workspace / 8MB int workspace: XLA owns scratch. The
  ``float_workspace_buffer`` constructor arg is accepted and ignored for
  API compatibility.
- No split-KV work estimation (scheduler.cuh:150): a TPU core walks KV
  sequentially with pipelined DMA; grid starvation doesn't exist here.
- ``use_tensor_cores`` is accepted and ignored: the Pallas kernel always
  packs the GQA group onto the MXU (decode.py:1629's tensor-core routing
  is the default and only path).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from flashinfer_tpu.api_logging import flashinfer_api
import numpy as np

from flashinfer_tpu.ops.flash_attention import flash_attention
from flashinfer_tpu.ops.paged_decode import paged_decode_attention
from flashinfer_tpu.ops.xla_ref import xla_paged_decode, xla_ragged_attention
from flashinfer_tpu.utils import (
    check_kv_layout,
    check_pos_encoding_mode,
    get_alibi_slopes,
    get_sm_scale,
    next_power_of_two,
    resolve_backend,
    TensorLayout,
)


@flashinfer_api
def single_decode_with_kv_cache(
    q: jax.Array,  # [num_qo_heads, head_dim]
    k: jax.Array,  # [kv_len, num_kv_heads, head_dim] (NHD) or HND
    v: jax.Array,
    kv_layout: str = "NHD",
    pos_encoding_mode: str = "NONE",
    use_tensor_cores: bool = False,
    sm_scale: Optional[float] = None,
    rope_scale: Optional[float] = None,
    rope_theta: Optional[float] = None,
    window_left: int = -1,
    logits_soft_cap: Optional[float] = None,
    return_lse: bool = False,
    backend: str = "auto",
    k_scale: Optional[float] = None,
    v_scale: Optional[float] = None,
):
    """Single-request decode attention (reference
    ``single_decode_with_kv_cache``, flashinfer/decode.py:514).

    ``k_scale``/``v_scale`` are the fp8 calibration scales (reference
    decode.py:640): k_scale folds into sm_scale, v_scale multiplies the
    output; sub-16-bit (fp8) k/v upcast losslessly before attention —
    the dequantized-value math of the reference's fp8 kernels.

    ``pos_encoding_mode="ROPE_LLAMA"`` applies RoPE to q at position
    ``kv_len-1`` and to k at positions ``0..kv_len-1`` before attention
    (the reference's fused-RoPE option, decode.cuh:217).
    ``pos_encoding_mode="ALIBI"`` adds ``slope_h * (kv_pos - (kv_len-1))``
    to the scaled logits (reference variants.cuh:68, slopes from
    ``get_alibi_slopes``) — served on the dense xla path."""
    check_pos_encoding_mode(pos_encoding_mode)  # typos raise, not fall through
    if check_kv_layout(kv_layout) == TensorLayout.HND:
        k = jnp.swapaxes(k, 0, 1)
        v = jnp.swapaxes(v, 0, 1)
    kv_len = k.shape[0]
    head_dim = q.shape[-1]
    sm_scale = get_sm_scale(head_dim, sm_scale)
    if k.dtype.itemsize < 2:  # fp8 cache: lossless upcast, scales fold
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    if k_scale is not None:
        sm_scale *= float(k_scale)
    if pos_encoding_mode == "ROPE_LLAMA":
        from flashinfer_tpu.rope import rotate_at_positions

        q = rotate_at_positions(
            q[None], jnp.array([kv_len - 1], jnp.int32),
            rope_scale or 1.0, rope_theta or 1e4,
        )[0]
        k = rotate_at_positions(
            k, jnp.arange(kv_len, dtype=jnp.int32),
            rope_scale or 1.0, rope_theta or 1e4,
        )
    backend = resolve_backend(backend, "single_decode")
    kw = {}
    if pos_encoding_mode == "ALIBI":
        backend = "xla"  # bias term lives on the dense reference path
        kw["alibi_slopes"] = get_alibi_slopes(q.shape[0])
    fn = flash_attention if backend == "pallas" else xla_ragged_attention
    qb = q[None]  # [1, H, D]
    seg_q = jnp.zeros((1,), jnp.int32)
    seg_kv = jnp.zeros((kv_len,), jnp.int32)
    out = fn(
        qb, k, v, seg_q, seg_kv,
        jnp.array([kv_len - 1], jnp.int32), jnp.arange(kv_len, dtype=jnp.int32),
        causal=False, sm_scale=sm_scale,
        logits_soft_cap=logits_soft_cap or 0.0, window_left=window_left,
        return_lse=return_lse, **kw,
    )
    o, l = (out[0][0], out[1][0]) if return_lse else (out[0], None)
    if v_scale is not None:
        o = (o.astype(jnp.float32) * float(v_scale)).astype(o.dtype)
    return (o, l) if return_lse else o


@dataclass(frozen=True)
class _DecodePlan:
    """Plan arrays for a batch-decode geometry (the TPU analogue of
    ``DecodePlanInfo``, scheduler.cuh:366)."""

    page_table: jax.Array  # [B_pad, P_bucket] int32
    kv_lens: jax.Array  # [B_pad] int32
    batch_size: int  # actual batch
    num_qo_heads: int
    num_kv_heads: int
    head_dim: int
    page_size: int
    sm_scale: float
    logits_soft_cap: float
    window_left: int
    q_data_type: object = None
    pos_encoding_mode: str = "NONE"
    alibi_slopes: object = None  # [num_qo_heads] f32, ALIBI mode only
    rope: object = None  # (rope_scale, rope_theta), ROPE_LLAMA mode only
    # split-KV partition (reference scheduler.cuh:150 DecodePlan split
    # work estimation, cost-model-chosen here): num_splits == 1 runs
    # the unsplit kernel; > 1 runs the partial-state kernel + merge
    # over these build_decode_split_units arrays
    num_splits: int = 1
    split_arrays: object = None  # dict of jnp scalar-prefetch arrays
    split_units: int = 0
    split_single_chunk: bool = False
    split_ppc: int = 0


_SPLIT_PROJECT_CACHE: list = []  # one-element AST-project cache
# (shape_key, batch, ctx, kv_itemsize) -> chosen S: the chooser (and
# its per-candidate L009 symbolic evaluations) is pure in these, and
# plan() sits on the serving replan path — growth is bounded by the
# pow2 geometry buckets the keys are built from
_SPLIT_CHOICE_CACHE: dict = {}


def _split_vmem_feasible(num_splits: int, shape_fields) -> bool:
    """Prune a split candidate through the L009 VMEM-feasibility
    evaluator: plug the candidate into the ``decode.splits`` knob
    launch binding (analysis/vmem_budget.KNOB_LAUNCHES) and evaluate
    the split launcher's own scratch arithmetic symbolically — only
    compilable tactics reach the cost-model comparison (ROADMAP item
    5's compose-them direction).  The evaluator is a LOWER bound, so
    False is a proof of infeasibility; anything unresolvable (or any
    analysis failure) keeps the candidate — pruning must never be a
    guess."""
    try:
        from flashinfer_tpu.analysis.core import Project
        from flashinfer_tpu.analysis.vmem_budget import (KNOB_LAUNCHES,
                                                         _estimate)
        from flashinfer_tpu.obs import hwspec
        from flashinfer_tpu.ops import paged_decode as _pd

        if not _SPLIT_PROJECT_CACHE:
            _SPLIT_PROJECT_CACHE.append(
                Project.from_paths([_pd.__file__]))
        est = _estimate(
            _SPLIT_PROJECT_CACHE[0], KNOB_LAUNCHES["decode.splits"],
            int(num_splits), [str(f) for f in shape_fields])
        if est is None:
            return True
        total, declared, _launcher = est
        budget = declared if declared is not None \
            else hwspec.current_spec().vmem_bytes
        return total <= budget
    except Exception:
        return True


class BatchDecodeWithPagedKVCacheWrapper:
    """Batched paged-KV decode with plan/run lifecycle (reference
    ``BatchDecodeWithPagedKVCacheWrapper``, flashinfer/decode.py:710).

    plan() host-side: converts ragged (indptr, indices, last_page_len) into a
    padded rectangular page table bucketed to powers of two — bounded
    recompile count replaces CUDAGraph shape freezing.  When the batch
    geometry sits on the short-context/large-batch decode cliff, plan()
    additionally partitions each request's KV into ``num_splits``
    chunk-aligned spans (split-KV decode, reference scheduler.cuh:150)
    — the factor is chosen by inverting the analytic cost model
    (``obs.costmodel.choose_decode_splits``) over L009-feasible
    candidates, overridable by the ``decode.splits`` autotune knob or
    the explicit ``num_splits=`` plan argument."""

    def __init__(
        self,
        float_workspace_buffer=None,  # accepted for API parity; unused
        kv_layout: str = "NHD",
        use_cuda_graph: bool = False,  # parity; shapes are bucketed regardless
        use_tensor_cores: bool = False,  # parity; MXU packing is always on
        backend: str = "auto",
        **_unused,
    ):
        check_kv_layout(kv_layout)
        self._kv_layout = kv_layout
        self._backend = backend
        self._plan: Optional[_DecodePlan] = None

    def plan(
        self,
        indptr,  # [B+1] host array: page-table offsets
        indices,  # [total_pages] host array: page ids
        last_page_len,  # [B] host array
        num_qo_heads: int,
        num_kv_heads: int,
        head_dim: int,
        page_size: int,
        pos_encoding_mode: str = "NONE",
        window_left: int = -1,
        logits_soft_cap: Optional[float] = None,
        q_data_type=None,  # when given, run() validates q.dtype against it
        kv_data_type=None,
        data_type=None,
        sm_scale: Optional[float] = None,
        rope_scale: Optional[float] = None,
        rope_theta: Optional[float] = None,
        non_blocking: bool = True,
        seq_lens=None,
        *,
        num_splits: Optional[int] = None,  # split-KV factor; None = auto
        # keyword-only: beyond the reference plan() arity (L002) — a
        # verbatim reference call never reaches it
    ) -> None:
        check_pos_encoding_mode(pos_encoding_mode)  # typos raise KeyError
        from flashinfer_tpu import native, obs

        replan = self._plan is not None
        indptr = np.asarray(indptr)
        indices = np.asarray(indices)
        last_page_len = np.asarray(last_page_len)
        batch = len(indptr) - 1
        pages_per_req = indptr[1:] - indptr[:-1]

        # bucketed padding: bounded set of compiled shapes; table build in
        # the native planner (csrc/planner.cpp decode_plan)
        p_bucket = max(next_power_of_two(int(pages_per_req.max(initial=1))), 8)
        b_bucket = max(next_power_of_two(batch), 8)
        table, kv_lens_pad = native.decode_plan(
            indptr, indices, last_page_len, page_size, b_bucket, p_bucket
        )

        # ---- split-KV partitioning (HND fused-heads path only; the
        # dense ALIBI/ROPE routes and NHD never consult it) ----------------
        split_kw = dict(num_splits=1, split_arrays=None, split_units=0,
                        split_single_chunk=False, split_ppc=0)
        split_eligible = (self._kv_layout == "HND"
                          and pos_encoding_mode == "NONE")
        if not split_eligible and num_splits is not None \
                and int(num_splits) > 1:
            # an explicit request that cannot be honored must not be
            # silently downgraded to the unsplit path
            raise ValueError(
                f"num_splits={num_splits} requires kv_layout='HND' and "
                f"pos_encoding_mode='NONE' (got {self._kv_layout!r}, "
                f"{pos_encoding_mode!r}) — the split kernel is the HND "
                "fused-heads path only")
        if split_eligible:
            from flashinfer_tpu.ops.paged_decode import (
                build_decode_split_units, decode_split_tactic_key,
                split_pages_per_chunk)

            kv_itemsize = (jnp.dtype(kv_data_type).itemsize
                           if kv_data_type is not None else 2)
            ppc = split_pages_per_chunk(page_size, num_kv_heads,
                                        head_dim, kv_itemsize)
            key_dtype = jnp.dtype(q_data_type) if q_data_type \
                else (jnp.dtype(data_type) if data_type else "bfloat16")
            shape_key = decode_split_tactic_key(
                b_bucket, p_bucket, num_qo_heads, num_kv_heads,
                head_dim, page_size, ppc, key_dtype)
            S = num_splits
            if S is None:
                # knob first (measured winner / user override), then the
                # analytic cost model over L009-feasible candidates
                from flashinfer_tpu.autotuner import AutoTuner

                S = AutoTuner.get().lookup("decode.splits", shape_key,
                                           default=None)
            if S is None:
                ctx = int(np.asarray(kv_lens_pad).max(initial=0))
                cache_key = (shape_key, batch, ctx, kv_itemsize)
                S = _SPLIT_CHOICE_CACHE.get(cache_key)
                if S is None:
                    try:
                        from flashinfer_tpu.obs import costmodel, hwspec

                        S, _table = costmodel.choose_decode_splits(
                            batch, ctx, num_qo_heads, num_kv_heads,
                            head_dim,
                            hbm_tbps=hwspec.current_spec().hbm_tbps,
                            page_size=page_size, pages_per_chunk=ppc,
                            kv_bytes=kv_itemsize,
                            feasible=lambda s: _split_vmem_feasible(
                                s, shape_key))
                    except Exception:
                        S = 1  # selection must never cost a plan
                    _SPLIT_CHOICE_CACHE[cache_key] = S
            S = max(int(S), 1)
            if S > 1:
                sp = build_decode_split_units(
                    table, kv_lens_pad, num_splits=S,
                    page_size=page_size, pages_per_chunk=ppc)
                sp.pop("stats")
                split_kw = dict(
                    num_splits=sp.pop("num_splits"),
                    split_units=sp.pop("num_units"),
                    split_single_chunk=sp.pop("single_chunk"),
                    split_ppc=sp.pop("pages_per_chunk"),
                    split_arrays={k: jnp.asarray(v)
                                  for k, v in sp.items()},
                )
            obs.counter_inc("plan.decode_splits",
                            wrapper=type(self).__name__, splits=str(S))

        self._plan = _DecodePlan(
            page_table=jnp.asarray(table),
            kv_lens=jnp.asarray(kv_lens_pad),
            batch_size=batch,
            num_qo_heads=num_qo_heads,
            num_kv_heads=num_kv_heads,
            head_dim=head_dim,
            page_size=page_size,
            sm_scale=get_sm_scale(head_dim, sm_scale),
            logits_soft_cap=logits_soft_cap or 0.0,
            window_left=window_left,
            q_data_type=jnp.dtype(q_data_type) if q_data_type else None,
            pos_encoding_mode=pos_encoding_mode,
            # slopes are plan-derived: computed once here, not per decode
            # step in run()
            alibi_slopes=(
                get_alibi_slopes(num_qo_heads)
                if pos_encoding_mode == "ALIBI" else None
            ),
            rope=(
                (rope_scale or 1.0, rope_theta or 1e4)
                if pos_encoding_mode == "ROPE_LLAMA" else None
            ),
            **split_kw,
        )
        # plan-lifecycle metrics (obs catalog plan.*): bucketed-padding
        # waste is the recompile-bound trade-off this plan makes — the
        # batch axis pads to b_bucket, the page table to b_bucket x
        # p_bucket slots vs len(indices) real pages
        obs.record_plan(
            self, replan=replan,
            padded_vs_actual=(
                ("batch", b_bucket, batch),
                ("pages", b_bucket * p_bucket, int(indices.size)),
            ),
            # flight recorder (FLASHINFER_TPU_SPANS): a replan whose
            # frozen statics moved forces a fresh kernel compile on the
            # next run() — the diff names the exact static that changed
            statics=self._plan,
        )

    def run(
        self,
        q: jax.Array,  # [batch, num_qo_heads, head_dim]
        paged_kv_cache: Union[Tuple[jax.Array, jax.Array], jax.Array],
        *,
        q_scale: Optional[float] = None,
        k_scale: Optional[float] = None,
        v_scale: Optional[float] = None,
        return_lse: bool = False,
    ):
        """Run decode attention for the planned geometry (reference
        ``run``, decode.py:1810).  Scale factors fold into sm_scale / output
        exactly as the reference does (decode.py:2004)."""
        plan = self._plan
        if plan is None:
            raise RuntimeError("plan() must be called before run()")
        if isinstance(paged_kv_cache, tuple):
            k_cache, v_cache = paged_kv_cache
        else:
            k_cache, v_cache = paged_kv_cache[:, 0], paged_kv_cache[:, 1]
        batch = q.shape[0]
        assert batch == plan.batch_size, (
            f"q batch {batch} != planned {plan.batch_size}"
        )
        if (
            plan.num_qo_heads != q.shape[1]
            or plan.head_dim != q.shape[2]
        ):
            raise ValueError(
                f"q shape {q.shape[1:]} != planned heads/dim "
                f"({plan.num_qo_heads}, {plan.head_dim})"
            )
        if plan.q_data_type is not None and q.dtype != plan.q_data_type:
            raise ValueError(
                f"q dtype {q.dtype} != planned q_data_type "
                f"{plan.q_data_type} (reference decode.py:1916 validation)"
            )
        sm_scale = plan.sm_scale
        if q_scale is not None:
            sm_scale *= q_scale
        if k_scale is not None:
            sm_scale *= k_scale

        b_pad = plan.page_table.shape[0]
        if b_pad != batch:
            q = jnp.pad(q, ((0, b_pad - batch), (0, 0), (0, 0)))

        backend = resolve_backend(self._backend, "batch_decode")
        alibi_kw = {}
        if plan.alibi_slopes is not None:
            # ALiBi rides the dense xla path (the bias term is not a
            # Pallas-kernel mode); reference decode qo position = last
            backend = "xla"
            alibi_kw["alibi_slopes"] = plan.alibi_slopes
        if plan.rope is not None:
            # in-attention RoPE over an UNROTATED cache: the dense path
            # rotates gathered keys at their positions (decode.cuh:217)
            backend = "xla"
            alibi_kw["rope"] = plan.rope
        if backend == "pallas" and plan.num_splits > 1:
            # split-KV path: partial-state kernel over the plan's work
            # units + merge_states reduction (plan-time cost-model
            # choice; the arrays were built by build_decode_split_units
            # in plan())
            from flashinfer_tpu.ops.paged_decode import (
                paged_decode_attention_split)

            out = paged_decode_attention_split(
                q, k_cache, v_cache, plan.split_arrays,
                num_units=plan.split_units,
                num_splits=plan.num_splits,
                single_chunk=plan.split_single_chunk,
                pages_per_chunk=plan.split_ppc,
                sm_scale=sm_scale,
                logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left,
                return_lse=return_lse,
            )
        elif backend == "pallas":
            # autotuned pages-per-chunk (reference AutoTuner.choose_one role;
            # zero overhead outside an autotune() context — cached/default)
            from flashinfer_tpu.autotuner import AutoTuner
            from flashinfer_tpu.ops import paged_decode as _pd_module

            ppc_default = max(1, min(512 // plan.page_size, 16))
            candidates = sorted({
                max(1, min(c // plan.page_size, 64))
                for c in (128, 256, 512, 1024)
            })
            # one shape key + one runner shared by both tactic tuners and
            # the final call — a plan field added to
            # decode_tactic_key reaches all three AND the model decode
            # paths identically
            from flashinfer_tpu.ops.paged_decode import decode_tactic_key

            shape_key = decode_tactic_key(
                plan.page_table.shape[0], plan.page_table.shape[1],
                plan.num_qo_heads, plan.num_kv_heads, plan.head_dim,
                plan.page_size, q.dtype,
            )

            def _run(ppc_, csp_):
                return paged_decode_attention(
                    q, k_cache, v_cache, plan.page_table, plan.kv_lens,
                    sm_scale=sm_scale, logits_soft_cap=plan.logits_soft_cap,
                    window_left=plan.window_left, kv_layout=self._kv_layout,
                    pages_per_chunk=ppc_, return_lse=return_lse,
                    cross_step_prefetch=csp_,
                )

            ppc = AutoTuner.get().choose_one(
                "paged_decode.pages_per_chunk", shape_key, candidates,
                lambda c: (lambda: _run(c, False)),
                default=ppc_default,
                module=_pd_module,
            )
            # second tactic: next-request chunk-0 prefetch.  "static" hides
            # the per-request cold-start DMA stall with compile-time slot
            # indices (see _decode_kernel_fused_heads); "off" keeps the
            # stall.  Default static BY MEASUREMENT (2026-07-31 A/B,
            # before PR 1, script since deleted: bit-identical outputs and
            # +1-2.4% everywhere measured, 0.713->0.728 TB/s at the
            # headline shape).  The dynamic SMEM-parity variant measured
            # losing on v5e (0.68 vs 0.75 TB/s) and is env-only.
            pf = AutoTuner.get().choose_one(
                "paged_decode.prefetch", shape_key, ["static", "off"],
                lambda c: (lambda: _run(
                    int(ppc), "static" if c == "static" else False)),
                default="static",
                module=_pd_module,
            ) if self._kv_layout == "HND" else "off"
            csp = "static" if pf == "static" else False

            out = _run(int(ppc), csp)
        if backend != "pallas":
            out = xla_paged_decode(
                q, k_cache, v_cache, plan.page_table, plan.kv_lens,
                sm_scale=sm_scale, logits_soft_cap=plan.logits_soft_cap,
                window_left=plan.window_left, return_lse=return_lse,
                kv_layout=self._kv_layout, **alibi_kw,
            )
        if return_lse:
            o, lse = out
            if v_scale is not None:
                o = (o.astype(jnp.float32) * v_scale).astype(o.dtype)
            return o[:batch], lse[:batch]
        if v_scale is not None:
            out = (out.astype(jnp.float32) * v_scale).astype(out.dtype)
        return out[:batch]

    forward = run  # legacy alias kept by the reference

    def run_return_lse(self, q, paged_kv_cache, **kw):
        """Reference ``run_return_lse`` (decode.py:2266,
        functools.partialmethod(run, return_lse=True)): run with the
        natural-log LSE returned alongside the output."""
        kw.pop("return_lse", None)
        return self.run(q, paged_kv_cache, return_lse=True, **kw)

    forward_return_lse = run_return_lse  # reference legacy alias

    def end_forward(self) -> None:  # reference legacy no-op
        pass
