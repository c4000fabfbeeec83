"""MLA (DeepSeek Multi-Latent Attention) wrapper.

TPU re-design of ``flashinfer/mla/_core.py:1397``
(``BatchMLAPagedAttentionWrapper``, plan :1568 / run :1742): paged attention
over compressed KV (ckv head_dim 512 + kpe head_dim 64) with MQA-shaped
sharing across query heads.

Two execution paths, selected by the planned qo lengths:
- all qo_len == 1 -> the MLA decode Pallas kernel (ops/mla_decode.py);
- otherwise (speculative multi-token / chunked prefill) -> gather the
  planned pages into flattened ragged K/V and run the segment flash kernel
  with q = [q_nope | q_pe], k = [ckv | kpe], v = ckv (prefill is
  compute-bound; the gather pass is the documented v1 trade-off, as for
  paged batch prefill).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flashinfer_tpu.ops.flash_attention import flash_attention
from flashinfer_tpu.ops.mla_decode import (
    mla_paged_decode_attention,
    xla_mla_paged_decode,
)
from flashinfer_tpu.ops.xla_ref import xla_ragged_attention
from flashinfer_tpu.utils import next_power_of_two, resolve_backend


@dataclass(frozen=True)
class _MLAPlan:
    decode_mode: bool
    causal: bool
    sm_scale: float
    num_heads: int
    head_dim_ckv: int
    head_dim_kpe: int
    page_size: int
    batch_size: int
    # decode-mode arrays
    page_table: Optional[jax.Array] = None
    kv_lens: Optional[jax.Array] = None
    # ragged-mode arrays
    q_seg: Optional[jax.Array] = None
    q_pos: Optional[jax.Array] = None
    kv_seg: Optional[jax.Array] = None
    kv_pos: Optional[jax.Array] = None
    kv_rows: Optional[jax.Array] = None
    total_q: int = 0
    tq_pad: int = 0


class BatchMLAPagedAttentionWrapper:
    """plan/run MLA attention (reference mla/_core.py:1397)."""

    def __init__(self, float_workspace_buffer=None, backend: str = "auto",
                 **_unused):
        self._backend = backend
        self._plan: Optional[_MLAPlan] = None

    def plan(
        self,
        qo_indptr,  # [B+1]
        kv_indptr,  # [B+1] page-table offsets
        kv_indices,  # [total_pages]
        kv_len_arr,  # [B] kv token lengths
        num_heads: int,
        head_dim_ckv: int,
        head_dim_kpe: int,
        page_size: int,
        causal: bool = False,
        sm_scale: Optional[float] = None,
        q_data_type=jnp.bfloat16,
        kv_data_type=None,
        use_profiler: bool = False,
        **_unused,
    ) -> None:
        qo_indptr = np.asarray(qo_indptr)
        kv_indptr = np.asarray(kv_indptr)
        kv_indices = np.asarray(kv_indices)
        kv_len = np.asarray(kv_len_arr).astype(np.int64)
        batch = len(qo_indptr) - 1
        qo_lens = qo_indptr[1:] - qo_indptr[:-1]
        if sm_scale is None:
            sm_scale = 1.0 / float(head_dim_ckv + head_dim_kpe) ** 0.5

        if (qo_lens == 1).all():
            from flashinfer_tpu import native

            pages_per_req = kv_indptr[1:] - kv_indptr[:-1]
            p_bucket = max(next_power_of_two(int(pages_per_req.max(initial=1))), 8)
            b_bucket = max(next_power_of_two(batch), 8)
            # decode_plan builds the padded table; token lengths come from
            # the caller's kv_len_arr directly (last_page_len arg unused for
            # lengths here, so pass a valid placeholder)
            table, lens = native.decode_plan(
                kv_indptr, kv_indices, np.ones(batch, np.int32), page_size,
                b_bucket, p_bucket,
            )
            lens[:batch] = kv_len
            self._plan = _MLAPlan(
                decode_mode=True, causal=causal, sm_scale=float(sm_scale),
                num_heads=num_heads, head_dim_ckv=head_dim_ckv,
                head_dim_kpe=head_dim_kpe, page_size=page_size,
                batch_size=batch,
                page_table=jnp.asarray(table), kv_lens=jnp.asarray(lens),
            )
            return

        # ragged mode: flatten tokens with segments (same scheme as prefill),
        # built by the native planner
        from flashinfer_tpu import native

        total_q = int(qo_indptr[-1])
        kv_tok_indptr = np.concatenate([[0], np.cumsum(kv_len)])
        total_kv = int(kv_tok_indptr[-1])
        tq_pad = max(next_power_of_two(total_q), 128)
        tkv_pad = max(next_power_of_two(total_kv), 128)
        q_seg, q_pos = native.token_axis_plan(
            qo_indptr, kv_len - qo_lens, tq_pad, -1
        )
        kv_seg, kv_pos = native.token_axis_plan(
            kv_tok_indptr, np.zeros(batch, np.int64), tkv_pad, -2
        )
        rows = native.paged_gather_plan(
            kv_tok_indptr, kv_indptr, kv_indices, page_size, tkv_pad
        )
        self._plan = _MLAPlan(
            decode_mode=False, causal=causal, sm_scale=float(sm_scale),
            num_heads=num_heads, head_dim_ckv=head_dim_ckv,
            head_dim_kpe=head_dim_kpe, page_size=page_size, batch_size=batch,
            q_seg=jnp.asarray(q_seg), q_pos=jnp.asarray(q_pos),
            kv_seg=jnp.asarray(kv_seg), kv_pos=jnp.asarray(kv_pos),
            kv_rows=jnp.asarray(rows, dtype=jnp.int32),
            total_q=total_q, tq_pad=tq_pad,
        )

    def run(
        self,
        q_nope: jax.Array,  # [total_q, num_heads, head_dim_ckv]
        q_pe: jax.Array,  # [total_q, num_heads, head_dim_kpe]
        ckv_cache: jax.Array,  # [num_pages, page_size, head_dim_ckv]
        kpe_cache: jax.Array,  # [num_pages, page_size, head_dim_kpe]
        *,
        return_lse: bool = False,
    ):
        plan = self._plan
        if plan is None:
            raise RuntimeError("plan() must be called before run()")
        backend = resolve_backend(self._backend, "batch_mla")
        if ckv_cache.shape[0] == 0:
            # empty cache (every request has kv_len == 0): attention over
            # the empty set — zero output, lse = log(0) (the reference
            # kernel returns zeros and its tests skip the lse check,
            # test_deepseek_mla.py:630)
            n = plan.batch_size if plan.decode_mode else plan.total_q
            out = jnp.zeros((n, plan.num_heads, plan.head_dim_ckv),
                            q_nope.dtype)
            if return_lse:
                return out, jnp.full((n, plan.num_heads), -jnp.inf,
                                     jnp.float32)
            return out
        if plan.decode_mode:
            b_pad = plan.page_table.shape[0]
            if q_nope.shape[0] != b_pad:
                pad = b_pad - q_nope.shape[0]
                q_nope = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
                q_pe = jnp.pad(q_pe, ((0, pad), (0, 0), (0, 0)))
            if backend == "pallas":
                # autotuned scratch layout: "split" (two buffers, two
                # score dots — the hardware-validated default) vs
                # "packed" (one [chunk, 640] buffer, one concatenated
                # dot; same DMA queue depth).  Shipped-config/default
                # outside an autotune() context, like decode's ppc.
                from flashinfer_tpu.autotuner import AutoTuner
                from flashinfer_tpu.ops import mla_decode as _mla_module

                key = (
                    plan.page_table.shape[0], plan.page_table.shape[1],
                    plan.num_heads, plan.head_dim_ckv, plan.head_dim_kpe,
                    plan.page_size, str(q_nope.dtype),
                )

                def _run(layout_):
                    return mla_paged_decode_attention(
                        q_nope, q_pe, ckv_cache, kpe_cache,
                        plan.page_table, plan.kv_lens,
                        sm_scale=plan.sm_scale, return_lse=return_lse,
                        layout=layout_,
                    )

                layout = AutoTuner.get().choose_one(
                    "mla_decode.layout", key, ["split", "packed"],
                    lambda c: (lambda: _run(c)),
                    default="split",
                    module=_mla_module,
                )
                out = _run(str(layout))
            else:
                out = xla_mla_paged_decode(
                    q_nope, q_pe, ckv_cache, kpe_cache, plan.page_table,
                    plan.kv_lens, sm_scale=plan.sm_scale,
                    return_lse=return_lse,
                )
            if return_lse:
                return out[0][: plan.batch_size], out[1][: plan.batch_size]
            return out[: plan.batch_size]

        # ragged path: gather + segment flash with asymmetric head dims
        ckv_rows = ckv_cache.reshape(-1, plan.head_dim_ckv)[plan.kv_rows]
        # kpe cache may be lane-padded to 128 (TPU-native layout): slice back
        kpe_rows = kpe_cache.reshape(-1, kpe_cache.shape[-1])[plan.kv_rows][
            :, : plan.head_dim_kpe
        ]
        k = jnp.concatenate([ckv_rows, kpe_rows], axis=-1)[:, None, :]  # MQA
        v = ckv_rows[:, None, :]
        q = jnp.concatenate(
            [q_nope.astype(jnp.float32), q_pe.astype(jnp.float32)], axis=-1
        ).astype(q_nope.dtype)
        if q.shape[0] != plan.tq_pad:
            q = jnp.pad(q, ((0, plan.tq_pad - q.shape[0]), (0, 0), (0, 0)))
        fn = flash_attention if backend == "pallas" else xla_ragged_attention
        out = fn(
            q, k, v, plan.q_seg, plan.kv_seg, plan.q_pos, plan.kv_pos,
            causal=plan.causal, sm_scale=plan.sm_scale, return_lse=return_lse,
        )
        if return_lse:
            return out[0][: plan.total_q], out[1][: plan.total_q]
        return out[: plan.total_q]

    def run_sparse(
        self,
        q_nope: jax.Array,  # [batch, num_heads, head_dim_ckv]
        q_pe: jax.Array,  # [batch, num_heads, head_dim_kpe]
        ckv_cache: jax.Array,
        kpe_cache: jax.Array,
        sparse_rows: jax.Array,  # [batch, k] flat cache rows (from
        # topk.top_k_page_table_transform), padded entries < 0
        *,
        sm_scale: Optional[float] = None,
        return_lse: bool = False,
    ):
        """Top-k sparse MLA decode (the DSv3.2 sparse-MLA path, reference
        ``flashinfer/mla/_sparse_mla_sm120.py`` + sparse_mla bindings):
        attention restricted to the top-k selected KV tokens per request.
        Selection comes from ``flashinfer_tpu.topk.top_k_page_table_transform``
        over per-token proxy scores; rows < 0 are masked padding.
        ``sm_scale`` is the model's softmax scale; the default is a
        placeholder (see below) and matches no published model."""
        d_ckv = ckv_cache.shape[-1]
        if sm_scale is None:
            # A PLACEHOLDER, no published model's scale: the absorbed query
            # is ``d_ckv + d_kpe`` wide, but a model scales by its own
            # per-head query/key width (GLM-5: 1/sqrt(192 + 64); DeepSeek-V3:
            # 1/sqrt(128 + 64)).  A caller that serves a model passes its
            # ``attention_scale`` (``models/glm_dsa.py`` does).
            sm_scale = 1.0 / float(d_ckv + q_pe.shape[-1]) ** 0.5
        return _sparse_mla_decode(
            q_nope, q_pe, ckv_cache, kpe_cache, sparse_rows,
            sm_scale=float(sm_scale), return_lse=return_lse,
        )

    forward = run

    def end_forward(self) -> None:
        pass


# legacy alias kept by the reference for its earlier MLA API generation
BatchDecodeMlaWithPagedKVCacheWrapper = BatchMLAPagedAttentionWrapper


@functools.partial(jax.jit, static_argnames=("sm_scale", "return_lse"))
def _sparse_mla_decode(
    q_nope, q_pe, ckv_cache, kpe_cache, sparse_rows,
    *, sm_scale: float, return_lse: bool,
):
    """Gather the selected latent rows and attend over them
    (``ops/sparse_mla.py sparse_mla_attend``, the function the serving
    engine's latent layer calls too), here in float32 over the wrapper's
    separate ckv / kpe caches."""
    from flashinfer_tpu.ops.sparse_mla import sparse_mla_attend

    d_ckv = q_nope.shape[-1]
    rows = jnp.maximum(sparse_rows, 0)
    ckv = ckv_cache.reshape(-1, d_ckv)[rows].astype(jnp.float32)  # [B,k,d]
    kpe = kpe_cache.reshape(-1, kpe_cache.shape[-1])[rows].astype(jnp.float32)
    kpe = kpe[..., : q_pe.shape[-1]]  # drop TPU lane padding if present
    q = jnp.concatenate([q_nope, q_pe], -1).astype(jnp.float32)
    out = sparse_mla_attend(
        q, jnp.concatenate([ckv, kpe], -1), sparse_rows >= 0, d_v=d_ckv,
        sm_scale=sm_scale, return_lse=return_lse)
    if return_lse:
        return out[0].astype(q_nope.dtype), out[1]
    return out.astype(q_nope.dtype)


def project_latents(x, layer, positions, *, num_heads: int, nope: int,
                    rope: int, ckv: int, eps: float, rope_theta: float,
                    interleave: bool = False):
    """The MLA projections of normed rows ``x`` ``[N, hidden]`` on a flat
    token axis, the one definition every MLA model here uses
    (``models/deepseek.py``, ``models/glm_dsa.py``): ``c_q = RMSNorm(x
    W_qa)``; ``q = c_q W_qb`` -> per head ``nope`` + ``rope`` dims, the rope
    part rotated at ``positions``; ``[c_kv | k_pe] = x W_kva``, ``c_kv``
    normed, ``k_pe`` rotated and shared by all heads.  Returns ``(c_q [N,
    q_lora], q_nope [N, H, nope], q_pe [N, H, rope], c_kv [N, ckv], k_pe [N,
    rope])``; the cache row is ``[c_kv | k_pe]``."""
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.rope import apply_rope_pos_ids

    n = x.shape[0]
    c_q = rmsnorm(x @ layer["q_a"], layer["q_a_norm"], eps)
    q = (c_q @ layer["q_b"]).reshape(n, num_heads, nope + rope)
    kv = x @ layer["kv_a"]  # [N, ckv + rope]
    c_kv = rmsnorm(kv[:, :ckv], layer["kv_a_norm"], eps)
    q_pe, k_pe = apply_rope_pos_ids(
        q[..., nope:], kv[:, None, ckv:], positions, rope_theta=rope_theta,
        interleave=interleave)
    return c_q, q[..., :nope], q_pe, c_kv, k_pe[:, 0]
