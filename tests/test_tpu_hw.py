"""Real-TPU correctness tier at serving shapes (Llama-3-8B geometry:
32 q heads / 8 kv heads, head_dim 128, ctx 4k, bf16).

The smoke tier (test_tpu_smoke.py) proves each kernel Mosaic-compiles;
this tier is the TPU analogue of the reference's GPU-correctness tests
(tests/attention/test_batch_prefill_kernels.py): oracle comparison at the
shapes the benchmarks run.  Auto-skips off-TPU; on the chip it runs in
ONE process without xdist (test_tpu_smoke.py's header has the command),
under the caller's timeout.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import flashinfer_tpu as fi
from flashinfer_tpu.testing import attention_ref

pytestmark = pytest.mark.tpu_only

HQ, HKV, D = 32, 8, 128
BF16_TOL = dict(rtol=3e-2, atol=3e-2)


def test_flash_ragged_prefill_llama_shape():
    from flashinfer_tpu.ops import flash_attention

    T = 4096
    q = jax.random.normal(jax.random.PRNGKey(0), (T, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (T, HKV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (T, HKV, D), jnp.bfloat16)
    seg = jnp.zeros((T,), jnp.int32)
    pos = jnp.arange(T)
    out = flash_attention(
        q, k, v, seg, seg, pos, pos, causal=True, sm_scale=D ** -0.5
    )
    ref = attention_ref(q, k, v, causal=True, sm_scale=D ** -0.5)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **BF16_TOL
    )


def test_paged_decode_llama_shape():
    from flashinfer_tpu.ops import paged_decode_attention, xla_paged_decode

    B, PS, ctx = 16, 16, 4096
    ppr = ctx // PS
    npages = B * ppr
    pt = jnp.asarray(
        np.random.default_rng(0).permutation(npages).astype(np.int32)
    ).reshape(B, ppr)
    lens = jnp.asarray(
        np.random.default_rng(1).integers(1, ctx + 1, B).astype(np.int32)
    )
    kc = jax.random.normal(
        jax.random.PRNGKey(0), (npages, HKV, PS, D), jnp.bfloat16
    )
    vc = jax.random.normal(
        jax.random.PRNGKey(1), (npages, HKV, PS, D), jnp.bfloat16
    )
    q = jax.random.normal(jax.random.PRNGKey(2), (B, HQ, D), jnp.bfloat16)
    o = paged_decode_attention(
        q, kc, vc, pt, lens, sm_scale=D ** -0.5, kv_layout="HND"
    )
    ref = xla_paged_decode(
        q, jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2), pt, lens,
        sm_scale=D ** -0.5,
    )
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32), **BF16_TOL
    )


def test_paged_decode_static_prefetch_on_chip():
    """The static-parity next-request prefetch (default tactic since
    2026-07-31) must be BIT-IDENTICAL to the plain kernel on hardware —
    mixed even/odd/zero chunk counts exercise the scalar-derived
    warmup/epilogue DMA handshake."""
    from flashinfer_tpu.ops import paged_decode_attention

    B, PS, ctx = 16, 16, 4096
    ppr = ctx // PS
    npages = B * ppr
    pt = jnp.asarray(
        np.random.default_rng(0).permutation(npages).astype(np.int32)
    ).reshape(B, ppr)
    lens_np = np.random.default_rng(1).integers(0, ctx + 1, B)
    lens_np[0] = ctx  # even chunk count at the tuned ppc
    lens_np[1] = 0    # zero-length request mid-batch
    lens = jnp.asarray(lens_np.astype(np.int32))
    kc = jax.random.normal(
        jax.random.PRNGKey(0), (npages, HKV, PS, D), jnp.bfloat16
    )
    vc = jax.random.normal(
        jax.random.PRNGKey(1), (npages, HKV, PS, D), jnp.bfloat16
    )
    q = jax.random.normal(jax.random.PRNGKey(2), (B, HQ, D), jnp.bfloat16)
    outs = {}
    for mode in (False, "static"):
        outs[mode] = paged_decode_attention(
            q, kc, vc, pt, lens, sm_scale=D ** -0.5, kv_layout="HND",
            pages_per_chunk=16, cross_step_prefetch=mode,
        )
    np.testing.assert_array_equal(
        np.asarray(outs[False], np.float32),
        np.asarray(outs["static"], np.float32),
    )


def test_fused_paged_prefill_llama_shape():
    """First-class hardware check of the work-unit fused prefill kernel
    (ops/paged_prefill.py) against the gather+flash path, mixed ragged
    batch with append semantics."""
    from flashinfer_tpu.ops.paged_prefill import (
        build_prefill_work_units, fused_paged_prefill,
    )

    PS = 16
    rng = np.random.default_rng(0)
    qo_lens = [512, 128, 1024, 37]
    kv_lens = [1024, 512, 2048, 333]
    qo_indptr = np.concatenate([[0], np.cumsum(qo_lens)]).astype(np.int32)
    pages_per = [int(np.ceil(l / PS)) for l in kv_lens]
    kv_page_indptr = np.concatenate([[0], np.cumsum(pages_per)]).astype(np.int32)
    npages = int(kv_page_indptr[-1])
    kv_page_indices = rng.permutation(npages).astype(np.int32)

    total_q = int(qo_indptr[-1])
    q = jax.random.normal(jax.random.PRNGKey(0), (total_q, HQ, D), jnp.bfloat16)
    kc = jax.random.normal(
        jax.random.PRNGKey(1), (npages, HKV, PS, D), jnp.bfloat16
    )
    vc = jax.random.normal(
        jax.random.PRNGKey(2), (npages, HKV, PS, D), jnp.bfloat16
    )

    plan_np = build_prefill_work_units(
        qo_indptr, kv_page_indptr, kv_page_indices,
        np.asarray(kv_lens, np.int32), block_q=128, pages_per_chunk=8,
        page_size=PS,
    )
    num_units = plan_np.pop("num_units")
    plan_np.pop("block_q"), plan_np.pop("pages_per_chunk")
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    out = fused_paged_prefill(
        q, kc, vc, plan, num_units=num_units, block_q=128, pages_per_chunk=8,
        sm_scale=D ** -0.5, causal=True,
    )

    # oracle: per-request dense attention with append (bottom-right) causal
    for r in range(len(qo_lens)):
        qs, qe = int(qo_indptr[r]), int(qo_indptr[r + 1])
        pages = kv_page_indices[kv_page_indptr[r]:kv_page_indptr[r + 1]]
        kr = np.asarray(kc, np.float32)[pages]  # [p, HKV, PS, D]
        vr = np.asarray(vc, np.float32)[pages]
        kr = kr.transpose(0, 2, 1, 3).reshape(-1, HKV, D)[: kv_lens[r]]
        vr = vr.transpose(0, 2, 1, 3).reshape(-1, HKV, D)[: kv_lens[r]]
        qr = np.asarray(q, np.float32)[qs:qe]
        qpos = kv_lens[r] - qo_lens[r] + np.arange(qo_lens[r])
        kpos = np.arange(kv_lens[r])
        kg = np.repeat(kr, HQ // HKV, axis=1)
        vg = np.repeat(vr, HQ // HKV, axis=1)
        s = np.einsum("qhd,khd->hqk", qr, kg) * (D ** -0.5)
        s = np.where(kpos[None, None, :] <= qpos[None, :, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref_r = np.einsum("hqk,khd->qhd", p, vg)
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[qs:qe], ref_r, **BF16_TOL
        )


def test_mla_decode_deepseek_shape():
    from flashinfer_tpu.ops.mla_decode import (
        mla_paged_decode_attention, xla_mla_paged_decode,
    )

    B, H, d_ckv, d_kpe, PS, ctx = 4, 128, 512, 64, 16, 2048
    ppr = ctx // PS
    npages = B * ppr
    ckv = jax.random.normal(
        jax.random.PRNGKey(0), (npages, PS, d_ckv), jnp.bfloat16
    )
    kpe = jax.random.normal(
        jax.random.PRNGKey(1), (npages, PS, d_kpe), jnp.bfloat16
    )
    qn = jax.random.normal(jax.random.PRNGKey(2), (B, H, d_ckv), jnp.bfloat16)
    qp = jax.random.normal(jax.random.PRNGKey(3), (B, H, d_kpe), jnp.bfloat16)
    pt = jnp.arange(npages, dtype=jnp.int32).reshape(B, ppr)
    lens = jnp.array([2048, 1031, 64, 1999], jnp.int32)
    sm = (d_ckv + d_kpe) ** -0.5
    o = mla_paged_decode_attention(qn, qp, ckv, kpe, pt, lens, sm_scale=sm)
    ref = xla_mla_paged_decode(qn, qp, ckv, kpe, pt, lens, sm_scale=sm)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_mla_decode_packed_layout_on_chip():
    """Packed single-buffer MLA scratch (one concatenated score dot,
    128-aligned dst lane slices 0:512 / 512:640) vs the validated split
    kernel on real hardware — first Mosaic compile of the packed form."""
    from flashinfer_tpu.ops.mla_decode import mla_paged_decode_attention

    B, H, d_ckv, d_kpe, PS, ctx = 4, 128, 512, 64, 16, 2048
    ppr = ctx // PS
    npages = B * ppr
    ckv = jax.random.normal(
        jax.random.PRNGKey(0), (npages, PS, d_ckv), jnp.bfloat16
    )
    kpe = jax.random.normal(
        jax.random.PRNGKey(1), (npages, PS, d_kpe), jnp.bfloat16
    )
    qn = jax.random.normal(jax.random.PRNGKey(2), (B, H, d_ckv), jnp.bfloat16)
    qp = jax.random.normal(jax.random.PRNGKey(3), (B, H, d_kpe), jnp.bfloat16)
    pt = jnp.arange(npages, dtype=jnp.int32).reshape(B, ppr)
    lens = jnp.array([2048, 1031, 64, 1999], jnp.int32)
    sm = (d_ckv + d_kpe) ** -0.5
    o_p = mla_paged_decode_attention(
        qn, qp, ckv, kpe, pt, lens, sm_scale=sm, layout="packed")
    o_s = mla_paged_decode_attention(
        qn, qp, ckv, kpe, pt, lens, sm_scale=sm, layout="split")
    np.testing.assert_allclose(
        np.asarray(o_p, np.float32), np.asarray(o_s, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_rmsnorm_llama_shape():
    T, H = 4096, 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (T, H), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (H,), jnp.bfloat16)
    out = fi.rmsnorm(x, w)
    xf = np.asarray(x, np.float32)
    ref = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6)
    ref = ref * np.asarray(w, np.float32)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, **BF16_TOL)


def test_sampling_threshold_kernel_128k_vocab():
    """VMEM bisection top-k/top-p filter vs the XLA sort filter at the
    serving vocab (128k) — kept-set mass must agree."""
    from flashinfer_tpu.ops.sampling_kernels import threshold_select
    from flashinfer_tpu.sampling import _top_k_top_p_filter_xla

    bs, vocab = 16, 128 * 1024
    logits = jax.random.normal(jax.random.PRNGKey(0), (bs, vocab)) * 4.0
    probs = jax.nn.softmax(logits, axis=-1)
    k = jnp.full((bs,), 40.0)
    tp = jnp.full((bs,), 0.95)
    got = np.asarray(threshold_select(probs, k, tp, mode="top_k_top_p_seq"))
    ref = np.asarray(
        _top_k_top_p_filter_xla(probs, k.astype(jnp.int32), tp, False)
    )
    ref = ref / ref.sum(-1, keepdims=True)
    # same support (up to exact ties) and same renormalized mass
    np.testing.assert_allclose(got.sum(-1), 1.0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=1e-6)


def test_topk_threshold_backend_128k_vocab():
    """Bit-space-bisection exact top-k vs jax.lax.top_k on-chip."""
    from flashinfer_tpu import topk

    bs, vocab, k = 16, 128 * 1024, 2048
    scores = jax.random.normal(jax.random.PRNGKey(0), (bs, vocab)) * 4.0
    _, ix = topk.top_k_values_indices(scores, k, backend="xla")
    _, it = topk.top_k_values_indices(scores, k, backend="threshold")
    for rx, rt in zip(np.asarray(ix), np.asarray(it)):
        assert set(map(int, rx)) == set(i for i in map(int, rt) if i >= 0)


def test_cascade_merge_on_chip():
    """Cascade state algebra: merged split-KV attention == full attention
    (merge_state over flash-kernel LSE outputs)."""
    from flashinfer_tpu.ops.merge import merge_state

    T, N = 512, 2048
    q = jax.random.normal(jax.random.PRNGKey(0), (T, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (N, HKV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (N, HKV, D), jnp.bfloat16)
    half = N // 2
    oa, sa = fi.single_prefill_with_kv_cache(
        q, k[:half], v[:half], causal=False, return_lse=True
    )
    ob, sb = fi.single_prefill_with_kv_cache(
        q, k[half:], v[half:], causal=False, return_lse=True
    )
    merged, _ = merge_state(oa, sa, ob, sb)
    ref = fi.single_prefill_with_kv_cache(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(merged, np.float32), np.asarray(ref, np.float32),
        **BF16_TOL
    )


def test_attention_sink_on_chip():
    """StreamingLLM sink epilogue over the flash kernel's LSE output: the
    sink renormalization must equal a softmax that includes the sink
    logit as an extra zero-value token."""
    from flashinfer_tpu.attention import apply_attention_sink

    T = 1024
    q = jax.random.normal(jax.random.PRNGKey(0), (T, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (T, HKV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (T, HKV, D), jnp.bfloat16)
    sink = jax.random.normal(jax.random.PRNGKey(3), (HQ,))
    out, lse = fi.single_prefill_with_kv_cache(
        q, k, v, causal=True, return_lse=True
    )
    got = np.asarray(apply_attention_sink(out, lse, sink), np.float32)
    scale = np.exp(np.asarray(lse, np.float32))
    scale = scale / (scale + np.exp(np.asarray(sink, np.float32))[None, :])
    ref = np.asarray(out, np.float32) * scale[..., None]
    np.testing.assert_allclose(got, ref, rtol=1e-2, atol=1e-2)  # bf16 store


def test_msa_token_granular_on_chip():
    """Token-granular MSA selection + VBSR kernel vs the dense-masked
    oracle under the same per-token selection."""
    from flashinfer_tpu.msa_ops import msa_sparse_attention

    N = 2048
    q = jax.random.normal(jax.random.PRNGKey(0), (N, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (N, HKV, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (N, HKV, D), jnp.bfloat16)
    out_kernel = msa_sparse_attention(
        q, k, v, top_k=8, backend="pallas", granularity="token"
    )
    out_oracle = msa_sparse_attention(
        q, k, v, top_k=8, backend="xla", granularity="token"
    )
    np.testing.assert_allclose(
        np.asarray(out_kernel, np.float32),
        np.asarray(out_oracle, np.float32), **BF16_TOL
    )


def test_int8_kv_decode_llama_shape():
    """Fused in-register-dequant int8-KV decode at serving shapes."""
    from flashinfer_tpu.ops import paged_decode_attention

    B, PS, ctx = 16, 16, 4096
    ppr = ctx // PS
    npages = B * ppr
    pt = jnp.arange(npages, dtype=jnp.int32).reshape(B, ppr)
    lens = jnp.asarray(
        np.random.default_rng(1).integers(1, ctx + 1, B).astype(np.int32)
    )
    kc = jax.random.normal(
        jax.random.PRNGKey(0), (npages, HKV, PS, D), jnp.bfloat16
    )
    vc = jax.random.normal(
        jax.random.PRNGKey(1), (npages, HKV, PS, D), jnp.bfloat16
    )
    q = jax.random.normal(jax.random.PRNGKey(2), (B, HQ, D), jnp.bfloat16)
    sm = D ** -0.5
    ref = np.asarray(paged_decode_attention(
        q, kc, vc, pt, lens, sm_scale=sm, kv_layout="HND"), np.float32)
    from flashinfer_tpu.quantization import quantize_symmetric_int8

    ks = float(np.abs(np.asarray(kc, np.float32)).max() / 127)
    vs = float(np.abs(np.asarray(vc, np.float32)).max() / 127)
    kq = quantize_symmetric_int8(kc, ks)
    vq = quantize_symmetric_int8(vc, vs)
    o = np.asarray(paged_decode_attention(
        q, kq, vq, pt, lens, sm_scale=sm * ks, kv_layout="HND"),
        np.float32) * vs
    np.testing.assert_allclose(o, ref, rtol=4e-2, atol=4e-2)


def test_fp4_decode_llama_shape():
    """Fused token-pair int4 decode at its best legal ppc (wedge-culprit
    restructure a8f73ff: rolled page loops, selector-dot scales)."""
    from flashinfer_tpu.ops.paged_decode_fp4 import (
        fp4_paged_decode_attention, quantize_kv_int4_paged,
    )
    from flashinfer_tpu.ops import paged_decode_attention

    B, PS, ctx = 16, 16, 2048
    ppr = ctx // PS
    npages = B * ppr
    pt = jnp.arange(npages, dtype=jnp.int32).reshape(B, ppr)
    lens = jnp.full((B,), ctx, jnp.int32)
    kc = jax.random.normal(
        jax.random.PRNGKey(0), (npages, HKV, PS, D), jnp.float32
    )
    vc = jax.random.normal(
        jax.random.PRNGKey(1), (npages, HKV, PS, D), jnp.float32
    )
    q = jax.random.normal(jax.random.PRNGKey(2), (B, HQ, D), jnp.bfloat16)
    k4, ksc = quantize_kv_int4_paged(kc)
    v4, vsc = quantize_kv_int4_paged(vc)
    sm = D ** -0.5
    o = fp4_paged_decode_attention(
        q, k4, ksc, v4, vsc, pt, lens, sm_scale=sm
    )
    ref = paged_decode_attention(
        q, kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16), pt, lens,
        sm_scale=sm, kv_layout="HND",
    )
    # int4 quantization noise dominates the comparison
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32),
        rtol=1.5e-1, atol=1.5e-1,
    )


def test_moe_gmm_kernel_mixtral_shape():
    """Pallas gather-GMM MoE vs the ragged_dot oracle at Mixtral-8x7B
    hidden/inter dims (token count scaled down)."""
    from flashinfer_tpu import fused_moe as moe

    T, E, K, h, inter = 256, 8, 2, 4096, 14336
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, h), jnp.bfloat16)
    w1 = jax.random.normal(
        jax.random.fold_in(key, 1), (E, h, 2 * inter), jnp.bfloat16
    ) * 0.02
    w2 = jax.random.normal(
        jax.random.fold_in(key, 2), (E, inter, h), jnp.bfloat16
    ) * 0.02
    logits = jax.random.normal(jax.random.fold_in(key, 3), (T, E))
    wts, ids = moe.route_renormalize(logits, K)
    ref = moe.fused_moe(x, w1, w2, wts, ids, E, backend="ragged")
    # sorted must pass on hardware (aligned BlockSpec DMAs only); the
    # in-kernel gather variants are attempted so the log records the
    # Mosaic verdict each run — this Mosaic rejects sub-8-row HBM slices
    # ("Slice shape along dimension 0 must be aligned to tiling (8)",
    # banked 2026-07-31)
    rejected = []
    for gv in ("sorted", "stream", "rowcache"):
        try:
            out = moe.fused_moe(x, w1, w2, wts, ids, E, backend="gmm",
                                gather_variant=gv)
        except Exception as e:  # noqa: BLE001 - compiler verdict triage
            if gv != "sorted" and "aligned to tiling" in str(e):
                rejected.append(gv)
                print(f"moe gather variant {gv}: Mosaic still rejects "
                      f"single-row HBM slices ({str(e).splitlines()[0][:100]})")
                continue
            raise
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=6e-2, atol=6e-2, err_msg=gv,
        )
    if rejected == ["stream", "rowcache"]:
        pytest.xfail("in-kernel gather variants rejected by Mosaic "
                     "(sub-8-row DMA alignment); sorted variant passed")


def test_gather_gmm_rowcache_straddle_on_chip():
    """The rowcache variant's aliased-output boundary merge is a
    HARDWARE-ONLY code path (interpret mode reads out blocks back
    directly, so CPU CI cannot exercise the prev_ref merge or the
    input_output_aliases machinery).  Mid-tile group starts force
    non-consecutive output-block revisits; every row must survive."""
    from flashinfer_tpu.ops.moe_gmm import gather_gmm

    rng = np.random.default_rng(9)
    t_rows, k, n = 128, 512, 512
    m = 256
    sizes = np.asarray([37, 90, 56, 73], np.int32)  # all starts mid-tile
    x = jnp.asarray(rng.standard_normal((t_rows, k)), jnp.bfloat16)
    row_ids = jnp.asarray(rng.integers(0, t_rows, m), jnp.int32)
    rhs = jnp.asarray(rng.standard_normal((4, k, n)) / np.sqrt(k),
                      jnp.bfloat16)
    try:
        out = gather_gmm(x, row_ids, rhs, jnp.asarray(sizes),
                         tm=64, tn=128, tk=128, variant="rowcache")
    except Exception as e:  # noqa: BLE001 - compiler verdict triage
        if "aligned to tiling" in str(e):
            pytest.xfail(
                "Mosaic rejects single-row HBM slices (banked 2026-07-31: "
                "'Slice shape along dimension 0 must be aligned to tiling "
                "(8)'); rowcache gather stays interpret-only until the "
                "compiler relaxes sub-8-row DMA alignment"
            )
        raise
    xs = np.asarray(x, np.float32)[np.asarray(row_ids)]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    ref = np.zeros((m, n), np.float32)
    for g in range(4):
        ref[offsets[g]:offsets[g + 1]] = (
            xs[offsets[g]:offsets[g + 1]]
            @ np.asarray(rhs, np.float32)[g]
        )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=5e-2, atol=5e-2
    )


def test_masked_fused_prefill_on_chip():
    """In-kernel packed custom masks (selector-dot bit expansion) on the
    fused prefill kernel vs the dense-oracle, multi-item scoring mask."""
    prefix, items = 512, [128, 96]
    kvl = prefix + sum(items)
    mask = np.asarray(fi.build_multi_item_mask(prefix, items))
    PS = 16
    pages = (kvl + PS - 1) // PS
    packed = np.packbits(mask.reshape(-1).astype(np.uint8),
                         bitorder="little")
    kc = jax.random.normal(
        jax.random.PRNGKey(1), (pages, HKV, PS, D), jnp.bfloat16
    )
    vc = jax.random.normal(
        jax.random.PRNGKey(2), (pages, HKV, PS, D), jnp.bfloat16
    )
    q = jax.random.normal(jax.random.PRNGKey(0), (kvl, HQ, D), jnp.bfloat16)
    w = fi.BatchPrefillWithPagedKVCacheWrapper(
        kv_layout="HND", backend="pallas_fused"
    )
    w.plan(
        np.array([0, kvl]), np.array([0, pages]), np.arange(pages),
        [kvl - (pages - 1) * PS], HQ, HKV, D, PS,
        packed_custom_mask=packed,
    )
    assert "mask_bytes" in w._fused_plan[0]
    out = w.run(q, (kc, vc))
    kflat = jnp.swapaxes(kc, 1, 2).reshape(-1, HKV, D)[:kvl]
    vflat = jnp.swapaxes(vc, 1, 2).reshape(-1, HKV, D)[:kvl]
    ref = fi.single_prefill_with_kv_cache(
        q, kflat, vflat, custom_mask=jnp.asarray(mask)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **BF16_TOL
    )


def test_alibi_flash_kernel_on_chip():
    """In-kernel ALiBi bias (f32 slopes as a scalar-prefetch operand +
    per-head SMEM read) must Mosaic-compile and match the dense oracle."""
    q_len, kv_len = 256, 1024
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (q_len, HQ, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (kv_len, HKV, D),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (kv_len, HKV, D),
                          jnp.bfloat16)
    o = fi.single_prefill_with_kv_cache(
        q, k, v, causal=True, pos_encoding_mode="ALIBI", backend="pallas"
    )
    ref = fi.single_prefill_with_kv_cache(
        q, k, v, causal=True, pos_encoding_mode="ALIBI"
    )
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32), **BF16_TOL
    )


def test_trace_events_prefill_on_chip():
    """The in-kernel device-tag tracing variant (trace_events=True) must
    Mosaic-compile and emit decodable tags on hardware — the last prefill
    variant of the round-3/4 backlog without an on-chip verdict."""
    from flashinfer_tpu import profiler
    from flashinfer_tpu.ops.paged_prefill import (
        build_prefill_work_units, fused_paged_prefill,
    )

    PS = 16
    qo_len, kv_len = 256, 512
    pages = kv_len // PS
    plan_np = build_prefill_work_units(
        np.array([0, qo_len]), np.array([0, pages]),
        np.arange(pages, dtype=np.int32), np.array([kv_len], np.int64),
        block_q=128, pages_per_chunk=8, page_size=PS,
    )
    num_units = plan_np.pop("num_units")
    plan_np.pop("block_q"), plan_np.pop("pages_per_chunk")
    plan_np.pop("stats")
    plan = {k: jnp.asarray(v) for k, v in plan_np.items()}
    q = jax.random.normal(jax.random.PRNGKey(0), (qo_len, HQ, D),
                          jnp.bfloat16)
    kc = jax.random.normal(jax.random.PRNGKey(1), (pages, HKV, PS, D),
                           jnp.bfloat16)
    vc = jax.random.normal(jax.random.PRNGKey(2), (pages, HKV, PS, D),
                           jnp.bfloat16)
    out, events = fused_paged_prefill(
        q, kc, vc, plan, num_units=num_units, block_q=128,
        pages_per_chunk=8, trace_events=True,
    )
    # numerics unchanged by tracing
    out_plain = fused_paged_prefill(
        q, kc, vc, plan, num_units=num_units, block_q=128,
        pages_per_chunk=8,
    )
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(out_plain, np.float32)
    )
    ev = np.asarray(events)
    assert ev.shape == (HKV, num_units)
    # one tag per unit the launch ran (the plan's real units, which lead
    # the arrays), none for the pad units past its trip count
    n_units = int(plan["n_units"])
    assert 0 < n_units < num_units and not ev[:, n_units:].any()
    for h in range(HKV):
        for u in range(n_units):
            blk, grp, ei, et, sm = profiler.decode_tag(
                int(ev[h, u]), num_units, 1
            )
            assert (sm, blk, et) == (h, u, 2), (h, u, ev[h, u])


def test_gdn_pallas_kernel_on_chip():
    """Fused chunked GDN kernel vs the exact recurrence at model shapes
    (normalized keys — the delta-rule operating regime)."""
    from flashinfer_tpu.gdn import gdn_prefill
    from flashinfer_tpu.ops.gdn_kernel import gdn_chunk_prefill_pallas

    rng = np.random.default_rng(0)
    B, L, H, dk, dv = 2, 1024, 4, 128, 128
    qn = rng.standard_normal((B, L, H, dk))
    kn = rng.standard_normal((B, L, H, dk))
    q = jnp.asarray(qn / np.linalg.norm(qn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    k = jnp.asarray(kn / np.linalg.norm(kn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, L, H, dv)), jnp.bfloat16)
    alpha = jnp.asarray(np.exp(-0.1 * rng.random((B, L, H))), jnp.float32)
    beta = jnp.asarray(rng.random((B, L, H)), jnp.float32)
    o_ref, s_ref = gdn_prefill(q, k, v, alpha, beta)
    o, s = gdn_chunk_prefill_pallas(q, k, v, alpha, beta)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        rtol=4e-2, atol=4e-2,
    )
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(s_ref), rtol=4e-2, atol=4e-2
    )


def test_mamba_ssd_pallas_kernel_on_chip():
    """Fused SSD kernel vs the XLA chunked form at Mamba-2-ish shapes."""
    from flashinfer_tpu.mamba import mamba_chunk_scan_combined

    rng = np.random.default_rng(1)
    B, L, H, G, dim, ds = 2, 1024, 8, 2, 64, 128
    x = jnp.asarray(rng.standard_normal((B, L, H, dim)), jnp.bfloat16)
    dt = jnp.asarray(rng.random((B, L, H)) + 0.1, jnp.float32)
    A = jnp.asarray(-np.abs(rng.standard_normal(H)) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, L, G, ds)) * 0.3, jnp.bfloat16)
    Cm = jnp.asarray(rng.standard_normal((B, L, G, ds)) * 0.3, jnp.bfloat16)
    y_ref, s_ref = mamba_chunk_scan_combined(x, dt, A, Bm, Cm, chunk_size=64)
    y, s = mamba_chunk_scan_combined(x, dt, A, Bm, Cm, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(s_ref), rtol=5e-2, atol=5e-2
    )


def test_kda_pallas_kernel_on_chip():
    """Fused KDA kernel vs the exact recurrence (normalized keys,
    trained-gate-range decay)."""
    from flashinfer_tpu.gdn import kda_chunk_prefill, kda_prefill

    rng = np.random.default_rng(2)
    B, L, H, dk, dv = 1, 512, 4, 128, 128
    qn = rng.standard_normal((B, L, H, dk))
    kn = rng.standard_normal((B, L, H, dk))
    q = jnp.asarray(qn / np.linalg.norm(qn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    k = jnp.asarray(kn / np.linalg.norm(kn, axis=-1, keepdims=True),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, L, H, dv)), jnp.bfloat16)
    alpha = jnp.asarray(np.exp(-0.05 * rng.random((B, L, H, dk))),
                        jnp.float32)
    beta = jnp.asarray(rng.random((B, L, H)), jnp.float32)
    o_ref, s_ref = kda_prefill(q, k, v, alpha, beta)
    o, s = kda_chunk_prefill(q, k, v, alpha, beta, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )
    np.testing.assert_allclose(
        np.asarray(s), np.asarray(s_ref), rtol=5e-2, atol=5e-2
    )
