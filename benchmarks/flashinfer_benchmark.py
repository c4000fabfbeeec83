"""Unified benchmark harness.

TPU re-design of the reference's ``benchmarks/flashinfer_benchmark.py`` +
``benchmarks/routines/`` (SURVEY §2.7/L5): one CLI spanning the op families,
emitting CSV rows of latency / TFLOPS / TB/s.

    python benchmarks/flashinfer_benchmark.py --routine decode \
        --batch 64 --ctx 4096 [--csv out.csv]
    python benchmarks/flashinfer_benchmark.py --routine all --quick

Routines: decode (paged batch decode), prefill (causal ragged), gemm
(bf16 square), moe (fused MoE forward), sampling (top-k/top-p over 128k
vocab), mamba (SSD prefill + selective-state-update decode), gdn
(GDN/KDA prefill + decode steps), norm (rmsnorm family), rope,
quantization (fp8/int8/fp4), sparse_attention (BSR), mla (paged MLA
decode at DeepSeek shapes).  Runs on whatever backend jax selects (TPU
on hardware; CPU with the xla backend elsewhere — pass --quick for
CI-sized shapes).
"""

import argparse
import csv
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _bench(args, fn, *operands):
    """Slope-fit device timing (see testing.bench_fn_device) — the plain
    per-call timer reports dispatch overhead, not kernel time.  The whole
    first timing call (which contains the Mosaic compile) runs under
    ``compile_guard.guarded``."""
    from flashinfer_tpu import compile_guard
    from flashinfer_tpu.testing import bench_fn_device

    hi = max(args.iters, 3)
    lo = max(hi // 4, 1)
    # fingerprint by the bench fn's source location + operand signature:
    # stable across reruns/routine subsets (a call-order counter would make
    # persisted quarantine entries miss on any differently-ordered rerun)
    code = getattr(fn, "__code__", None)
    fn_id = (f"{getattr(code, 'co_filename', '?')}:"
             f"{getattr(code, 'co_firstlineno', 0)}")
    statics = (fn_id, args.routine,
               tuple((getattr(o, "shape", None), str(getattr(o, "dtype", "")))
                     for o in operands))
    return compile_guard.guarded(
        f"flashinfer_benchmark.{args.routine}", statics,
        lambda: bench_fn_device(fn, *operands, iters_low=lo, iters_high=hi,
                                repeats=2),
    )


def _rows_decode(args):
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi
    from flashinfer_tpu.testing import attention_bytes

    dtype = jnp.bfloat16
    hq, hkv, hd, ps = args.num_qo_heads, args.num_kv_heads, args.head_dim, 16
    for bs in args.batch:
        for ctx in args.ctx:
            ppr = ctx // ps
            npages = bs * ppr
            indptr = np.arange(bs + 1, dtype=np.int32) * ppr
            idx = np.random.default_rng(0).permutation(npages).astype(np.int32)
            last = np.full((bs,), ps, np.int32)
            kc = jax.random.normal(jax.random.PRNGKey(0), (npages, hkv, ps, hd), dtype)
            vc = jax.random.normal(jax.random.PRNGKey(1), (npages, hkv, ps, hd), dtype)
            q = jax.random.normal(jax.random.PRNGKey(2), (bs, hq, hd), dtype)
            w = fi.BatchDecodeWithPagedKVCacheWrapper(kv_layout="HND")
            w.plan(indptr, idx, last, hq, hkv, hd, ps)
            t = _bench(args, lambda qq, kk, vv: w.run(qq, (kk, vv)), q, kc, vc)
            tb = bs * attention_bytes(1, ctx, hq, hkv, hd, hd, 2) / t / 1e12
            yield dict(routine="decode", config=f"bs{bs}_ctx{ctx}",
                       latency_us=t * 1e6, tbps=tb, tflops="")


def _rows_prefill(args):
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi
    from flashinfer_tpu.testing import attention_flops

    dtype = jnp.bfloat16
    hq, hkv, hd = args.num_qo_heads, args.num_kv_heads, args.head_dim
    for ctx in args.ctx:
        q = jax.random.normal(jax.random.PRNGKey(0), (ctx, hq, hd), dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (ctx, hkv, hd), dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (ctx, hkv, hd), dtype)
        t = _bench(
            args,
            lambda qq, kk, vv: fi.single_prefill_with_kv_cache(qq, kk, vv, causal=True),
            q, k, v,
        )
        fl = attention_flops(ctx, ctx, hq, hd, hd, causal=True)
        yield dict(routine="prefill", config=f"ctx{ctx}",
                   latency_us=t * 1e6, tbps="", tflops=fl / t / 1e12)


def _rows_gemm(args):
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi

    for n in args.gemm_sizes:
        a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
        t = _bench(args, lambda aa, bb: fi.mm_bf16(aa, bb), a, b)
        yield dict(routine="gemm_bf16", config=f"{n}x{n}x{n}",
                   latency_us=t * 1e6, tbps="", tflops=2 * n**3 / t / 1e12)


def _rows_moe(args):
    import jax
    import jax.numpy as jnp
    from flashinfer_tpu.fused_moe import fused_moe, route_renormalize

    T, E, K = args.moe_tokens, args.moe_experts, 2
    h, inter = args.moe_hidden, 4 * args.moe_hidden
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.bfloat16)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter), jnp.bfloat16)
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h), jnp.bfloat16)
    logits = jax.random.normal(jax.random.PRNGKey(3), (T, E))
    wts, ids = route_renormalize(logits, K)
    t = _bench(args, lambda xx, ww1, ww2, wt, ii: fused_moe(xx, ww1, ww2, wt, ii, E),
               x, w1, w2, wts, ids)
    fl = 2 * T * K * (h * 2 * inter + inter * h)
    yield dict(routine="moe", config=f"T{T}_E{E}_h{h}",
               latency_us=t * 1e6, tbps="", tflops=fl / t / 1e12)


def _rows_sampling(args):
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi

    bs, vocab = args.sampling_batch, args.vocab
    logits = jax.random.normal(jax.random.PRNGKey(0), (bs, vocab))
    key = jax.random.PRNGKey(1)
    t = _bench(
        args,
        lambda lg, kk: fi.top_k_top_p_sampling_from_logits(lg, kk, 40, 0.9),
        logits, key,
    )
    yield dict(routine="sampling_topk_topp", config=f"bs{bs}_v{vocab}",
               latency_us=t * 1e6, tbps="", tflops="")


def _rows_mamba(args):
    """SSM routines (reference bench: mamba/SSD kernels): chunked SSD
    prefill (Mamba-2 shapes) + the bandwidth-bound selective-state decode
    step at serving batch."""
    import jax
    import jax.numpy as jnp
    from flashinfer_tpu.mamba import (
        mamba_chunk_scan_combined, selective_state_update,
    )

    B, L = args.mamba_batch, args.mamba_seqlen
    H, dim, G, dstate = args.mamba_heads, 64, 1, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, L, H, dim), jnp.bfloat16)
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(key, 1), (B, L, H)) - 4
    )
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, L, G, dstate),
                           jnp.bfloat16)
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, L, G, dstate),
                           jnp.bfloat16)
    t = _bench(
        args,
        lambda xx, dd, bb, cc: mamba_chunk_scan_combined(xx, dd, A, bb, cc)[0],
        x, dt, Bm, Cm,
    )
    fl = 6 * B * L * H * dim * dstate  # score + state matmul pairs
    yield dict(routine="mamba_prefill", config=f"B{B}_L{L}_H{H}",
               latency_us=t * 1e6, tbps="", tflops=fl / t / 1e12)

    state = jax.random.normal(key, (B, H, dim, dstate), jnp.float32)
    xd = jax.random.normal(jax.random.fold_in(key, 5), (B, H, dim), jnp.bfloat16)
    dtd = jnp.ones((B, H, dim), jnp.float32) * 0.1
    Ad = -jnp.ones((H, dim, dstate), jnp.float32)
    Bd = jax.random.normal(jax.random.fold_in(key, 6), (B, G, dstate), jnp.bfloat16)
    td = _bench(
        args,
        lambda ss, xx, bb, cc: selective_state_update(ss, xx, dtd, Ad, bb, cc)[0],
        state, xd, Bd, Cm[:, 0],
    )
    state_bytes = 2 * B * H * dim * dstate * 4  # read + write f32 state
    yield dict(routine="mamba_decode", config=f"B{B}_H{H}",
               latency_us=td * 1e6, tbps=state_bytes / td / 1e12, tflops="")


def _rows_gdn(args):
    """GDN + KDA chunked prefill and decode steps (reference
    routines/gdn.py)."""
    import jax
    import jax.numpy as jnp
    from flashinfer_tpu.gdn import (
        gdn_chunk_prefill, gdn_decode_step, kda_chunk_prefill,
        kda_decode_step,
    )

    B, L, H = args.mamba_batch, args.mamba_seqlen, args.mamba_heads
    dk = dv = 32 if args.quick else 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, L, H, dk), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, H, dk)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, H, dv))
    beta = jax.nn.sigmoid(
        jax.random.normal(jax.random.fold_in(key, 3), (B, L, H)))
    a_g = jnp.exp(-0.05 * jax.random.uniform(
        jax.random.fold_in(key, 4), (B, L, H)))
    a_k = jnp.exp(-0.05 * jax.random.uniform(
        jax.random.fold_in(key, 5), (B, L, H, dk)))
    flops = 2 * B * L * H * dk * dv * 2
    # backend="xla" pins the reference form: auto resolves to the pallas
    # kernel on these eligible shapes since the 2026-07-31 default flip,
    # and these rows are banked against XLA-form history
    for name, fn, aa in (
        ("gdn_prefill",
         lambda *a: gdn_chunk_prefill(*a, backend="xla")[0], a_g),
        ("kda_prefill",
         lambda *a: kda_chunk_prefill(*a, backend="xla")[0], a_k),
    ):
        t = _bench(args, fn, q, k, v, aa, beta)
        yield dict(routine=name, config=f"B{B}_L{L}_H{H}",
                   latency_us=t * 1e6, tbps="", tflops=flops / t / 1e12)
    s = jax.random.normal(key, (B, H, dk, dv), jnp.float32)
    state_bytes = 2 * B * H * dk * dv * 4
    # bench the WHOLE (o, new_state) tuple: selecting [1] would let XLA
    # dead-code-eliminate the output einsum (o depends on the state, not
    # vice versa) and under-report the step
    for name, fn, aa in (
        ("gdn_decode", gdn_decode_step, a_g[:, 0]),
        ("kda_decode", kda_decode_step, a_k[:, 0]),
    ):
        t = _bench(args, fn, s, q[:, 0], k[:, 0], v[:, 0], aa, beta[:, 0])
        yield dict(routine=name, config=f"B{B}_H{H}",
                   latency_us=t * 1e6, tbps=state_bytes / t / 1e12,
                   tflops="")


def _rows_norm(args):
    """rmsnorm family (reference routines/norm.py)."""
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi

    h = 256 if args.quick else 8192
    for tname, tokens in (("small", 128 if args.quick else 1024),
                          ("large", 512 if args.quick else 16384)):
        x = jax.random.normal(jax.random.PRNGKey(0), (tokens, h),
                              jnp.bfloat16)
        r = jax.random.normal(jax.random.PRNGKey(1), (tokens, h),
                              jnp.bfloat16)
        w = jnp.ones((h,), jnp.bfloat16)
        gbytes = 2 * tokens * h * 2
        t = _bench(args, lambda xx, ww: fi.rmsnorm(xx, ww), x, w)
        yield dict(routine="rmsnorm", config=f"{tname}_t{tokens}_h{h}",
                   latency_us=t * 1e6, tbps=gbytes / t / 1e12, tflops="")
        t = _bench(args, lambda xx, rr, ww: fi.fused_add_rmsnorm(xx, rr, ww),
                   x, r, w)
        yield dict(routine="fused_add_rmsnorm",
                   config=f"{tname}_t{tokens}_h{h}",
                   latency_us=t * 1e6, tbps=2 * gbytes / t / 1e12, tflops="")


def _rows_rope(args):
    """RoPE family (reference routines/rope.py)."""
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi

    hq, hkv, hd = args.num_qo_heads, args.num_kv_heads, args.head_dim
    tokens = 256 if args.quick else 8192
    q = jax.random.normal(jax.random.PRNGKey(0), (tokens, hq, hd),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (tokens, hkv, hd),
                          jnp.bfloat16)
    pos = jnp.arange(tokens, dtype=jnp.int32)
    gbytes = 2 * tokens * (hq + hkv) * hd * 2
    t = _bench(args, lambda qq, kk, pp: fi.apply_rope_pos_ids(qq, kk, pp),
               q, k, pos)
    yield dict(routine="rope", config=f"t{tokens}_h{hq}/{hkv}",
               latency_us=t * 1e6, tbps=gbytes / t / 1e12, tflops="")


def _rows_quantization(args):
    """Quantize family (reference routines/quantization.py)."""
    import jax
    import jax.numpy as jnp
    from flashinfer_tpu.quantization import (
        quantize_fp4, quantize_fp8_per_tensor, quantize_int8,
    )

    m = 256 if args.quick else 8192
    k = 256 if args.quick else 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.bfloat16)
    gbytes = m * k * 3  # read bf16 + write ~1B
    for name, fn in (
        ("quant_fp8", lambda xx: quantize_fp8_per_tensor(xx)[0]),
        ("quant_int8", lambda xx: quantize_int8(xx)[0]),
        ("quant_fp4", lambda xx: quantize_fp4(xx)[0]),
    ):
        t = _bench(args, fn, x)
        yield dict(routine=name, config=f"{m}x{k}",
                   latency_us=t * 1e6, tbps=gbytes / t / 1e12, tflops="")


def _rows_sparse_attention(args):
    """Block-sparse attention (reference routines/sparse_attention.py)."""
    import numpy as _np
    import jax
    import jax.numpy as jnp
    import flashinfer_tpu as fi
    from flashinfer_tpu.testing import attention_flops

    hd = args.head_dim
    n = 512 if args.quick else 4096
    R = C = 64
    MB, NB = n // R, n // C
    rng = _np.random.default_rng(0)
    # ~25%-dense random BSR mask
    mask = rng.random((MB, NB)) < 0.25
    _np.fill_diagonal(mask, True)
    indptr = _np.zeros(MB + 1, _np.int32)
    indices = []
    for i in range(MB):
        cols = _np.nonzero(mask[i])[0]
        indices.extend(cols)
        indptr[i + 1] = len(indices)
    q = jax.random.normal(jax.random.PRNGKey(0), (n, 1, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (n, 1, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (n, 1, hd), jnp.bfloat16)
    w = fi.BlockSparseAttentionWrapper()
    w.plan(_np.asarray(indptr), _np.asarray(indices, _np.int32), n, n,
           R, C, 1, 1, hd)
    t = _bench(args, lambda qq, kk, vv: w.run(qq, kk, vv), q, k, v)
    density = mask.mean()
    fl = attention_flops(n, n, 1, hd, hd, causal=False) * density
    yield dict(routine="sparse_attention",
               config=f"n{n}_{R}x{C}_d{density:.2f}",
               latency_us=t * 1e6, tbps="", tflops=fl / t / 1e12)


def _rows_mla(args):
    """MLA paged decode (reference bench_deepseek_mla.py shapes)."""
    import jax
    import jax.numpy as jnp
    from flashinfer_tpu.ops.mla_decode import (
        mla_paged_decode_attention, xla_mla_paged_decode,
    )
    from flashinfer_tpu.utils import is_tpu

    rank, rope, ps = (64, 64, 8) if args.quick else (512, 64, 16)
    H = 4 if args.quick else 128
    for bs in args.batch:
        for ctx in args.ctx:
            ppr = ctx // ps
            npages = bs * ppr
            qn = jax.random.normal(jax.random.PRNGKey(0), (bs, H, rank),
                                   jnp.bfloat16)
            qp = jax.random.normal(jax.random.PRNGKey(1), (bs, H, rope),
                                   jnp.bfloat16)
            ckv = jax.random.normal(jax.random.PRNGKey(2),
                                    (npages, ps, rank), jnp.bfloat16)
            kpe = jax.random.normal(jax.random.PRNGKey(3),
                                    (npages, ps, 128), jnp.bfloat16)
            # permuted pages, like _rows_decode: contiguous tables would
            # benchmark an unrealistically sequential gather pattern
            table = jnp.asarray(
                np.random.default_rng(0).permutation(npages)
                .reshape(bs, ppr).astype(np.int32)
            )
            lens = jnp.full((bs,), ctx, jnp.int32)
            fn = (mla_paged_decode_attention if is_tpu()
                  else xla_mla_paged_decode)
            sm = 1.0 / float(128 + rope) ** 0.5
            t = _bench(
                args,
                lambda a, b, c, d: fn(a, b, c, d, table, lens, sm_scale=sm),
                qn, qp, ckv, kpe,
            )
            gbytes = bs * ctx * (rank + rope) * 2  # cache read per step
            yield dict(routine="mla_decode", config=f"bs{bs}_ctx{ctx}",
                       latency_us=t * 1e6, tbps=gbytes / t / 1e12,
                       tflops="")


ROUTINES = {
    "decode": _rows_decode,
    "prefill": _rows_prefill,
    "gemm": _rows_gemm,
    "moe": _rows_moe,
    "sampling": _rows_sampling,
    "mamba": _rows_mamba,
    "gdn": _rows_gdn,
    "norm": _rows_norm,
    "rope": _rows_rope,
    "quantization": _rows_quantization,
    "sparse_attention": _rows_sparse_attention,
    "mla": _rows_mla,
}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--routine", default="all",
                   choices=["all"] + sorted(ROUTINES))
    p.add_argument("--batch", type=int, nargs="+", default=[64])
    p.add_argument("--ctx", type=int, nargs="+", default=[4096])
    p.add_argument("--num-qo-heads", type=int, default=32)
    p.add_argument("--num-kv-heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--gemm-sizes", type=int, nargs="+", default=[4096])
    p.add_argument("--moe-tokens", type=int, default=512)
    p.add_argument("--moe-experts", type=int, default=32)
    p.add_argument("--moe-hidden", type=int, default=1024)
    p.add_argument("--sampling-batch", type=int, default=64)
    p.add_argument("--vocab", type=int, default=128256)
    p.add_argument("--mamba-batch", type=int, default=8)
    p.add_argument("--mamba-seqlen", type=int, default=4096)
    p.add_argument("--mamba-heads", type=int, default=24)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--quick", action="store_true",
                   help="CI-sized shapes (CPU-friendly)")
    p.add_argument("--csv", default=None)
    args = p.parse_args(argv)
    if args.quick:
        args.batch, args.ctx = [2], [256]
        args.gemm_sizes = [256]
        args.moe_tokens, args.moe_experts, args.moe_hidden = 16, 4, 64
        args.sampling_batch, args.vocab = 4, 1024
        args.mamba_batch, args.mamba_seqlen, args.mamba_heads = 1, 128, 2
        args.iters = 3

    names = sorted(ROUTINES) if args.routine == "all" else [args.routine]
    rows = []
    for name in names:
        for row in ROUTINES[name](args):
            rows.append(row)
            print(
                f"{row['routine']:>18} {row['config']:>16} "
                f"{row['latency_us']:10.1f} us"
                + (f"  {row['tbps']:.3f} TB/s" if row["tbps"] != "" else "")
                + (f"  {row['tflops']:.2f} TFLOPS" if row["tflops"] != "" else "")
            )
    if args.csv:
        with open(args.csv, "w", newline="") as f:
            wr = csv.DictWriter(
                f, fieldnames=["routine", "config", "latency_us", "tbps", "tflops"]
            )
            wr.writeheader()
            wr.writerows(rows)
        print(f"wrote {len(rows)} rows to {args.csv}")
    return 0


if __name__ == "__main__":
    if "--cpu" in sys.argv:  # before jax starts a backend
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.exit(main([a for a in sys.argv[1:] if a != "--cpu"]))
