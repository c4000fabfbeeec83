"""End-to-end serving loop: chunked prefill -> paged batch decode -> sampling.

The TPU analogue of the reference's ``examples/pytorch`` integration blocks:
a complete generate() built from flashinfer_tpu public APIs, showing the
canonical serving lifecycle —

1. allocate a paged KV cache + page tables;
2. prefill each prompt with ``BatchPrefillWithPagedKVCacheWrapper``
   (appending K/V via ``append_paged_kv_cache``);
3. decode step-by-step with ``BatchDecodeWithPagedKVCacheWrapper``
   (plan once per geometry bucket, run per layer per step);
4. sample with the logits pipeline.

``--engine`` then serves the same prompts through the serving surface,
``flashinfer_tpu.serve.ServingEngine`` (``submit`` / ``run``), which owns
all four steps, and checks its first tokens against the hand-built
prefill's.

Run: ``python examples/generate.py`` (tiny random model).  It runs on the
devices JAX has — ``JAX_PLATFORMS=cpu`` for the CPU — and prints which.
"""

import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import jax
import jax.numpy as jnp

# the example demonstrates the metered serving lifecycle (ISSUE 10):
# flight recorder ON by default here (an explicit FLASHINFER_TPU_SPANS=0
# still wins — the library itself stays zero-overhead-by-default)
os.environ.setdefault("FLASHINFER_TPU_SPANS", "1")

import flashinfer_tpu as fi
from flashinfer_tpu import obs
from flashinfer_tpu.logits_processor import (
    LogitsPipe, Sample, Softmax, Temperature, TopK, TopP,
)
from flashinfer_tpu.models import LlamaConfig, init_llama_params, llama_decode_step


def _print_lifecycle_summary(label: str) -> None:
    """Per-run request-lifecycle summary out of the flight recorder's
    histograms (TTFT / TPOT p50+p99, tok/s) — silent when the spans
    gate is off."""
    ls = obs.lifecycle_snapshot()
    if not ls:
        return

    def pq(name):
        h = ls.get(name)
        if not h:
            return "n/a"
        return f"p50 {h.get('p50', 0):.0f} / p99 {h.get('p99', 0):.0f}"

    toks = ls.get("lifecycle.tokens_per_s") or {}
    print(f"# lifecycle[{label}]: ttft_us {pq('lifecycle.ttft_us')} | "
          f"tpot_us {pq('lifecycle.tpot_us')} | "
          f"tok/s p50 {toks.get('p50', 0):.1f} "
          f"({toks.get('count', 0)} requests)")


def generate(prompt_lens, max_new_tokens=8, seed=0, int8_weights=False,
             engine=False):
    """Serving loop; ``int8_weights=True`` runs every projection on the
    int8 MXU path (quantize_llama_weights) — the quantized serving mode.

    ``engine=True`` ADDITIONALLY serves the same prompts through
    ``ServingEngine`` (greedy), whose first token of every request must
    be the argmax of the logits this function's own prefill computed:
    two routes through the library, one model."""
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    params = init_llama_params(jax.random.PRNGKey(seed), cfg)
    if int8_weights:
        from flashinfer_tpu.models import quantize_llama_weights

        params = quantize_llama_weights(params)
    B = len(prompt_lens)
    PS = 8
    max_len = max(prompt_lens) + max_new_tokens
    pages_per_req = -(-max_len // PS)
    num_pages = B * pages_per_req
    use_pallas = jax.default_backend() == "tpu"

    # paged cache (HND) + contiguous page allocation per request
    caches = [
        (
            jnp.zeros((num_pages, cfg.num_kv_heads, PS, cfg.head_dim), cfg.dtype),
            jnp.zeros((num_pages, cfg.num_kv_heads, PS, cfg.head_dim), cfg.dtype),
        )
        for _ in range(cfg.num_layers)
    ]
    page_table = jnp.arange(num_pages, dtype=jnp.int32).reshape(B, pages_per_req)

    # ---- prefill: the real serving flow — one ragged batch-prefill pass.
    # Per layer: project the prompt tokens, RoPE, append K/V into the paged
    # cache, then BatchPrefillWithPagedKVCacheWrapper over the cache.
    from flashinfer_tpu.models.llama import _tp_param_specs  # noqa: F401
    from flashinfer_tpu.norm import rmsnorm
    from flashinfer_tpu.activation import silu_and_mul
    from flashinfer_tpu.rope import apply_rope_pos_ids

    # request lifecycle (flight recorder): admitted here, queue window
    # closed by the prefill chunk, TTFT at the first sampled token
    rids = [f"req{b}" for b in range(B)]
    for rid in rids:
        obs.request_begin(rid)

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, l).tolist() for l in prompt_lens]
    qo_indptr = np.concatenate([[0], np.cumsum(prompt_lens)]).astype(np.int32)
    total_q = int(qo_indptr[-1])
    flat_tokens = jnp.asarray(np.concatenate(prompts), jnp.int32)
    # positions within each request
    pos = jnp.asarray(
        np.concatenate([np.arange(l) for l in prompt_lens]), jnp.int32
    )
    seq_lens = np.asarray(prompt_lens, np.int32)
    pages_used = [-(-int(l) // PS) for l in prompt_lens]
    kv_page_indptr = np.concatenate([[0], np.cumsum(pages_used)]).astype(np.int32)
    kv_page_indices = np.concatenate(
        [np.arange(b * pages_per_req, b * pages_per_req + pages_used[b])
         for b in range(B)]
    ).astype(np.int32)
    last_page = np.asarray(
        [l - (p - 1) * PS for l, p in zip(prompt_lens, pages_used)], np.int32
    )
    bi, tok_pos = fi.get_batch_indices_positions(
        jnp.asarray(qo_indptr), jnp.asarray(seq_lens), total_q
    )
    prefill = fi.BatchPrefillWithPagedKVCacheWrapper(kv_layout="HND")
    prefill.plan(
        qo_indptr, kv_page_indptr, kv_page_indices, last_page,
        cfg.num_qo_heads, cfg.num_kv_heads, cfg.head_dim, PS, causal=True,
    )

    x = params["embed"][flat_tokens].astype(cfg.dtype)
    new_caches = []
    for li, layer in enumerate(params["layers"]):
        from flashinfer_tpu.models.llama import _mm, _pre_quant

        h = rmsnorm(x, layer["input_norm"], cfg.rms_eps)
        pre = _pre_quant(h, layer)
        qp = _mm(h, layer, "q_proj", pre).reshape(
            total_q, cfg.num_qo_heads, cfg.head_dim)
        kp = _mm(h, layer, "k_proj", pre).reshape(
            total_q, cfg.num_kv_heads, cfg.head_dim)
        vp = _mm(h, layer, "v_proj", pre).reshape(
            total_q, cfg.num_kv_heads, cfg.head_dim)
        qp, kp = apply_rope_pos_ids(qp, kp, pos, rope_theta=cfg.rope_theta)
        kc, vc = caches[li]
        # append into the HND paged cache (append op expects NHD views)
        kc_n, vc_n = fi.append_paged_kv_cache(
            kp, vp, bi, tok_pos,
            (jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2)),
            jnp.asarray(kv_page_indices), jnp.asarray(kv_page_indptr),
            None, "NHD",
        )
        kc, vc = jnp.swapaxes(kc_n, 1, 2), jnp.swapaxes(vc_n, 1, 2)
        new_caches.append((kc, vc))
        attn = prefill.run(qp, (kc, vc))
        x = x + _mm(attn.reshape(total_q, -1), layer, "o_proj").astype(
            cfg.dtype)
        h2 = rmsnorm(x, layer["post_norm"], cfg.rms_eps)
        pre2 = _pre_quant(h2, layer, "gate_proj")
        mlp = jnp.concatenate(
            [_mm(h2, layer, "gate_proj", pre2),
             _mm(h2, layer, "up_proj", pre2)], -1)
        x = x + _mm(silu_and_mul(mlp), layer, "down_proj").astype(cfg.dtype)
    caches = new_caches
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    all_logits = _mm(x, params, "lm_head").astype(jnp.float32)
    # decode starts from each request's LAST prompt-token logits
    last_idx = jnp.asarray(qo_indptr[1:] - 1, jnp.int32)
    logits = all_logits[last_idx]
    kv_lens = jnp.asarray(seq_lens)
    out_tokens = [[] for _ in range(B)]
    # the whole ragged batch prefilled in one pass: each request's
    # prompt chunk lands now (closing its queue window)
    for b, rid in enumerate(rids):
        obs.prefill_chunk(rid, prompt_lens[b])

    if engine:
        _serve_through_engine(cfg, params, prompts, max_new_tokens,
                              np.asarray(jnp.argmax(logits, -1)))

    # ---- per-op decode loop with sampling pipeline.  The jitted step
    # is hoisted OUT of the loop (one trace, then replay): re-entering
    # llama_decode_step eagerly re-dispatched every op per token.
    step_fn = jax.jit(
        functools.partial(llama_decode_step, use_pallas=use_pallas),
        static_argnums=(1,),  # cfg: frozen hashable dataclass
    )
    pipe = LogitsPipe([Temperature(), Softmax(), TopK(), TopP(), Sample()])
    key = jax.random.PRNGKey(seed + 1)
    for step in range(max_new_tokens):
        key, sk = jax.random.split(key)
        tokens = pipe(logits, key=sk, temperature=0.8, top_k=40, top_p=0.95)
        for b in range(B):
            out_tokens[b].append(int(tokens[b]))
            obs.decode_step(rids[b])
        logits, caches = step_fn(
            params, cfg, tokens, kv_lens, caches, page_table, kv_lens,
        )
        kv_lens = kv_lens + 1
    for rid in rids:
        obs.request_finish(rid)
    return out_tokens


def _serve_through_engine(cfg, params, prompts, max_new_tokens,
                          first_tokens):
    """The prompts through ``ServingEngine``: it allocates the pages,
    chunks the prefill, batches the decode and samples (greedy here, so
    the first token of a request is a function of its prompt alone and
    ``first_tokens``, the argmax of the hand-built prefill, must be
    what it serves)."""
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    page_size = 8
    pages = -(-(max(map(len, prompts)) + max_new_tokens) // page_size)
    eng = ServingEngine(cfg, params, EngineConfig(
        num_pages=1 + len(prompts) * pages, page_size=page_size,
        max_batch=len(prompts), prefill_budget_tokens=16,
        max_seq_tokens=pages * page_size,
        sampling=SamplingConfig(top_k=1)))
    for b, prompt in enumerate(prompts):
        eng.submit(EngineRequest(f"req{b}.engine", prompt,
                                 max_new_tokens=max_new_tokens))
    served = eng.run()
    firsts = [served[f"req{b}.engine"][0] for b in range(len(prompts))]
    assert firsts == [int(t) for t in first_tokens], (
        f"engine's first tokens {firsts} != the hand-built prefill's "
        f"argmax {list(first_tokens)}")
    assert eng.num_traces == len(eng._rung_traced), (
        f"{eng.num_traces} traces for {len(eng._rung_traced)} rungs — "
        "the compile-once contract broke")
    for b in range(len(prompts)):
        print(f"engine request {b}: generated {served[f'req{b}.engine']}")
    print(f"# engine parity: first tokens equal the prefill's argmax, "
          f"{eng.num_traces} traces for {len(eng._rung_traced)} rungs")


def generate_stepwise(model: str, prompt_lens, max_new_tokens=8, seed=0):
    """Serving loop for the MoE/MLA model families (mixtral, deepseek):
    the prompt is consumed token-by-token through the SAME paged decode
    step that serves generation — the semantically-real serving flow for
    an example (production prefill for these families batches tokens;
    the llama path above shows that shape with the prefill wrapper)."""
    B = len(prompt_lens)
    PS = 8
    max_len = max(prompt_lens) + max_new_tokens
    pages_per_req = -(-max_len // PS)
    num_pages = B * pages_per_req
    page_table = jnp.arange(num_pages, dtype=jnp.int32).reshape(
        B, pages_per_req)
    use_pallas = jax.default_backend() == "tpu"

    if model == "mixtral":
        from flashinfer_tpu.models import (
            MixtralConfig, init_mixtral_params, mixtral_decode_step,
        )

        cfg = MixtralConfig.tiny(num_layers=2)
        params = init_mixtral_params(jax.random.PRNGKey(seed), cfg)
        caches = [
            (jnp.zeros((num_pages, cfg.num_kv_heads, PS, cfg.head_dim),
                       cfg.dtype),) * 2
            for _ in range(cfg.num_layers)
        ]
        step = jax.jit(functools.partial(
            mixtral_decode_step, params, cfg, use_pallas=use_pallas))
    elif model == "deepseek":
        from flashinfer_tpu.models import (
            DeepseekConfig, deepseek_decode_step, init_deepseek_params,
        )

        cfg = DeepseekConfig.tiny(num_layers=2)
        params = init_deepseek_params(jax.random.PRNGKey(seed), cfg)
        caches = [
            (jnp.zeros((num_pages, PS, cfg.kv_lora_rank), cfg.dtype),
             jnp.zeros((num_pages, PS, 128), cfg.dtype))  # lane-padded kpe
            for _ in range(cfg.num_layers)
        ]
        step = jax.jit(functools.partial(
            deepseek_decode_step, params, cfg, use_pallas=use_pallas))
    else:
        raise ValueError(f"unknown model {model!r}")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, l) for l in prompt_lens]
    rids = [f"{model}.req{b}" for b in range(B)]
    for rid in rids:
        obs.request_begin(rid)
    maxp = max(prompt_lens)
    kv_lens = jnp.zeros((B,), jnp.int32)
    # consume prompts; each request's HANDOFF logits are captured at its
    # own last prompt token (shorter requests then idle by re-feeding
    # that token — the re-fed write lands in the slot the first
    # generated token overwrites, so the cache enters generation exact)
    handoff = jnp.zeros((B, cfg.vocab_size), jnp.float32)
    for t in range(maxp):
        toks = jnp.asarray(
            [p[min(t, len(p) - 1)] for p in prompts], jnp.int32)
        active = jnp.asarray([t < l for l in prompt_lens])
        positions = jnp.minimum(kv_lens, t)
        logits, caches = step(toks, positions, caches, page_table, kv_lens)
        # stepwise prefill: each ACTIVE request advanced one prompt token
        for b, rid in enumerate(rids):
            if t < prompt_lens[b]:
                obs.prefill_chunk(rid, 1)
        finished_now = jnp.asarray([t == l - 1 for l in prompt_lens])
        handoff = jnp.where(finished_now[:, None], logits, handoff)
        kv_lens = kv_lens + active.astype(jnp.int32)
    logits = handoff

    pipe = LogitsPipe([Temperature(), Softmax(), TopK(), TopP(), Sample()])
    key = jax.random.PRNGKey(seed + 1)
    out_tokens = [[] for _ in range(B)]
    for _ in range(max_new_tokens):
        key, sk = jax.random.split(key)
        tokens = pipe(logits, key=sk, temperature=0.8, top_k=40, top_p=0.95)
        for b in range(B):
            out_tokens[b].append(int(tokens[b]))
            obs.decode_step(rids[b])
        logits, caches = step(tokens, kv_lens, caches, page_table, kv_lens)
        kv_lens = kv_lens + 1
    for rid in rids:
        obs.request_finish(rid)
    return out_tokens


if __name__ == "__main__":
    print(f"# running on {jax.devices()[0].platform} "
          f"({jax.devices()[0].device_kind})")
    int8 = "int8" in sys.argv
    engine = "--engine" in sys.argv
    model = next((a for a in sys.argv[1:] if a in ("mixtral", "deepseek")),
                 None)
    if model:
        outs = generate_stepwise(model, [5, 9], max_new_tokens=6)
        label = model
    else:
        outs = generate([5, 9], max_new_tokens=6, int8_weights=int8,
                        engine=engine)
        label = "llama" + (" int8 weights" if int8 else "") + \
            (" engine" if engine else "")
    for b, toks in enumerate(outs):
        print(f"request {b}: generated {toks}")
    _print_lifecycle_summary(label)
    # FLASHINFER_TPU_SPANS_OUT=<path>: export this run's flight
    # recorder as the unified chrome trace (spans + registry snapshot
    # on the shared clock base) — the file `python -m
    # flashinfer_tpu.obs trace` produces from its built-in loop, here
    # from a REAL generate run
    out_path = os.environ.get("FLASHINFER_TPU_SPANS_OUT")
    if out_path and obs.spans_enabled():
        from flashinfer_tpu.obs import export, spans

        trace = export.write_unified_trace(out_path, obs.snapshot(),
                                           None, spans.drain())
        problems = export.validate_chrome_trace(trace,
                                                require_lifecycle=True)
        assert not problems, problems
        print(f"# unified trace -> {out_path} "
              f"({len(trace['traceEvents'])} events, schema-valid)")
    print(f"generate.py ok ({label})")
