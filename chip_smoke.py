#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` serves a handful of requests through
``flashinfer_tpu.serve.ServingEngine`` (built by
``EngineConfig.from_knobs`` on the Pallas tier) at Llama-3-8B widths with
random weights from ``--seed``, checks what comes out, and prints as its
LAST line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It runs in ONE process (a chip belongs to one process), needs no network,
and exits non-zero — with no result line — when JAX finds no TPU, when a
phase raises, or when a check fails.  Nothing here catches an exception.

``--chips 4`` runs ONLY the sharded serving step
(``parallel.plan.ShardedServingStep`` on a 4-device tp mesh) and the
unsharded step it is compared with.

``--tiny`` changes sizes only, for a rehearsal on the CPU
(``JAX_PLATFORMS=cpu``; with ``--chips 4`` also
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  Its last line
says ``"rehearsal"``, never ``"ok"``: only a TPU run is a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

# The check of the kernel tier against the dense reference tier is made on
# what attention feeds: the K/V rows every layer writes to the paged cache,
# for every prompt AND generated position (the reference tier re-serves
# each request's tokens as one prompt — teacher forcing — so both tiers see
# the same inputs).  Rows are bf16 with std ~1.3 (h @ W_k, |h| ~ 1,
# W ~ N(0, 0.02^2), K = 4096), so one bf16 ulp at that size is ~0.008; the
# tiers differ in accumulation order and in bf16-vs-f32 softmax operands,
# and a broken kernel is off by O(1).
KV_ATOL = 0.125
# Greedy last tokens of the two tiers may differ where the top two of
# 128256 random-weight logits sit within that noise; a broken last layer
# or sampler would agree on ~none.
LAST_TOKEN_AGREE_MIN = 0.5
# --chips 4, sharded vs unsharded int8 step at two layers: int8 codes a
# layer appends may sit this far apart (of 127), and one token in this many
# may differ per step (top-40 sampling over near-flat random-weight logits
# turns a 1% logit difference into an occasional other token: 4-8 of 64
# per step on the chip, PR 21).  A sharding fault — a wrong head, a
# missing reduction — moves every code and token.
SHARD_CODE_ATOL = 3
SHARD_TOKEN_DIFFER_MAX = 4


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything ``--tiny`` changes."""

    model: dict              # LlamaConfig overrides (widths stay published)
    num_pages: int           # KV pool of the main engine, incl. scratch
    max_seq_tokens: int
    long_prompt: int         # the >= 1k-token prompt
    shared_prefix: int       # two requests share this many leading tokens
    other_prompts: tuple     # (suffix A, suffix B, short, mid, tiny)
    new_tokens: int
    cmp_layers: int          # depth of the tier-vs-tier check
    cmp_max_seq: int         # ... and its context (reference windows are
    cmp_budget: int          #     [T, K, Hkv, D] f32: T and K stay small)
    cmp_pages: int
    cmp_prompts: tuple       # (chunked, prefix, suffix A, suffix B, short)
    cmp_new_tokens: int
    # --chips 4: (bs, ctx, layers) of the sharded int8 step
    shard: tuple


FULL = Sizes(
    model=dict(num_layers=16),
    num_pages=4096, max_seq_tokens=2048,
    long_prompt=1536, shared_prefix=256, other_prompts=(64, 48, 40, 300, 9),
    new_tokens=32,
    cmp_layers=2, cmp_max_seq=256, cmp_budget=128, cmp_pages=160,
    cmp_prompts=(150, 64, 20, 30, 10), cmp_new_tokens=8,
    shard=(64, 1024, 16),
)
TINY = Sizes(
    model=dict(num_layers=2, vocab_size=512, hidden_size=256,
               intermediate_size=512, num_qo_heads=8, num_kv_heads=4,
               head_dim=32),
    num_pages=48, max_seq_tokens=160,
    long_prompt=100, shared_prefix=32, other_prompts=(8, 6, 5, 20, 3),
    new_tokens=6,
    cmp_layers=2, cmp_max_seq=96, cmp_budget=32, cmp_pages=40,
    cmp_prompts=(40, 32, 5, 7, 4), cmp_new_tokens=4,
    shard=(4, 64, 2),
)


class CacheCounter:
    """Hits and writes of JAX's persistent compilation cache."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return self.hits, self.writes


def _prompts(rng, vocab, prefix, suffixes, others):
    """{rid: tokens}: ``share_a`` and ``share_b`` open with the same
    ``prefix`` tokens, the ``others`` ({rid: length}) share nothing."""
    import numpy as np

    shared = rng.integers(1, vocab, prefix)
    out = {rid: np.concatenate([shared, rng.integers(1, vocab, n)])
           for rid, n in zip(("share_a", "share_b"), suffixes)}
    out.update({rid: rng.integers(1, vocab, n) for rid, n in others.items()})
    return out


def _requests(specs):
    """EngineRequests from {rid: (tokens, new tokens)}."""
    from flashinfer_tpu.serve import EngineRequest

    return {rid: EngineRequest(rid, list(map(int, toks)), max_new_tokens=n)
            for rid, (toks, n) in specs.items()}


def _serve(eng, waves, leader, cache):
    """Drive ``eng`` to completion: submit wave 0, step until ``leader``
    has prefilled (its full pages are then in the prefix trie), submit
    wave 1, finish.  Returns per-step facts."""
    log = []

    def step():
        traces, (h0, w0) = eng.num_traces, cache.snapshot()
        t0 = time.perf_counter()
        facts = eng.step()  # ends with the sampled tokens on the host
        facts["seconds"] = time.perf_counter() - t0
        facts["traced"] = eng.num_traces > traces
        h1, w1 = cache.snapshot()
        facts["cache_hits"], facts["cache_writes"] = h1 - h0, w1 - w0
        log.append(facts)

    for r in waves[0]:
        eng.submit(r)
    while leader.kv_len < len(leader.prompt):
        step()
    for r in waves[1]:
        eng.submit(r)
    while eng.has_work():
        if len(log) > 10000:
            raise RuntimeError("engine made no progress")
        step()
    return log


def _report_rungs(log, label):
    for f in log:
        if f["traced"]:
            _say(f"{label}: rung {f['rung']} first step {f['seconds']:.1f} s "
                 f"(trace + compile + run; compile cache: {f['cache_hits']} "
                 f"hit(s), {f['cache_writes']} written)")


def _kv_rows(eng, req, n):
    """[layers, 2, n, Hkv, D] f32: the first ``n`` K/V rows of ``req``."""
    import jax.numpy as jnp

    pages = jnp.asarray(req.pages, jnp.int32)
    out = []
    for kc, vc in eng.caches:
        pair = []
        for c in (kc, vc):
            w = c[pages]  # [npages, Hkv, PS, D]
            w = jnp.swapaxes(w, 1, 2).reshape(-1, c.shape[1], c.shape[3])
            pair.append(w[:n].astype(jnp.float32))
        out.append(jnp.stack(pair))
    return jnp.stack(out)


def phase_compare(mcfg, params, sz: Sizes, seed: int, cache) -> None:
    """Kernel tier vs the dense reference tier, same widths, small depth
    and context."""
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.serve import (EngineConfig, SamplingConfig,
                                      ServingEngine)

    cfg = dataclasses.replace(mcfg, num_layers=sz.cmp_layers)
    cparams = dict(params, layers=params["layers"][:sz.cmp_layers])
    rng = np.random.default_rng(seed + 1)
    chunked, prefix, suf_a, suf_b, short = sz.cmp_prompts
    prompts = _prompts(rng, cfg.vocab_size, prefix, (suf_a, suf_b),
                       dict(chunked=chunked, short=short))

    def run(backend, specs):
        # role="prefill" keeps a finished request's pages, so its K/V can
        # be read back after the run; greedy, so the last token does not
        # hang on a sampling seed
        ecfg = EngineConfig.from_knobs(
            cfg, num_pages=sz.cmp_pages, max_seq_tokens=sz.cmp_max_seq,
            attention_backend=backend,
            prefill_budget_tokens=sz.cmp_budget, role="prefill",
            sampling=SamplingConfig(temperature=1.0, top_k=1), seed=seed)
        eng = ServingEngine(cfg, cparams, ecfg)
        reqs = _requests(specs)
        waves = ([reqs["chunked"], reqs["share_a"]],
                 [reqs["share_b"], reqs["short"]])
        log = _serve(eng, waves, reqs["share_a"], cache)
        _report_rungs(log, f"compare/{backend}")
        if not reqs["share_b"].hit_tokens:
            raise AssertionError(f"{backend}: no prefix-trie hit")
        return eng, reqs

    n_new = sz.cmp_new_tokens
    keng, kreqs = run("kernel", {k: (v, n_new) for k, v in prompts.items()})
    # teacher forcing: the reference tier prefills prompt + the kernel
    # tier's first n_new - 1 tokens and samples one — the same inputs
    replay = {k: (list(prompts[k]) + kreqs[k].out_tokens[:-1], 1)
              for k in prompts}
    reng, rreqs = run("reference", replay)

    worst, agree = 0.0, 0
    for rid in prompts:
        n = len(prompts[rid]) + n_new - 1
        if not kreqs[rid].kv_len == rreqs[rid].kv_len == n:
            raise AssertionError(f"{rid}: the tiers hold different K/V spans")
        dev = jnp.abs(_kv_rows(keng, kreqs[rid], n)
                      - _kv_rows(reng, rreqs[rid], n))
        if not bool(jnp.all(jnp.isfinite(dev))):
            raise AssertionError(f"{rid}: non-finite K/V rows")
        per_layer = [float(x) for x in jnp.max(dev, axis=(1, 2, 3, 4))]
        worst = max(worst, max(per_layer))
        same = kreqs[rid].out_tokens[-1] == rreqs[rid].out_tokens[-1]
        agree += same
        _say(f"compare {rid}: {n} positions, max |dK/dV| per layer "
             f"{[round(x, 4) for x in per_layer]}, last token "
             f"{'same' if same else 'differs'}")
    _say(f"kernel-vs-reference: max |dK/dV| {worst:.4f} (tolerance "
         f"{KV_ATOL}), greedy last token agrees on {agree}/{len(prompts)} "
         f"(at least {LAST_TOKEN_AGREE_MIN:.0%} asked)")
    if worst > KV_ATOL:
        raise AssertionError(
            f"kernel tier K/V deviates {worst:.4f} > {KV_ATOL} from the "
            "reference tier")
    if agree < LAST_TOKEN_AGREE_MIN * len(prompts):
        raise AssertionError(
            f"greedy last token agrees on only {agree}/{len(prompts)}")


def phase_serve(mcfg, params, ecfg, sz: Sizes, seed: int, cache,
                on_tpu) -> None:
    """The main path at full width: submit -> chunked prefill -> paged
    append -> decode -> sampling -> finish."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.serve import ServingEngine

    eng = ServingEngine(mcfg, params, ecfg)

    # record each rung's argument shapes on the way through (the
    # kernel-presence check below lowers them again without compiling)
    shapes, jitted = {}, eng._step

    def spy(*args):
        shapes.setdefault(args[1].shape[0], jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args))
        return jitted(*args)

    eng._step = spy

    rng = np.random.default_rng(seed)
    suf_a, suf_b, short, mid, tiny = sz.other_prompts
    specs = _prompts(rng, mcfg.vocab_size, sz.shared_prefix, (suf_a, suf_b),
                     dict(long=sz.long_prompt, short=short, mid=mid,
                          tiny=tiny))
    reqs = _requests({k: (v, sz.new_tokens) for k, v in specs.items()})
    waves = ([reqs["long"], reqs["share_a"], reqs["short"]],
             [reqs["share_b"], reqs["mid"], reqs["tiny"]])
    t0 = time.perf_counter()
    log = _serve(eng, waves, reqs["share_a"], cache)
    wall = time.perf_counter() - t0
    _report_rungs(log, "serve")

    rungs = sorted({f["rung"] for f in log})
    _say(f"served {len(reqs)} requests ({sum(map(len, specs.values()))} "
         f"prompt tokens, {sz.new_tokens} new each) in {len(log)} steps, "
         f"{wall:.1f} s; rungs used {rungs}; traces {eng.num_traces}")
    for rid, r in reqs.items():
        if r.state != "finished" or len(r.out_tokens) != sz.new_tokens:
            raise AssertionError(
                f"{rid}: {r.state}, {len(r.out_tokens)} tokens")
        if not all(0 <= t < mcfg.vocab_size for t in r.out_tokens):
            raise AssertionError(f"{rid}: token out of range")
    if len(rungs) < 2:
        raise AssertionError(f"only rungs {rungs} exercised")
    if eng.num_traces > len(rungs):
        raise AssertionError(
            f"{eng.num_traces} traces for rungs {rungs}: a rung retraced")
    if reqs["share_b"].hit_tokens < sz.shared_prefix // ecfg.page_size \
            * ecfg.page_size:
        raise AssertionError(
            f"prefix hit {reqs['share_b'].hit_tokens} tokens")
    _say(f"prefix trie: share_b adopted {reqs['share_b'].hit_tokens} cached "
         "tokens")
    for li, (kc, vc) in enumerate(eng.caches):
        if not bool(jnp.all(jnp.isfinite(kc.astype(jnp.float32)))
                    & jnp.all(jnp.isfinite(vc.astype(jnp.float32)))):
            raise AssertionError(f"layer {li}: non-finite KV cache")

    # decode window: steps that only advanced running requests, compiled
    # before.  An observation of one run, not a metric.
    dec = [f for f in log if not f["traced"] and f["tokens"] == f["sampled"]]
    if dec:
        secs = sorted(f["seconds"] for f in dec)
        toks = sum(f["tokens"] for f in dec)
        _say(f"decode window: {len(dec)} steps, {toks} tokens, median step "
             f"{secs[len(secs) // 2] * 1e3:.2f} ms, "
             f"{toks / sum(secs):.1f} tokens/s (observation)")

    # the compiled step holds kernels, not interpreted or XLA stand-ins
    for rung in rungs:
        n = jitted.lower(*shapes[rung]).as_text().count("tpu_custom_call")
        _say(f"rung {rung}: {n} tpu_custom_call sites in the lowered step")
        if on_tpu and n == 0:
            raise AssertionError(f"rung {rung}: no Pallas kernel in the step")


def run_one_chip(sz: Sizes, seed: int, on_tpu: bool, cache) -> None:
    import jax
    import jax.numpy as jnp

    from flashinfer_tpu import native
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.obs import hwspec
    from flashinfer_tpu.serve import EngineConfig, SamplingConfig

    mcfg = LlamaConfig.llama3_8b(**sz.model)
    full = LlamaConfig.llama3_8b()
    ecfg = EngineConfig.from_knobs(
        mcfg, num_pages=sz.num_pages, max_seq_tokens=sz.max_seq_tokens,
        attention_backend="kernel", seed=seed,
        sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95))
    _say(f"engine: rungs {ecfg.rungs()}, max_batch {ecfg.max_batch}, "
         f"prefill budget {ecfg.prefill_budget_tokens}, page "
         f"{ecfg.page_size}, kv_offload {ecfg.kv_offload}/"
         f"{ecfg.spill_policy}")
    item = int(jnp.dtype(mcfg.dtype).itemsize)
    h, kvd = mcfg.hidden_size, mcfg.num_kv_heads * mcfg.head_dim
    layer_b = item * h * (2 * mcfg.num_qo_heads * mcfg.head_dim + 2 * kvd
                          + 3 * mcfg.intermediate_size)
    ends_b = item * 2 * mcfg.vocab_size * h
    kv_b = item * 2 * sz.num_pages * ecfg.page_size * kvd
    _say(f"model: Llama-3-8B widths (hidden {h}, heads "
         f"{mcfg.num_qo_heads}/{mcfg.num_kv_heads} x {mcfg.head_dim}, mlp "
         f"{mcfg.intermediate_size}, vocab {mcfg.vocab_size}, "
         f"{jnp.dtype(mcfg.dtype).name}); depth {mcfg.num_layers} of "
         f"{full.num_layers}")
    _say(f"why that depth: a layer is {layer_b / 2**20:.0f} MiB, embed + "
         f"lm_head {ends_b / 2**30:.2f} GiB, KV {kv_b / 2**20:.0f} MiB per "
         f"layer for {sz.num_pages * ecfg.page_size} tokens: "
         f"{(mcfg.num_layers * (layer_b + kv_b) + ends_b) / 2**30:.1f} GiB "
         f"at depth {mcfg.num_layers}, "
         f"{(full.num_layers * (layer_b + kv_b) + ends_b) / 2**30:.1f} GiB "
         f"at {full.num_layers}")
    dev = jax.devices()[0]
    stats = dev.memory_stats() if on_tpu else None
    if stats:
        need = mcfg.num_layers * (layer_b + kv_b) + ends_b
        _say(f"device memory limit {stats['bytes_limit'] / 2**30:.2f} GiB")
        if need > 0.92 * stats["bytes_limit"]:
            raise AssertionError("that depth does not fit this device")
    _say(f"chip spec: {hwspec.detect_chip()}; planner: "
         f"{'native .so (csrc/planner.cpp)' if native.get_lib() else 'numpy'}")

    t0 = time.perf_counter()
    params = init_llama_params(jax.random.PRNGKey(seed), mcfg)
    jax.block_until_ready(params)
    _say(f"random weights from seed {seed}: {time.perf_counter() - t0:.1f} s")

    phase_compare(mcfg, params, sz, seed, cache)
    phase_serve(mcfg, params, ecfg, sz, seed, cache, on_tpu)
    if stats:
        peak = dev.memory_stats()["peak_bytes_in_use"]
        _say(f"HBM peak {peak} bytes ({peak / 2**30:.2f} GiB)")


def run_four_chips(sz: Sizes, seed: int, on_tpu: bool, cache) -> None:
    """The sharded fused step on a 4-device tp mesh: run at full depth,
    and compared at two layers with the unsharded fused step on one
    device — same weights, same state, same keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flashinfer_tpu.models.llama import LlamaConfig
    from flashinfer_tpu.parallel.plan import (
        ShardedServingStep, make_serving_mesh, split_shard_weights_for_spec)
    from flashinfer_tpu.quantization import quantize_int8
    from flashinfer_tpu.serve.shard import Int8ShardSpec, build_fused_step
    from flashinfer_tpu.utils import is_tpu

    m = LlamaConfig.llama3_8b(**{k: v for k, v in sz.model.items()
                                 if k != "num_layers"})
    bs, ctx, layers = sz.shard
    ps, steps = 16, 4
    devices = jax.devices()[:4]
    plan = make_serving_mesh(4, hidden=m.hidden_size,
                             num_qo_heads=m.num_qo_heads,
                             num_kv_heads=m.num_kv_heads, devices=devices)
    spec = Int8ShardSpec(
        bs=bs, hidden=m.hidden_size, hq=m.num_qo_heads, hkv=m.num_kv_heads,
        hd=m.head_dim, inter=m.intermediate_size, vocab_shard=m.vocab_size,
        page_size=ps, use_pallas=is_tpu())
    _say(f"mesh {plan.mesh_axes} over {[d.id for d in devices]}; int8 shard "
         f"step: hidden {spec.hidden}, heads {spec.hq}/{spec.hkv} x "
         f"{spec.hd}, mlp {spec.inter}, vocab {spec.vocab_shard}, depth "
         f"{layers}, batch {bs}, context {ctx}, pallas={spec.use_pallas}")

    # weights and state are made on device 0 (the unsharded twin's home)
    # and then laid out over the mesh: nothing else sits on the default
    # device
    key = jax.random.PRNGKey(seed)

    def qw(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=0)
        return wq, ws.reshape(1, -1)

    ks = jax.random.split(key, 6 * layers + 2)
    H, I = spec.hidden, spec.inter
    layer_ws = [(
        *qw(ks[6 * i], (H, spec.qdim + 2 * spec.kvdim)),
        *qw(ks[6 * i + 1], (spec.qdim, H)),
        *qw(ks[6 * i + 2], (H, 2 * I)),
        *qw(ks[6 * i + 3], (I, H)),
        jax.random.normal(ks[6 * i + 4], (H,)) * 0.02 + 1.0,
        jax.random.normal(ks[6 * i + 5], (H,)) * 0.02 + 1.0,
    ) for i in range(layers)]
    head, head_s = qw(ks[-1], (H, spec.vocab_shard))
    ppr = ctx // ps
    npages = bs * ppr
    pt0 = np.random.default_rng(seed).permutation(npages) \
        .astype(np.int32).reshape(bs, ppr)
    x0 = jax.random.normal(ks[-2], (bs, H), jnp.bfloat16)

    def mk_caches():
        return [tuple(jax.random.randint(
            jax.random.fold_in(ks[-2], 2 * i + j),
            (npages, spec.hkv, ps, spec.hd), -127, 127, jnp.int8)
            for j in range(2)) for i in range(layers)]

    in_sh, _ = plan.shard_step_shardings(layers)
    split = split_shard_weights_for_spec(layer_ws, spec)
    s_ws = jax.device_put(split, in_sh[1])
    s_head, s_head_s = jax.device_put((head, head_s), (in_sh[3], in_sh[4]))
    del split
    model_b = sum(a.nbytes for w in s_ws for a in w.values()) + s_head.nbytes
    worst = max(sum(s.data.nbytes for a in jax.tree.leaves((s_ws, s_head))
                    for s in a.addressable_shards if s.device == d)
                for d in devices)
    _say(f"model {model_b / 2**30:.2f} GiB; largest share on one device "
         f"{worst / 2**30:.2f} GiB")
    if worst > 0.3 * model_b:
        raise AssertionError("a device holds more than its tp share")

    def sharded(depth):
        step = ShardedServingStep()
        step.plan(spec, plan, num_layers=depth)
        return step

    def chain(fn, ws, hd, hs, put):
        depth = len(ws)
        caches, p = put(mk_caches()[:depth], 2), put(jnp.asarray(pt0), 5)
        lens = put(jnp.full((bs,), ctx - 1, jnp.int32), 6)
        sk = put(jax.random.PRNGKey(seed + 3), 7)
        toks, secs = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            tok, caches, p, lens, sk = fn(put(x0, 0), ws, caches, hd, hs,
                                          p, lens, sk)
            toks.append(np.asarray(tok))
            secs.append(time.perf_counter() - t0)
        return toks, caches, secs

    def on_mesh(a, i):
        sh = in_sh[i]
        return jax.device_put(a, sh[:len(a)] if i == 2 else sh)

    def on_dev0(a, i):
        return jax.device_put(a, devices[0])

    # 1. the sharded step at full depth: starts, compiles once, holds
    #    collectives and kernels, samples tokens of the vocabulary
    step = sharded(layers)
    h0, w0 = cache.snapshot()
    toks, caches, secs = chain(step.run, s_ws, s_head, s_head_s, on_mesh)
    h1, w1 = cache.snapshot()
    _say(f"sharded step, depth {layers}: first {secs[0]:.1f} s (compile "
         f"cache: {h1 - h0} hit(s), {w1 - w0} written), then "
         f"{[round(t * 1e3, 2) for t in secs[1:]]} ms (observation); traces "
         f"{step.num_traces}")
    if step.num_traces != 1:
        raise AssertionError(f"{step.num_traces} traces of the sharded step")
    if not all(((0 <= t) & (t < spec.vocab_shard)).all() for t in toks):
        raise AssertionError("sharded step sampled outside the vocabulary")
    text = step._step.jitted.lower(
        x0, s_ws, caches, s_head, s_head_s, jnp.asarray(pt0),
        jnp.full((bs,), ctx - 1, jnp.int32),
        jax.random.PRNGKey(0)).as_text()
    ncoll = text.count("all_reduce") + text.count("all_gather")
    _say(f"lowered sharded step: {ncoll} all_reduce/all_gather sites, "
         f"{text.count('tpu_custom_call')} tpu_custom_call sites")
    if ncoll == 0 or (on_tpu and "tpu_custom_call" not in text):
        raise AssertionError("no collectives or no kernels in the step")
    del caches

    # 2. sharded against unsharded at the depth where they can be
    #    compared.  The two programs are the same arithmetic (int32
    #    reductions over tp), but not the same XLA fusions: on the chip a
    #    few per cent of the int8 codes a layer appends land one step
    #    apart, and a random-weight int8 network doubles that from layer
    #    to layer (measured at depth 16: codes up to 41 apart, tokens
    #    unrelated).  Two layers are deep enough to cross every
    #    collective of the step and shallow enough to compare.
    depth = sz.cmp_layers
    s_toks, s_caches, _ = chain(sharded(depth).run, s_ws[:depth], s_head,
                                s_head_s, on_mesh)
    r_toks, r_caches, r_secs = chain(build_fused_step(spec),
                                     layer_ws[:depth], head, head_s, on_dev0)
    per_layer = []
    for sc, rc in zip(s_caches, r_caches):
        d = jnp.stack([jnp.abs(on_dev0(x, 0).astype(jnp.int32)
                               - y.astype(jnp.int32)) for x, y in zip(sc, rc)])
        per_layer.append((int(jnp.max(d)), int(jnp.sum(d > 0))))
    differ = [int((a != b).sum()) for a, b in zip(s_toks, r_toks)]
    _say(f"sharded vs unsharded, depth {depth}: tokens differing per step "
         f"{differ} of {bs} (at most {bs // SHARD_TOKEN_DIFFER_MAX} asked); "
         f"cache codes per layer (largest difference, codes differing) "
         f"{per_layer} (at most {SHARD_CODE_ATOL} apart asked)")
    if max(differ) > bs // SHARD_TOKEN_DIFFER_MAX \
            or max(m for m, _ in per_layer) > SHARD_CODE_ATOL:
        raise AssertionError("sharded and unsharded steps disagree")
    if on_tpu:  # device 0 also made the weights and holds the unsharded twin
        for d in devices:
            _say(f"device {d.id} HBM peak "
                 f"{d.memory_stats()['peak_bytes_in_use'] / 2**30:.2f} GiB")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes (the only way to run off a TPU)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded step and its comparison only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()  # raises where JAX cannot start its backend
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: JAX found no TPU ({device}); this is the chip "
              "check and does not fall back. --tiny rehearses on the CPU.",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX has {len(devices)}", file=sys.stderr)
        return 2

    from flashinfer_tpu import env
    from flashinfer_tpu.utils import resolve_backend, use_interpret

    if on_tpu and (use_interpret() or resolve_backend("auto") != "pallas"):
        print("chip_smoke: FLASHINFER_TPU_INTERPRET / FLASHINFER_TPU_BACKEND "
              "steer the kernels off Mosaic; unset them", file=sys.stderr)
        return 2
    env.enable_compilation_cache()
    cache = CacheCounter()
    _say(f"device {device}; jax {jax.__version__}; compile cache at "
         f"{env.xla_cache_dir()}")

    sz = TINY if args.tiny else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(sz, args.seed, on_tpu, cache)
    else:
        run_one_chip(sz, args.seed, on_tpu, cache)
    hits, writes = cache.snapshot()
    _say(f"done in {time.perf_counter() - t0:.1f} s; compile cache: {hits} "
         f"hit(s), {writes} entr{'y' if writes == 1 else 'ies'} written")
    if on_tpu:
        print(json.dumps({"ok": True, "device": device}))
    else:
        print(json.dumps({"rehearsal": "passed", "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
