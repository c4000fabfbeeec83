"""The main path's kernels, compiled for a v5e that is described and not
attached (guide `on-chip-measurement` section 2).

Interpret mode checks what a kernel computes; only the TPU compiler says
whether Mosaic accepts it.  The compiler is installed here, so every
tier-1 run asks it — at Llama-3-8B widths, for the kernels the serving
engine launches and for the whole engine step on a prefill rung and on the
decode rung.  A pass is a compile, never a chip run.

Only one process may load the TPU's library, so the topology is described
inside a module-scoped fixture of THIS file (never at import, in a
``skipif``, in ``parametrize`` or in conftest.py), every compile happens in
the test's own process, and all such tests live in this one file.  The
persistent compilation cache is off around them: a TPU executable written
to it cannot be read back without a chip.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

HQ, HKV, D, PS, VOCAB = 32, 8, 128, 16, 128256
SM = D ** -0.5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps it from describing
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip, with the compilation cache off and no
    trace of this process's interpret-mode runs (or, after, of ours) in
    JAX's own caches: an inner jit does not key on ``is_tpu``."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _as_on_a_v5e(mp):
    """Steer ``use_interpret()`` / ``resolve_backend()`` onto the branch a
    TPU takes (``utils.is_tpu`` is a ``functools.cache``: replace the
    name, do not call it) and load the shipped v5e tuning table."""
    from flashinfer_tpu import autotuner, utils

    mp.setattr(utils, "is_tpu", lambda: True)
    mp.setattr(autotuner, "_device_config_key", lambda: "v5e")
    mp.setattr(autotuner.AutoTuner, "_instance", None)


@pytest.fixture
def compiled_branch(monkeypatch):
    _as_on_a_v5e(monkeypatch)


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=chip)


def _like(chip, tree):
    return jax.tree.map(lambda a: _sds(chip, a.shape, a.dtype), tree)


def _compile(fn, *args):
    """Lower + compile ``fn`` for the described chip; the program must
    hold a Mosaic kernel.  Raises what the chip's compiler would."""
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text(), "no Pallas kernel lowered"
    return lowered.compile()


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode(chip, compiled_branch, kv_dtype):
    from flashinfer_tpu.ops import paged_decode_attention

    B, ppr = 64, 128
    cache = _sds(chip, (B * ppr, HKV, PS, D), kv_dtype)
    _compile(
        lambda q, kc, vc, pt, lens: paged_decode_attention(
            q, kc, vc, pt, lens, sm_scale=SM, kv_layout="HND"),
        _sds(chip, (B, HQ, D), jnp.bfloat16), cache, cache,
        _sds(chip, (B, ppr), jnp.int32), _sds(chip, (B,), jnp.int32))


def test_split_kv_decode(chip, compiled_branch):
    from flashinfer_tpu.ops.paged_decode import (
        build_decode_split_units, paged_decode_attention_split,
        split_pages_per_chunk)

    B, ppr, S = 64, 32, 2
    ppc = split_pages_per_chunk(PS, HKV, D, 2)
    plan = build_decode_split_units(
        np.arange(B * ppr, dtype=np.int32).reshape(B, ppr),
        np.full(B, ppr * PS, np.int64), num_splits=S, page_size=PS,
        pages_per_chunk=ppc)
    arrays = {k: plan[k] for k in
              ("pages", "kvlen", "wu_req", "wu_page0", "wu_kvlen")}
    cache = _sds(chip, (B * ppr, HKV, PS, D), jnp.bfloat16)
    _compile(
        lambda q, kc, vc, p: paged_decode_attention_split(
            q, kc, vc, p, num_units=int(plan["wu_req"].shape[0]),
            num_splits=S, single_chunk=bool(plan["single_chunk"]),
            pages_per_chunk=ppc, sm_scale=SM),
        _sds(chip, (B, HQ, D), jnp.bfloat16), cache, cache,
        _like(chip, arrays))


def test_flash_attention(chip, compiled_branch):
    from flashinfer_tpu.ops import flash_attention

    T = 2048
    q = _sds(chip, (T, HQ, D), jnp.bfloat16)
    kv = _sds(chip, (T, HKV, D), jnp.bfloat16)
    i32 = _sds(chip, (T,), jnp.int32)
    _compile(
        lambda q, k, v, seg, pos: flash_attention(
            q, k, v, seg, seg, pos, pos, causal=True, sm_scale=SM),
        q, kv, kv, i32, i32)


def _prefill_geometry():
    qo = np.asarray([0, 512, 640, 1664, 1701], np.int64)
    kv_lens = np.asarray([1024, 512, 2048, 333], np.int64)
    pages = -(-kv_lens // PS)
    return qo, np.concatenate([[0], np.cumsum(pages)]), \
        np.arange(int(pages.sum()), dtype=np.int64), kv_lens


@pytest.mark.parametrize("masked", [False, True])
def test_work_unit_paged_prefill(chip, compiled_branch, masked):
    """The plan carries its count of real units (``n_units``), so the
    unit axis of the grid has a dynamic bound; ``masked`` adds the
    pipelined per-unit mask operand, as the engine's level-0 launch has
    it (the kernel's other operands are manual DMAs)."""
    from flashinfer_tpu.ops.paged_prefill import (build_prefill_work_units,
                                                  fused_paged_prefill)

    qo, kvp, kvi, kv_lens = _prefill_geometry()
    mask = None
    if masked:  # causal by position, spelled as a custom mask
        mask = np.concatenate([
            (np.arange(kv)[None, :]
             <= (kv - n + np.arange(n))[:, None]).reshape(-1)
            for n, kv in zip(np.diff(qo), kv_lens)])
    plan = build_prefill_work_units(qo, kvp, kvi, kv_lens, block_q=128,
                                    pages_per_chunk=8, page_size=PS,
                                    mask_flat=mask, causal=not masked)
    statics = {k: plan.pop(k)
               for k in ("num_units", "block_q", "pages_per_chunk")}
    stats = plan.pop("stats")
    assert plan["n_units"] == stats["units"] < statics["num_units"]
    assert ("mask_bytes" in plan) == masked
    cache = _sds(chip, (len(kvi), HKV, PS, D), jnp.bfloat16)
    _compile(
        lambda q, kc, vc, p: fused_paged_prefill(
            q, kc, vc, p, sm_scale=SM, causal=not masked, return_lse=True,
            **statics),
        _sds(chip, (int(qo[-1]), HQ, D), jnp.bfloat16), cache, cache,
        _like(chip, plan))


@pytest.mark.parametrize("kv_dtype,kv_quant",
                         [("bfloat16", "none"), ("int8", "int8")])
def test_fused_ingest_prefill(chip, compiled_branch, kv_dtype, kv_quant):
    """Refused until PR 21: a float ``tpu.iota``, then a dynamic slice of
    the tiled raw-row axis that Mosaic could not prove aligned."""
    from flashinfer_tpu.ops.paged_prefill import (
        build_prefill_ingest_units, fused_paged_prefill_ingest,
        ingest_block_q, ingest_pages_per_chunk)

    # from-scratch prefill of three requests whose rows start aligned
    lens = np.asarray([512, 256, 48], np.int64)
    qo = np.concatenate([[0], np.cumsum(lens)])
    pages = -(-lens // PS)
    T = int(qo[-1])
    plan = build_prefill_ingest_units(
        qo, np.concatenate([[0], np.cumsum(pages)]),
        np.arange(int(pages.sum()), dtype=np.int64), lens,
        ingest_block_q(T), ingest_pages_per_chunk(PS), PS, causal=True)
    statics = {k: plan.pop(k)
               for k in ("num_units", "block_q", "pages_per_chunk")}
    assert plan.pop("stats")["ingest_kv_aligned"]
    cache = _sds(chip, (int(pages.sum()) + 1, HKV, PS, D), kv_dtype)
    q = _sds(chip, (T, HQ, D), jnp.bfloat16)
    kv = _sds(chip, (T, HKV, D), jnp.bfloat16)
    _compile(
        lambda q, k, v, kc, vc, p: fused_paged_prefill_ingest(
            q, k, v, kc, vc, p, sm_scale=SM, causal=True, rope_theta=5e5,
            kv_quant=kv_quant, k_scale=0.05, v_scale=0.05, kv_aligned=True,
            **statics),
        q, kv, kv, cache, cache, _like(chip, plan))
    # rows that do not start aligned never reach the compiler
    with pytest.raises(ValueError, match="unaligned dynamic"):
        jax.jit(lambda q, k, v, kc, vc, p: fused_paged_prefill_ingest(
            q, k, v, kc, vc, p, sm_scale=SM, **statics)).lower(
            q, kv, kv, cache, cache, _like(chip, plan))


def test_sampling_kernels_at_vocab(chip, compiled_branch):
    from flashinfer_tpu import sampling as S

    probs = _sds(chip, (64, VOCAB), jnp.float32)
    _compile(lambda p: S.top_p_renorm_probs(S.top_k_renorm_probs(p, 40),
                                            0.95), probs)


@pytest.fixture(scope="module")
def engine_step(chip):
    """(engine, its config, rung -> argument shapes) of a 1-layer
    Llama-3-8B engine as ``EngineConfig.from_knobs`` builds it on a v5e."""
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve import (EngineConfig, SamplingConfig,
                                      ServingEngine, engine_kernels as ek)

    mp = pytest.MonkeyPatch()  # module scope: no function-scoped fixture
    _as_on_a_v5e(mp)
    try:
        mcfg = LlamaConfig.llama3_8b(num_layers=1)
        # 4,096 pages: a pool (128 MiB a K or V) that the compiler cannot
        # keep in fast memory, as no cell's can be
        ecfg = EngineConfig.from_knobs(
            mcfg, num_pages=4096, max_seq_tokens=2048,
            sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95))
        params = jax.eval_shape(
            lambda: init_llama_params(jax.random.PRNGKey(0), mcfg))
        eng = ServingEngine(mcfg, params, dataclasses.replace(
            ecfg, num_pages=2))  # shapes only: no such pool on the host
    finally:
        mp.undo()
    caches = [(_sds(chip, (ecfg.num_pages, HKV, ecfg.page_size, D),
                    eng.kv_dtype),) * 2 for _ in range(mcfg.num_layers)]

    def args(rung):
        n = -(-rung // ecfg.page_size)
        plans = ek.build_engine_work_units(
            [ek.SchedSeg(row0=0, n=rung, pages=tuple(range(1, n + 1)),
                         split=0, kv_after=rung, decoding=False, slot=0,
                         group=0)], rung=rung, geom=eng._geom)
        B, ppr = ecfg.max_batch, eng._ppr
        host = (params, *(np.zeros(rung, np.int32),) * 5,
                *(np.zeros((B, ppr), np.int32),) * 2,
                *(np.zeros(rung, np.int32),) * 2,
                *(np.zeros(B, np.int32),) * 2, ek.plans_to_device(plans))
        return _like(chip, host) + (caches,)

    return eng, ecfg, args


def test_from_knobs_on_v5e_is_the_kernel_tier(engine_step):
    _, ecfg, _ = engine_step
    assert ecfg.attention_backend == "kernel"
    assert ecfg.fused_ingest == "off"   # no longer read from the table
    # the top rung is 64 + 512 rounded up to the q tile, not to 1,024
    assert ecfg.rungs() == (64, 128, 256, 512, 640)


@pytest.mark.parametrize("which", ["decode", "prefill", "top"])
def test_engine_step(engine_step, compiled_branch, which):
    """The whole donated step, as from_knobs configures it, on the decode
    rung, on a prefill rung and on the top rung (640 rows: five q tiles,
    no power of two), each with the same launches and parts."""
    eng, ecfg, args = engine_step
    rung = {"decode": min(ecfg.rungs()), "prefill": 512,
            "top": max(ecfg.rungs())}[which]
    lowered = eng._step.lower(*args(rung))
    assert lowered.as_text().count("tpu_custom_call") >= 5
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # one layer + embed + lm_head in bf16, and the KV pool
    assert 2.0 * 2**30 < mem.argument_size_in_bytes < 3.0 * 2**30
    # What a device trace is read by (perfbench/layers/kernel.*.json,
    # readers/xplane_scopes.py): each attention launch is an instruction
    # of its own name (name= on the pallas_call), and the step's parts
    # are components of the operations' op_name (jax.named_scope).
    hlo = compiled.as_text()
    for kernel in ("fused_paged_prefill_l0", "fused_paged_prefill_l1",
                   "paged_decode_attention_split"):
        assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(", hlo), kernel
    assert "fused_paged_prefill_ingest" not in hlo
    for scope in ("embed", "attn.qkv", "attn.kv_write", "attn.core",
                  "attn.out", "mlp", "head", "sample"):
        assert re.search(rf'op_name="jit\(_body\)/(\S+/)?{scope}/', hlo), scope
    assert ("jit(_body)/attn.core/jit(fused_paged_prefill)/"
            "fused_paged_prefill_l0/pallas_call") in hlo
    # The K/V write is in place (serve/kv_write.py): no branch that returns
    # the pool, and no operation but the two scatters (K and V, each alone
    # or as its fusion's root) produces an array of the pool's size, in the
    # pool's own layout.  A `copy` of that shape is the pool re-laid out
    # around a write, which costs the pool and not the rows.
    assert " conditional(" not in hlo
    pool = (ecfg.num_pages, HKV, ecfg.page_size, D)
    made = []
    for dims, layout, op in re.findall(
            r"^\s*(?:ROOT )?%\S+ = bf16\[([\d,]+)\](\{[\d,]*)\S* ([\w\-]+)\(",
            hlo, re.M):
        dims = tuple(int(d) for d in dims.split(","))
        if (np.prod(dims) == np.prod(pool) and dims[0] % pool[0] == 0
                and op not in ("parameter", "get-tuple-element", "bitcast")):
            made.append((dims, layout, op))
    assert made and all(
        dims == pool and layout == "{3,2,1,0" and op in ("scatter", "fusion")
        for dims, layout, op in made), made
    # ... and the step's temporaries hold no copy of a pool (128 MiB each)
    assert mem.temp_size_in_bytes < 100 * 2**20


# ---------------------------------------------------------------------------
# granite-4.0-h-small's block (ISSUE 28): the grouped expert matmuls the
# engine newly reaches, and one whole step of a Mamba layer and an attention
# layer, at the published widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k, n", [(4096, 1536), (768, 4096)])
def test_moe_gmm_at_granite_widths(chip, compiled_branch, k, n):
    """36 held experts, 10 routes of 576 tokens (5,760 sorted rows of which
    about half are local), the tiles the heuristic picks: gate|up
    ``4096 -> 2 x 768`` and down ``768 -> 4096``."""
    from flashinfer_tpu.fused_moe.core import _heuristic_gmm_tiles
    from flashinfer_tpu.ops.moe_gmm import gmm

    m = 5760
    tm, tn, tk = _heuristic_gmm_tiles(m, k, n, 2, 2)
    compiled = _compile(
        lambda lhs, rhs, sizes: gmm(lhs, rhs, sizes, tm=tm, tn=tn, tk=tk),
        _sds(chip, (m, k), "bfloat16"), _sds(chip, (36, k, n), "bfloat16"),
        _sds(chip, (36,), "int32"))
    assert re.search(r"%moe_gmm(\.\d+)? = .*custom-call\(",
                     compiled.as_text())


def _whole_state_copies(hlo):
    """The compiled text's instructions that write a whole ``[64 slots,
    128 x 64, 128]`` float32 state array other than the scan's kernel."""
    return [ln.strip()[:160] for ln in hlo.splitlines()
            if re.search(r"= f32\[64,(8192,128|1048576)\]\S* "
                         r"(copy|fusion|dynamic-update-slice|select)\(", ln)]


@pytest.mark.parametrize("rung", [64, 640])
def test_ssm_slot_update_at_granite_widths(chip, compiled_branch, rung):
    """The slot-addressed scan kernel alone (``ops/ssm_slot_kernel.py``: the
    lanes' one-row path and the units' chunked path in one launch) at
    granite-4.0-h-small's widths, 64 slots, units of ``min(128, rung)``
    rows: Mosaic takes it, the state is updated in place."""
    from flashinfer_tpu.ops.ssm_slot_kernel import ssm_slot_update
    from flashinfer_tpu.serve import recurrent

    nh, dh, ds, slots = 128, 64, 128, 64
    compiled = jax.jit(
        functools.partial(ssm_slot_update, heads=nh, d_head=dh, d_state=ds,
                          groups=1, chunk=128), donate_argnums=0).lower(
        _sds(chip, (slots, nh * dh, ds), "float32"),
        _sds(chip, (slots, 3), "int32"),
        _sds(chip, (recurrent.work_units(rung, slots, 128), 6), "int32"),
        _sds(chip, (), "int32"), _sds(chip, (rung, nh * dh), "bfloat16"),
        _sds(chip, (rung, ds), "bfloat16"), _sds(chip, (rung, ds), "bfloat16"),
        _sds(chip, (rung, nh), "float32"), _sds(chip, (nh,), "float32")
    ).compile()
    hlo = compiled.as_text()
    assert re.search(r"%ssm_slot_update(\.\d+)? = .*custom-call\(", hlo)
    assert "input_output_alias" in hlo.splitlines()[0]
    assert not _whole_state_copies(hlo)


@pytest.fixture(scope="module")
def granite_step(chip):
    """The donated step of a Mamba-2 layer and an attention layer with
    their expert layers at granite-4.0-h-small's widths (36 of 72 experts
    held, half the vocabulary), on the largest rung, compiled once for the
    tests that read it."""
    from flashinfer_tpu.models.granite_hybrid import (
        GraniteHybridConfig, init_granite_hybrid_params)
    from flashinfer_tpu.serve import (EngineConfig, SamplingConfig,
                                      ServingEngine, engine_kernels as ek,
                                      recurrent)

    mp = pytest.MonkeyPatch()
    _as_on_a_v5e(mp)
    try:
        mcfg = GraniteHybridConfig(vocab_size=50176,
                                   layer_types=("mamba", "attention"),
                                   local_experts=(0, 36))
        ecfg = EngineConfig.from_knobs(
            mcfg, num_pages=512, max_seq_tokens=2048, fused_ingest="off",
            kv_offload="off", spill_policy="recompute",
            sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95))
        assert ecfg.rungs() == (64, 128, 256, 512, 640)
        params = jax.eval_shape(
            lambda: init_granite_hybrid_params(jax.random.PRNGKey(0), mcfg))
        eng = ServingEngine(mcfg, params, dataclasses.replace(
            ecfg, num_pages=2))   # shapes only: no 512-page pool on the host
        rung, chunk = max(ecfg.rungs()), 512
        n = -(-rung // ecfg.page_size)
        plans = ek.build_engine_work_units(
            [ek.SchedSeg(row0=0, n=chunk, pages=tuple(range(1, n + 1)),
                         split=0, kv_after=chunk, decoding=False, slot=0,
                         group=0)], rung=rung, geom=eng._geom)
        B, ppr = ecfg.max_batch, eng._ppr
        mplan, _ = recurrent.build_plan(
            [(0, chunk, 0, True)], tokens=chunk, rung=rung, max_batch=B,
            chunk=mcfg.scan_chunk)
        caches = [
            jax.eval_shape(lambda: mcfg.new_recurrent_state(B)),
            (jax.ShapeDtypeStruct((ecfg.num_pages, HKV, ecfg.page_size, D),
                                  jnp.bfloat16),) * 2]
        host = (params, *(np.zeros(rung, np.int32),) * 5,
                *(np.zeros((B, ppr), np.int32),) * 2,
                *(np.zeros(rung, np.int32),) * 2,
                *(np.zeros(B, np.int32),) * 2, ek.plans_to_device(plans),
                caches, mplan)
        lowered = eng._step.lower(*_like(chip, host))
        compiled = lowered.compile()
    finally:
        mp.undo()
    return compiled


def test_granite_hybrid_engine_step(granite_step):
    """The attention kernels without rotation, the grouped matmuls, the
    scan's one launch, and every part under its name."""
    compiled = granite_step
    hlo = compiled.as_text()
    for kernel in ("fused_paged_prefill_l0", "fused_paged_prefill_l1",
                   "paged_decode_attention_split", "moe_gmm",
                   "ssm_slot_update"):
        assert re.search(rf"%{kernel}(\.\d+)? = .*custom-call\(", hlo), kernel
    for scope in ("embed", "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                  "moe.route", "moe.experts", "moe.shared", "attn.qkv",
                  "attn.kv_write", "attn.core", "attn.out", "head", "sample"):
        assert re.search(rf'op_name="jit\(_body\)/(\S+/)?{scope}/', hlo), scope
    assert "apply_rope" not in hlo and "/rope" not in hlo
    mem = compiled.memory_analysis()
    # a Mamba layer and an attention layer with 36 experts each, half the
    # embedding, 64 slots and 512 pages: 2.1 GB; well under the chip's 16
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6e9


def test_the_granite_step_copies_no_whole_state(granite_step):
    """The slots' array goes into the scan's kernel and comes out of it as
    the engine holds it: no instruction of the compiled step writes all 64
    slots (a copy, a fusion, a select or an update of the array's shape)."""
    assert not _whole_state_copies(granite_step.as_text())


@pytest.mark.parametrize("k, n", [(6144, 4096), (2048, 6144)])
def test_moe_gmm_at_glm_widths(chip, compiled_branch, k, n):
    """16 held experts of 256, 8 routes of 1,024 tokens (8,192 sorted rows
    of which one in sixteen is local), the tiles the heuristic picks:
    gate|up ``6144 -> 2 x 2048`` and down ``2048 -> 6144``."""
    from flashinfer_tpu.fused_moe.core import _heuristic_gmm_tiles
    from flashinfer_tpu.ops.moe_gmm import gmm

    m = 8192
    tm, tn, tk = _heuristic_gmm_tiles(m, k, n, 2, 2)
    compiled = _compile(
        lambda lhs, rhs, sizes: gmm(lhs, rhs, sizes, tm=tm, tn=tn, tk=tk),
        _sds(chip, (m, k), "bfloat16"), _sds(chip, (16, k, n), "bfloat16"),
        _sds(chip, (16,), "int32"))
    assert re.search(r"%moe_gmm(\.\d+)? = .*custom-call\(",
                     compiled.as_text())


@pytest.mark.parametrize("k", [4160, 8320, 16640])
def test_glm_sparse_unit(chip, compiled_branch, k):
    """One unit of GLM-5's sparse attention at the cell's shapes (128 query
    rows against a window of a quarter, a half or all of a request's 16,640
    positions: index scores, the exact 2,048th score of each row by search,
    the selection as a mask, the window streamed once under it by the
    ``sparse_mla_stream`` kernel) fits the chip beside the cell's 10.9 GB of
    weights and pages.  The search is a Mosaic kernel under ``attn.select``
    and no sort is left in the unit; the stream is a custom call of
    ``attn.core`` itself, outside the two scopes nested in it, so
    ``step.index_ms_per_step`` and ``step.select_ms_per_step`` keep reading
    what they read."""
    from flashinfer_tpu.models.glm_dsa import GlmDsaConfig, _attend_block

    mcfg = GlmDsaConfig()
    q = 128

    def unit(*a):
        with jax.named_scope("attn.core"):
            return _attend_block(mcfg, *a)

    compiled = _compile(
        unit, _sds(chip, (q, 64, 640), "bfloat16"),
        _sds(chip, (q, 32, 128), "bfloat16"), _sds(chip, (q, 32), "float32"),
        _sds(chip, (q,), "int32"), _sds(chip, (k, 128), "bfloat16"),
        _sds(chip, (k, 640), "bfloat16"), _sds(chip, (), "int32"))
    hlo = compiled.as_text()
    for scope in ("attn.index", "attn.select"):
        assert f"/{scope}/" in hlo, scope
    assert not re.search(r"\) sort\(", hlo)      # "%sort = (...) sort(" before
    mosaic = [line for line in hlo.splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    search = [line for line in mosaic if "/attn.select/" in line]
    assert len(search) == 1 and len(mosaic) == 2
    assert re.search(
        r'op_name="jit\(unit\)/(\S+/)?attn\.core/attn\.select/', search[0])
    kernel = re.findall(r"%sparse_mla_stream(?:\.\d+)? = .*custom-call\(.*", hlo)
    assert len(kernel) == 1
    assert re.search(r'op_name="jit\(unit\)/(\S+/)?attn\.core/', kernel[0])
    assert "attn.index" not in kernel[0] and "attn.select" not in kernel[0]
    # the scores, the keys of the search and the mask, each [128, k] wide:
    # no [128, 2048, 640] block of gathered rows (335 MB and its two readers)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


def test_afmoe_engine_step(chip):
    """The donated step of one period of Trinity-Mini's layers (three window
    layers and a full one, a dense MLP and three expert layers, 16 of 128
    experts held, an eighth of the vocabulary) at the cell's engine sizes on
    the largest rung: both plans' launches, the window layers' over their own
    pool and narrower page lists under the window rule, rotation on the
    window layers only, and every part under its name."""
    from flashinfer_tpu.models.afmoe import AfmoeConfig, init_afmoe_params
    from flashinfer_tpu.serve import (EngineConfig, SamplingConfig,
                                      ServingEngine, engine_kernels as ek,
                                      recurrent)

    mp = pytest.MonkeyPatch()
    _as_on_a_v5e(mp)
    try:
        mcfg = AfmoeConfig(vocab_size=25024, num_dense_layers=1,
                           layer_types=AfmoeConfig().layer_types[:4],
                           local_experts=(0, 16))
        ecfg = EngineConfig.from_knobs(
            mcfg, num_pages=8321, max_seq_tokens=16640, max_batch=8,
            prefill_budget_tokens=1016, page_size=16, ladder=(8, 512, 1024),
            attention_backend="kernel", kv_offload="off",
            spill_policy="recompute",
            sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95))
        params = jax.eval_shape(
            lambda: init_afmoe_params(jax.random.PRNGKey(0), mcfg))
        eng = ServingEngine(mcfg, params, dataclasses.replace(
            ecfg, num_pages=2))   # shapes only: no 8,321-page pool here
        wp = eng._wpages
        assert wp.cap == 193 and wp.pool.num_pages == 8 * 193 + 1
        assert eng._wgeom.pages_per_req == 193 \
            and eng._geom.pages_per_req == 1040
        rung, chunk = 1024, 1016
        seg = ek.SchedSeg(row0=0, n=chunk, pages=tuple(range(1, 65)),
                          split=0, kv_after=chunk, decoding=False, slot=0,
                          group=0)
        plans = ek.build_engine_work_units([seg], rung=rung, geom=eng._geom)
        wplans = ek.build_engine_work_units(
            [seg], rung=rung, geom=eng._wgeom, window_left=2047)
        B, ppr = ecfg.max_batch, eng._ppr
        mplan, _ = recurrent.build_plan([(0, chunk, 0, True)], tokens=chunk,
                                        rung=rung, max_batch=B, chunk=0)
        mplan["window"] = dict(
            token_page=np.zeros(rung, np.int32),
            page_table=np.zeros((B, wp.cap), np.int32),
            first_tok=np.zeros(B, np.int32),
            plans=ek.window_plans_to_host(wplans))
        caches = [
            (jax.ShapeDtypeStruct(
                (ecfg.num_pages if kind == "attention"
                 else wp.pool.num_pages, 4, 16, 128), jnp.bfloat16),) * 2
            for kind in mcfg.layer_kinds()]
        host = (params, *(np.zeros(rung, np.int32),) * 5,
                *(np.zeros((B, ppr), np.int32),) * 2,
                *(np.zeros(rung, np.int32),) * 2,
                *(np.zeros(B, np.int32),) * 2, ek.plans_to_device(plans),
                caches, mplan)
        compiled = eng._step.lower(*_like(chip, host)).compile()
    finally:
        mp.undo()
    hlo = compiled.as_text()
    launches = {k: len(re.findall(rf"%{k}(\.\d+)? = .*custom-call\(", hlo))
                for k in ("fused_paged_prefill_l0", "fused_paged_prefill_l1",
                          "paged_decode_attention_split", "moe_gmm")}
    # level 0 in the full layer alone; level 1 and decode in all four
    assert launches == {"fused_paged_prefill_l0": 1,
                        "fused_paged_prefill_l1": 4,
                        "paged_decode_attention_split": 4, "moe_gmm": 6}
    for scope in ("embed", "attn.qkv", "attn.kv_write", "attn.core",
                  "attn.window", "attn.full", "attn.out", "mlp", "moe.route",
                  "moe.experts", "moe.shared", "head", "sample"):
        assert re.search(rf'op_name="jit\(_body\)/(\S+/)?{scope}/', hlo), scope
    # the kernels of a kind lie under that kind's scope
    assert re.search(r'attn\.core/attn\.window/\S*fused_paged_prefill_l1',
                     hlo)
    assert re.search(r'attn\.core/attn\.full/\S*fused_paged_prefill_l0', hlo)
    assert not re.search(r'attn\.window/\S*fused_paged_prefill_l0', hlo)
    mem = compiled.memory_analysis()
    # four layers' weights, an eighth of the ends, both pools of four layers
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4e9


def test_minicpm_sala_engine_step(chip):
    """The donated step of a sparse layer and a lightning layer (published
    layers 9 and 10) at MiniCPM-SALA's widths and the cell's engine sizes
    (requests of 65,536 tokens: page tables ``[8, 4096]``) on the largest
    rung: the paged kernel's two launches a sparse layer (the lanes' tiles
    and a unit's, inside the units' loop) read the page pool in place
    through scalar-prefetched block lists, the lightning units are matrix
    products, and every part lies under its name."""
    from flashinfer_tpu.models.minicpm_sala import (
        MiniCPMSalaConfig, init_minicpm_sala_params)
    from flashinfer_tpu.serve import (EngineConfig, SamplingConfig,
                                      ServingEngine, recurrent)

    mp = pytest.MonkeyPatch()
    _as_on_a_v5e(mp)
    try:
        mcfg = MiniCPMSalaConfig(
            mixer_types=MiniCPMSalaConfig().mixer_types[9:11],
            layer_index_published=(9, 10))
        assert mcfg.layer_kinds() == ("latent", "recurrent")
        ecfg = EngineConfig.from_knobs(
            mcfg, num_pages=32784, max_seq_tokens=65536, max_batch=8,
            prefill_budget_tokens=1016, page_size=16, ladder=(8, 512, 1024),
            attention_backend="kernel", kv_offload="off",
            spill_policy="recompute",
            sampling=SamplingConfig(temperature=0.8, top_k=40, top_p=0.95))
        params = jax.eval_shape(
            lambda: init_minicpm_sala_params(jax.random.PRNGKey(0), mcfg))
        eng = ServingEngine(mcfg, params, dataclasses.replace(
            ecfg, num_pages=2))   # shapes only: no 32,784-page pool here
        assert not eng._kernel_backend and eng._ppr == 4096
        rung, chunk = 1024, 1016
        B, ppr = ecfg.max_batch, eng._ppr
        mplan, _ = recurrent.build_plan(
            [(0, chunk, 0, True)], tokens=chunk, rung=rung, max_batch=B,
            chunk=mcfg.scan_chunk)
        caches = [
            jax.eval_shape(lambda: mcfg.new_latent_pages(ecfg.num_pages, 16)),
            jax.eval_shape(lambda: mcfg.new_recurrent_state(B))]
        host = (params, *(np.zeros(rung, np.int32),) * 5,
                *(np.zeros((B, ppr), np.int32),) * 2,
                *(np.zeros(rung, np.int32),) * 2,
                *(np.zeros(B, np.int32),) * 2, {}, caches, mplan)
        compiled = eng._step.lower(*_like(chip, host)).compile()
    finally:
        mp.undo()
    hlo = compiled.as_text()
    kernels = re.findall(
        r"%block_sparse_paged_attn(?:\.\d+)? = .*custom-call\(.*", hlo)
    assert len(kernels) == 2
    for k in kernels:
        assert re.search(r'op_name="jit\(_body\)/(\S+/)?attn\.core/', k)
        assert "attn.index" not in k and "attn.select" not in k
    for scope in ("embed", "ssm.in_proj", "ssm.scan", "ssm.out", "attn.qkv",
                  "attn.kv_write", "attn.core", "attn.index", "attn.select",
                  "attn.out", "mlp", "head", "sample"):
        assert re.search(rf'op_name="jit\(_body\)/(\S+/)?{scope}/', hlo), scope
    mem = compiled.memory_analysis()
    # two layers' weights, both ends, the page pool of one sparse layer
    # (1.1 GB) and 8 slots; a unit's scores, mask and bias are temporaries
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4e9
