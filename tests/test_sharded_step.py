"""Compile-once SHARDED serving step (parallel/plan.py).

Pins the mesh step's contracts on the 8-virtual-device CPU mesh:

- **parity**: the sharded fused step (one ``shard_map`` over the mesh)
  and the UNSHARDED ``serve/shard.py`` step sample token-for-token
  identical sequences and write bit-identical int8 caches — the int8
  pipeline's TP reductions accumulate in int32 (order-free), so tp
  sharding moves no numerics;
- **compile-once**: >= 8 steps, exactly ONE trace under the mesh,
  whether the caller's first state sits on the mesh or not;
- **donation**: the sharded program carries input->output aliasing for
  the KV caches / page table / lens / key, and a mesh-committed state
  is consumed by the step that takes it;
- **collective cost family**: hand-computed ICI byte pins (ring
  allreduce 2(p-1)/p, EP a2a, sampling gather), the single-chip fixed
  point, the tp8-shard == banked-shape identity, and the ``obs perf``
  ICI schema (``flashinfer_tpu.obs.perf/6`` + tp1->tp8 scaling curve);
- **counters**: ``comm.allreduce_bytes`` / ``moe.ep_a2a_bytes`` record
  per-traced-call payloads, zero-overhead with the gate off.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from flashinfer_tpu.obs import costmodel
from flashinfer_tpu.parallel.plan import (
    ShardedServingStep,
    ShardingPlan,
    build_sharded_fused_step,
    default_tp,
    plan_axes,
    shard_check,
    split_shard_weights_for_spec,
    validate_dp_page_table,
)
from flashinfer_tpu.serve.shard import Int8ShardSpec, build_fused_step

# GLOBAL model dims (the plan shards them): tp must tile hq=8 / hkv=4
BS, CTX, PS, L = 4, 64, 16, 2
HIDDEN, HQ, HKV, HD, INTER, VOCAB = 256, 8, 4, 64, 512, 512
PPR = CTX // PS
NPAGES = BS * PPR


def _mesh(dp, tp):
    devs = np.array(jax.devices()[: dp * tp]).reshape(dp, tp)
    return Mesh(devs, ("dp", "tp"))


def _spec():
    return Int8ShardSpec(bs=BS, hidden=HIDDEN, hq=HQ, hkv=HKV, hd=HD,
                         inter=INTER, vocab_shard=VOCAB, page_size=PS,
                         use_pallas=False)


def _fixture(plan=None):
    """(spec, fused layer 10-tuples, split dicts, mk_caches, head,
    head_s, pt0, x0) — pt0 honors the dp page-slab contract of `plan`
    (trivially satisfied at dp=1)."""
    from flashinfer_tpu.quantization import quantize_int8

    spec = _spec()
    key = jax.random.PRNGKey(0)

    def qw(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=0)
        return wq, ws.reshape(1, -1)

    ks = jax.random.split(key, 6 * L + 2)
    qdim, kvdim = spec.qdim, spec.kvdim
    layer_ws = [(
        *qw(ks[6 * i], (HIDDEN, qdim + 2 * kvdim)),
        *qw(ks[6 * i + 1], (qdim, HIDDEN)),
        *qw(ks[6 * i + 2], (HIDDEN, 2 * INTER)),
        *qw(ks[6 * i + 3], (INTER, HIDDEN)),
        jax.random.normal(ks[6 * i + 4], (HIDDEN,)) * 0.02 + 1.0,
        jax.random.normal(ks[6 * i + 5], (HIDDEN,)) * 0.02 + 1.0,
    ) for i in range(L)]

    def mk_caches():
        return [(jax.random.randint(
                    jax.random.fold_in(ks[-2], i),
                    (NPAGES, HKV, PS, HD), -127, 127, jnp.int8),
                 jax.random.randint(
                    jax.random.fold_in(ks[-1], i),
                    (NPAGES, HKV, PS, HD), -127, 127, jnp.int8))
                for i in range(L)]

    head, head_s = qw(jax.random.fold_in(key, 999), (HIDDEN, VOCAB))
    dp = plan.dp_size if plan is not None else 1
    bs_l, pages_l = BS // dp, NPAGES // dp
    rng = np.random.default_rng(0)
    pt0 = np.stack([
        rng.permutation(pages_l)[:PPR] + (b // bs_l) * pages_l
        for b in range(BS)]).astype(np.int32)
    x0 = jax.random.normal(jax.random.fold_in(key, 7), (BS, HIDDEN),
                           jnp.bfloat16)
    return (spec, layer_ws, split_shard_weights_for_spec(layer_ws, spec),
            mk_caches, head, head_s, pt0, x0)


def _chain(stepfn, ws, mk_caches, head, head_s, pt0, x0, n=3):
    caches = mk_caches()
    p = jnp.asarray(pt0)
    lens = jnp.full((BS,), CTX - 1, jnp.int32)
    sk = jax.random.PRNGKey(3)
    toks = []
    for _ in range(n):
        tok, caches, p, lens, sk = stepfn(x0, ws, caches, head, head_s,
                                          p, lens, sk)
        toks.append(np.asarray(tok))
    return toks, jax.device_get(caches)


def _assert_caches_equal(ca, cb, max_codes=0):
    for (k1, v1), (k2, v2) in zip(ca, cb):
        for x, y in ((k1, k2), (v1, v2)):
            diff = np.abs(np.asarray(x, np.int32) - np.asarray(y, np.int32))
            assert diff.max() <= max_codes, diff.max()


# -------------------------------------------------------------------------
# parity on the 8-device mesh
# -------------------------------------------------------------------------


@pytest.mark.quick
@pytest.mark.devices_8
def test_sharded_fused_tokens_bitwise_vs_unsharded():
    """THE tentpole parity: one program over a dp2 x tp4 mesh samples
    the SAME token sequence as the single-device fused step — sharding
    is a placement decision, not a numerics change (int32 psum before
    the f32 scale, pmax-amax quantization, logits all-gather)."""
    plan = ShardingPlan(_mesh(2, 4))
    spec, layer_ws, split_ws, mkc, head, head_s, pt0, x0 = _fixture(plan)
    validate_dp_page_table(pt0, NPAGES, plan)
    t_ref, c_ref = _chain(build_fused_step(spec), layer_ws, mkc, head,
                          head_s, pt0, x0)
    fused = build_sharded_fused_step(spec, plan, num_layers=L)
    t_sh, c_sh = _chain(fused, split_ws, mkc, head, head_s, pt0, x0)
    for a, b in zip(t_ref, t_sh):
        np.testing.assert_array_equal(a, b)
    _assert_caches_equal(c_ref, c_sh, max_codes=0)


@pytest.mark.devices_8
def test_sharded_tp_only_and_dp_only_meshes():
    """Degenerate axes work: a tp8-only mesh (hkv=8 variant) and a
    dp4-only mesh both stay token-parity with the unsharded step."""
    spec = dataclasses.replace(_spec(), hkv=8)  # hkv must tile tp=8
    # rebuild weights at the hkv=8 shape via the fixture's machinery
    from flashinfer_tpu.quantization import quantize_int8

    key = jax.random.PRNGKey(0)

    def qw(k, shape):
        w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
        wq, ws = quantize_int8(w, axis=0)
        return wq, ws.reshape(1, -1)

    ks = jax.random.split(key, 6 * L + 2)
    qdim, kvdim = spec.qdim, spec.kvdim
    layer_ws = [(
        *qw(ks[6 * i], (HIDDEN, qdim + 2 * kvdim)),
        *qw(ks[6 * i + 1], (qdim, HIDDEN)),
        *qw(ks[6 * i + 2], (HIDDEN, 2 * INTER)),
        *qw(ks[6 * i + 3], (INTER, HIDDEN)),
        jax.random.normal(ks[6 * i + 4], (HIDDEN,)) * 0.02 + 1.0,
        jax.random.normal(ks[6 * i + 5], (HIDDEN,)) * 0.02 + 1.0,
    ) for i in range(L)]
    split_ws = split_shard_weights_for_spec(layer_ws, spec)

    def mk_caches():
        return [(jax.random.randint(jax.random.fold_in(ks[-2], i),
                                    (NPAGES, 8, PS, HD), -127, 127,
                                    jnp.int8),
                 jax.random.randint(jax.random.fold_in(ks[-1], i),
                                    (NPAGES, 8, PS, HD), -127, 127,
                                    jnp.int8))
                for i in range(L)]

    head, head_s = qw(jax.random.fold_in(key, 999), (HIDDEN, VOCAB))
    pt0 = (np.random.default_rng(0).permutation(NPAGES)
           .reshape(BS, PPR).astype(np.int32))
    x0 = jax.random.normal(jax.random.fold_in(key, 7), (BS, HIDDEN),
                           jnp.bfloat16)
    t_ref, _ = _chain(build_fused_step(spec), layer_ws, mk_caches, head,
                      head_s, pt0, x0)
    tp8 = ShardingPlan(_mesh(1, 8))
    t_tp, _ = _chain(build_sharded_fused_step(spec, tp8, num_layers=L),
                     split_ws, mk_caches, head, head_s, pt0, x0)
    for a, b in zip(t_ref, t_tp):
        np.testing.assert_array_equal(a, b)
    # dp-only: page table must honor the slab contract
    dp4 = ShardingPlan(_mesh(4, 1))
    bs_l, pages_l = BS // 4, NPAGES // 4
    rng = np.random.default_rng(1)
    pt_dp = np.stack([
        rng.permutation(pages_l)[:PPR] + (b // bs_l) * pages_l
        for b in range(BS)]).astype(np.int32)
    t_ref2, _ = _chain(build_fused_step(spec), layer_ws, mk_caches,
                       head, head_s, pt_dp, x0)
    t_dp, _ = _chain(build_sharded_fused_step(spec, dp4, num_layers=L),
                     split_ws, mk_caches, head, head_s, pt_dp, x0)
    for a, b in zip(t_ref2, t_dp):
        np.testing.assert_array_equal(a, b)


# -------------------------------------------------------------------------
# compile-once + donation under the mesh
# -------------------------------------------------------------------------


@pytest.mark.quick
@pytest.mark.devices_8
def test_sharded_compile_once_and_donation():
    """>= 8 steps, ONE trace; the program aliases every donated state
    leaf input->output, and a mesh-committed state is consumed."""
    plan = ShardingPlan(_mesh(2, 4))
    spec, _, split_ws, mkc, head, head_s, pt0, x0 = _fixture(plan)
    fused = build_sharded_fused_step(spec, plan, num_layers=L)
    # lowered as the step calls it, on the plan's layout: an array the
    # mesh does not hold yet is another argument type to jax.jit
    x0, split_ws, caches, head, head_s, p, lens, sk = jax.device_put(
        (x0, split_ws, mkc(), head, head_s, jnp.asarray(pt0),
         jnp.full((BS,), CTX - 1, jnp.int32), jax.random.PRNGKey(3)),
        plan.shard_step_shardings(L)[0])
    # structural proof: aliasing annotations in the lowered program
    txt = fused.jitted.lower(x0, split_ws, caches, head, head_s, p,
                             lens, sk).as_text()
    n_aliased = txt.count("tf.aliasing_output")
    assert n_aliased >= 2 * L + 3, txt[:2000]  # caches + pt + lens + key
    state = (caches, p, lens, sk)
    for i in range(8):
        tok, c2, p2, l2, k2 = fused(x0, split_ws, state[0], head,
                                    head_s, state[1], state[2], state[3])
        state = (c2, p2, l2, k2)
    assert fused.num_traces == 1
    # behavioral proof: the NEXT step consumes the mesh-committed
    # output buffers of the previous one
    kc0 = state[0][0][0]
    fused(x0, split_ws, state[0], head, head_s, state[1], state[2],
          state[3])
    assert kc0.is_deleted()
    assert state[1].is_deleted() and state[2].is_deleted()
    assert fused.num_traces == 1


@pytest.mark.devices_8
def test_sharded_serving_step_lifecycle():
    """ShardedServingStep's plan/run contract: run before plan raises,
    and num_traces pins compile-once from a first state that no device
    of the mesh holds yet (the step lays it out)."""
    plan = ShardingPlan(_mesh(2, 4))
    spec, _, split_ws, mkc, head, head_s, pt0, x0 = _fixture(plan)
    step = ShardedServingStep()
    with pytest.raises(RuntimeError):
        step.run(x0, split_ws, [], head, head_s, None, None, None)
    step.plan(spec, plan, num_layers=L)
    assert step.mesh_axes == "dp2.tp4"
    caches = mkc()
    p = jnp.asarray(pt0)
    lens = jnp.full((BS,), CTX - 1, jnp.int32)
    sk = jax.random.PRNGKey(3)
    for _ in range(4):
        tok, caches, p, lens, sk = step.run(x0, split_ws, caches, head,
                                            head_s, p, lens, sk)
    assert step.num_traces == 1


def test_unsharded_fused_step_donates():
    """The oracle itself (serve/shard.py build_fused_step) consumes its
    donated state, and the state it returns replays."""
    spec, layer_ws, _, mkc, head, head_s, pt0, x0 = _fixture()
    caches = mkc()
    kc0 = caches[0][0]
    step = build_fused_step(spec)
    tok, caches, p, lens, sk = step(
        x0, layer_ws, caches, head, head_s, jnp.asarray(pt0),
        jnp.full((BS,), CTX - 1, jnp.int32), jax.random.PRNGKey(3))
    assert kc0.is_deleted()
    step(x0, layer_ws, caches, head, head_s, p, lens, sk)


# -------------------------------------------------------------------------
# plan-table / contract surfaces (no mesh needed)
# -------------------------------------------------------------------------


def test_shard_check_and_page_table_contract():
    spec = _spec()
    plan = ShardingPlan(_mesh(2, 4))
    shard_check(spec, plan)  # tiles fine
    with pytest.raises(ValueError, match="does not tile"):
        shard_check(dataclasses.replace(spec, hkv=3), plan)
    with pytest.raises(ValueError, match="not in mesh axes"):
        ShardingPlan(_mesh(2, 4), dp="nope")
    # page-slab contract: request 0 using a page from slab 1 raises
    pt = np.zeros((BS, PPR), np.int32)
    pt[0, 0] = NPAGES - 1
    with pytest.raises(ValueError, match="dp block"):
        validate_dp_page_table(pt, NPAGES, plan)
    validate_dp_page_table(pt, NPAGES, ShardingPlan(_mesh(1, 8)))


def test_split_shard_weights_column_exact():
    """The fused->named weight split changes no numerics: projecting
    with the split q/k/v equals slicing the fused qkv projection."""
    from flashinfer_tpu.gemm import mm_int8
    from flashinfer_tpu.quantization import quantize_int8

    spec, layer_ws, split_ws, *_ = _fixture()
    wqkv, sqkv = layer_ws[0][0], layer_ws[0][1]
    w = split_ws[0]
    x = jax.random.normal(jax.random.PRNGKey(5), (BS, HIDDEN),
                          jnp.float32)
    x8, xs = quantize_int8(x)
    fused = np.asarray(mm_int8(x8, wqkv, xs, sqkv))
    q = np.asarray(mm_int8(x8, w["q_proj"], xs, w["q_scale"]))
    k = np.asarray(mm_int8(x8, w["k_proj"], xs, w["k_scale"]))
    v = np.asarray(mm_int8(x8, w["v_proj"], xs, w["v_scale"]))
    np.testing.assert_array_equal(
        fused, np.concatenate([q, k, v], axis=1))


def test_plan_axes_defaults_and_fallback(monkeypatch):
    from flashinfer_tpu.autotuner import AutoTuner

    assert default_tp(8, 64, 8) == 8
    assert default_tp(8, 8, 4) == 4  # hkv=4 caps tp below the world
    assert default_tp(4, 6, 3) == 1  # nothing >1 tiles heads AND world
    # no config: the all-tp default
    monkeypatch.setattr(AutoTuner.get().__class__, "lookup",
                        lambda self, op, key, default=None: default)
    assert plan_axes(8, hidden=8192, num_qo_heads=64,
                     num_kv_heads=8) == (1, 8, 1)
    # a corrupt knob entry (tp does not tile heads) falls back instead
    # of building an uncompilable mesh
    monkeypatch.setattr(
        AutoTuner.get().__class__, "lookup",
        lambda self, op, key, default=None:
        3 if op == "parallel.tp" else default)
    assert plan_axes(8, hidden=8192, num_qo_heads=64,
                     num_kv_heads=8) == (1, 8, 1)


# -------------------------------------------------------------------------
# the ICI collective cost family (hand-computed pins)
# -------------------------------------------------------------------------


@pytest.mark.quick
def test_collective_bytes_hand_computed_pins():
    # ring allreduce: each chip moves 2(p-1)/p x payload
    c = costmodel.tp_allreduce(64, 8192, 8, act_bytes=2)
    assert c.ici_bytes == pytest.approx(2.0 * 7 / 8 * 64 * 8192 * 2)
    assert c.bytes_total == 0.0 and c.flops == 0.0
    # p=1: every collective is free
    assert costmodel.tp_allreduce(64, 8192, 1).ici_bytes == 0.0
    assert costmodel.collective("allgather", 1e6, 1).ici_bytes == 0.0
    # EP a2a: dispatch + combine, each (p-1)/p of T*K*H at act width
    e = costmodel.ep_all_to_all(128, 4096, 2, 4, act_bytes=2)
    assert e.ici_bytes == pytest.approx(2.0 * 3 / 4 * 128 * 2 * 4096 * 2)
    # sampling gather: the replicated-sampler contract gathers the
    # FULL f32 logits — vocab shards over tp AND batch shards over dp
    # (batch_local=64 rows per dp shard, 128 global)
    s = costmodel.sampling_gather(64, 128256, 8, dp_size=2)
    assert s.ici_bytes == pytest.approx(
        7 / 8 * 64 * 128256 * 4 + 1 / 2 * (64 * 2) * 128256 * 4)


@pytest.mark.quick
def test_sharded_phase_costs_fixed_point_and_tp8_shard():
    shape = costmodel.SHARDED_SERVING_SHAPES["llama70b_int8"]
    # tp=dp=1 is exactly the single-chip model, zero ICI
    a = costmodel.serving_phase_costs_sharded(64, 4096, 4, dp=1, tp=1,
                                              **shape)
    b = costmodel.serving_phase_costs(64, 4096, 4, **shape)
    for k in costmodel.SERVING_PHASES:
        assert a[k].flops == pytest.approx(b[k].flops)
        assert a[k].bytes_total == pytest.approx(b[k].bytes_total)
        assert a[k].ici_bytes == 0.0
    # tp8 of the GLOBAL dims is the banked per-chip shard shape
    tp8 = costmodel.serving_phase_costs_sharded(64, 4096, 4, dp=1, tp=8,
                                                **shape)
    shard = costmodel.serving_phase_costs(
        64, 4096, 4, **costmodel.SERVING_SHAPES["llama70b_tp8shard_int8"])
    for k in costmodel.SERVING_PHASES:
        assert tp8[k].flops == pytest.approx(shard[k].flops)
        assert tp8[k].bytes_total == pytest.approx(shard[k].bytes_total)
    # the attention phase carries layers x one allreduce
    ar = costmodel.tp_allreduce(64, 8192, 8)
    assert tp8["attention"].ici_bytes == pytest.approx(4 * ar.ici_bytes)
    # whole step: Cost addition carries ici through
    step = costmodel.serving_step_sharded(64, 4096, 4, dp=1, tp=8,
                                          **shape)
    assert step.ici_bytes == pytest.approx(
        sum(tp8[k].ici_bytes for k in costmodel.SERVING_PHASES))
    with pytest.raises(ValueError, match="do not tile"):
        costmodel.serving_phase_costs_sharded(64, 4096, 4, dp=1, tp=3,
                                              **shape)


def test_attribute_ici_dimension():
    from flashinfer_tpu.obs import hwspec, roofline

    v5e = hwspec.spec("v5e")
    # pure-collective cost: ici-bound, pct = t_ici / t
    c = costmodel.Cost(flops=0.0, bytes_read=0.0, bytes_written=0.0,
                       ici_bytes=200e9 * 0.001)  # 1 ms at v5e's 200 GB/s
    res = roofline.attribute(c, 0.002, v5e)
    assert res.bound == "ici"
    assert res.pct_ici_roofline == pytest.approx(0.5)
    assert res.pct_roofline == pytest.approx(0.5)
    assert res.peak_ici_gbps == v5e.ici_gbps
    # single-chip costs keep their old semantics exactly
    c2 = costmodel.paged_decode(64, 4096, 32, 8, 128)
    res2 = roofline.attribute(c2, 1e-3, v5e)
    assert res2.bound == "memory" and res2.pct_ici_roofline == 0.0


def test_stamp_row_mesh_identity_and_ici_measurement():
    """mesh_axes is configuration (a tp8 row never competes with tp1
    history); ici_bytes / pct_ici_roofline are measurement fields."""
    from flashinfer_tpu.obs import bench_audit, hwspec, roofline

    shape = costmodel.SHARDED_SERVING_SHAPES["llama70b_int8"]
    cost = costmodel.serving_step_sharded(64, 4096, 4, dp=1, tp=8,
                                          **shape)
    v5e = hwspec.spec("v5e")
    row = roofline.stamp_row(
        dict(phase="serving_sharded", bs=64, ctx=4096, us_step=5000.0),
        cost, 5e-3, v5e, step_mode="fused", mesh_axes="dp1.tp8")
    assert row["mesh_axes"] == "dp1.tp8"
    assert row["ici_bytes"] == pytest.approx(cost.ici_bytes)
    assert row["pct_ici_roofline"] > 0.0
    # identity: same config at a different mesh is a DIFFERENT key
    other = dict(row)
    other["mesh_axes"] = "dp1.tp1"
    assert bench_audit.row_key(row) != bench_audit.row_key(other)
    # measurement: ici fields do not fork the identity
    recal = dict(row)
    recal["ici_bytes"] = 1.0
    recal["pct_ici_roofline"] = 0.9
    assert bench_audit.row_key(row) == bench_audit.row_key(recal)
    # round-trip: a stamped row reconstructs its ici bytes
    cost2, _ = costmodel.cost_from_stamped_row(row)
    assert cost2.ici_bytes == pytest.approx(cost.ici_bytes)


@pytest.mark.quick
def test_perf_report_ici_schema_and_scaling_curve():
    """obs perf emits schema perf/6: per-phase predicted collectives
    and a tp1->tp8 scaling prediction for v5e AND v5p, speedups
    monotone and sublinear (ICI eats the linear win)."""
    from flashinfer_tpu.obs import roofline

    rows = [dict(phase="decode", bs=64, ctx=4096, us=100.0, tbps=0.5)]
    rep = roofline.build_perf_report(rows)
    assert rep["schema"] == "flashinfer_tpu.obs.perf/6"
    sc = rep["scaling_prediction"]
    assert set(sc) == {"v5e", "v5p"}
    for chip, table in sc.items():
        assert list(table) == ["1", "2", "4", "8"]
        speedups = [table[k]["speedup_vs_tp1"] for k in table]
        assert speedups == sorted(speedups)  # monotone
        assert speedups[0] == 1.0
        assert 1.0 < speedups[-1] < 8.0  # sublinear: ICI is not free
        for cell in table.values():
            assert {"pred_us", "ici_us", "ici_bytes", "bound",
                    "speedup_vs_tp1", "scaling_efficiency"} <= set(cell)
    si = rep["serving_ici"]
    assert si["mesh_axes"] == "dp1.tp8"
    assert {"attention", "moe_or_mlp", "sampling"} <= set(si["phases"])
    for p in si["phases"].values():
        assert p["ici_bytes"] > 0
        assert set(p["pred_ici_us"]) == {"v5e", "v5p"}
        # v5p ICI is 3x v5e's: predicted wire time must be smaller
        assert p["pred_ici_us"]["v5p"] < p["pred_ici_us"]["v5e"]
    # the human rendering covers the new sections
    text = roofline.render_perf_report(rep)
    assert "predicted tp scaling" in text
    assert "predicted serving collectives" in text


# -------------------------------------------------------------------------
# collective traffic counters (zero-overhead default pinned)
# -------------------------------------------------------------------------


@pytest.mark.devices_8
def test_allreduce_bytes_counter_and_zero_overhead(monkeypatch):
    from jax.sharding import PartitionSpec as P

    from flashinfer_tpu import obs
    from flashinfer_tpu.comm.allreduce import allreduce

    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    x = jnp.ones((4, 64), jnp.float32)

    def run():
        return jax.jit(jax.shard_map(
            lambda x: allreduce(x, "tp"), mesh=mesh,
            in_specs=P(None, "tp"), out_specs=P(None, "tp"),
            check_vma=False))(x)

    # gate OFF (default): nothing recorded — the zero-overhead pin
    monkeypatch.delenv("FLASHINFER_TPU_METRICS", raising=False)
    before = obs.snapshot()
    run()
    assert obs.snapshot() == before
    # gate ON: the local shard payload lands, once per traced call
    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    obs.reset()
    run()
    snap = obs.snapshot()
    # local block [4, 16] f32 = 256 bytes
    assert snap["counters"]["comm.allreduce_bytes"]["{axis=tp}"] == 256


@pytest.mark.devices_8
def test_ep_a2a_bytes_counter(monkeypatch):
    from jax.sharding import PartitionSpec as P

    from flashinfer_tpu import obs
    from flashinfer_tpu.fused_moe import fused_moe_ep

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    T, K, H, E, I = 4, 2, 32, 4, 64
    key = jax.random.PRNGKey(0)
    hidden = jax.random.normal(key, (2 * T, H), jnp.float32)
    wg = jax.random.normal(jax.random.fold_in(key, 1), (E, H, 2 * I),
                           jnp.float32) * 0.05
    wd = jax.random.normal(jax.random.fold_in(key, 2), (E, I, H),
                           jnp.float32) * 0.05
    weights = jax.nn.softmax(
        jax.random.normal(jax.random.fold_in(key, 3), (2 * T, K)))
    ids = jax.random.randint(jax.random.fold_in(key, 4), (2 * T, K),
                             0, E, jnp.int32)

    def run():
        fn = jax.shard_map(
            lambda h, w, wk, tw, ti: fused_moe_ep(
                h, w, wk, tw, ti, E, axis="tp", dispatch="alltoall"),
            mesh=mesh,
            in_specs=(P("tp", None), P("tp", None, None),
                      P("tp", None, None), P("tp", None), P("tp", None)),
            out_specs=P("tp", None), check_vma=False)
        return jax.jit(fn)(hidden, wg, wd, weights, ids)

    monkeypatch.setenv("FLASHINFER_TPU_METRICS", "1")
    obs.reset()
    run()
    snap = obs.snapshot()
    # ep=2, T_local=4, K=2, cap = ceil(4*2/2 * 2.0) = 8:
    # 2 (dispatch+combine) * ep * cap * H * 4 bytes
    want = 2 * 2 * 8 * H * 4
    assert snap["counters"]["moe.ep_a2a_bytes"][
        "{dispatch=alltoall}"] == want
