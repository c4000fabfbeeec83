"""Sequence-parallel attention + comm layer tests on the 8-device CPU mesh
(the TPU stand-in for the reference's multi-GPU spawn tests, SURVEY §4)."""

import jax
import jax.numpy as jnp

import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import flashinfer_tpu as fi
from flashinfer_tpu.comm import Mapping, allreduce_fusion
from flashinfer_tpu.parallel import ParallelAttention, dcp_decode
from flashinfer_tpu.testing import attention_ref


def _cp_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("cp",))


@pytest.mark.devices_8
@pytest.mark.parametrize("mode", ["ulysses", "ring"])
@pytest.mark.parametrize("causal", [False, True])
def test_parallel_attention_matches_single(mode, causal):
    mesh = _cp_mesh(4)
    S, H, KVH, D = 256, 8, 8, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (S, KVH, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (S, KVH, D), jnp.float32)
    pa = ParallelAttention(mesh, axis="cp", mode=mode, causal=causal)
    out = pa(q, k, v)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


@pytest.mark.devices_8
def test_ring_attention_gqa():
    mesh = _cp_mesh(4)
    S, H, KVH, D = 128, 8, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (S, KVH, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (S, KVH, D), jnp.float32)
    out = ParallelAttention(mesh, mode="ring", causal=True)(q, k, v)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.devices_8
def test_dcp_decode_matches_full():
    """KV split over 4 ranks -> merged decode == full decode."""
    mesh = _cp_mesh(4)
    B, HQ, HKV, D, PS, P_local = 4, 8, 2, 64, 8, 4
    ncache = 128
    kc = jax.random.normal(jax.random.PRNGKey(0), (ncache, PS, HKV, D), jnp.float32)
    vc = jax.random.normal(jax.random.PRNGKey(1), (ncache, PS, HKV, D), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, HQ, D), jnp.float32)
    # each rank owns P_local pages per request (contiguous shard of the seq)
    rng = np.random.default_rng(0)
    table_global = rng.permutation(ncache)[: B * 4 * P_local].reshape(B, 4 * P_local)
    kv_lens_global = np.array([4 * P_local * PS] * B, np.int32)

    # per-rank views: [cp, B, P_local]
    table_cp = table_global.reshape(B, 4, P_local).transpose(1, 0, 2).astype(np.int32)
    lens_cp = np.full((4, B), P_local * PS, np.int32)

    def shard_fn(q, kc, vc, table, lens):
        return dcp_decode(q, kc, vc, table[0], lens[0], axis="cp", kv_layout="NHD")

    out = jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P("cp"), P("cp")),
            out_specs=P(),
            check_vma=False,
        )
    )(q, kc, vc, jnp.asarray(table_cp), jnp.asarray(lens_cp))

    from flashinfer_tpu.ops.xla_ref import xla_paged_decode
    ref = xla_paged_decode(
        q, kc, vc, jnp.asarray(table_global.astype(np.int32)),
        jnp.asarray(kv_lens_global), sm_scale=1 / np.sqrt(D),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.devices_8
def test_allreduce_fusion_patterns(mesh8):
    hidden = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, hidden), jnp.float32)
    res = jax.random.normal(jax.random.PRNGKey(1), (16, hidden), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (hidden,), jnp.float32)

    def fn(x_shard, res, w):
        normed, new_res = allreduce_fusion(
            x_shard[0], residual=res, rms_weight=w, axis="tp"
        )
        return normed, new_res

    normed, new_res = jax.jit(
        jax.shard_map(
            fn, mesh=mesh8,
            in_specs=(P("tp"), P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )
    )(x.reshape(4, 2, 16, hidden).transpose(0, 2, 3, 1)[..., 0], res, w)
    # reference: sum over 4 shards (only tp axis participates)
    s = np.asarray(x.reshape(4, 2, 16, hidden).transpose(0, 2, 3, 1)[..., 0]).sum(0)
    s = s + np.asarray(res)
    var = (s * s).mean(-1, keepdims=True)
    ref = s / np.sqrt(var + 1e-6) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(new_res), s, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(normed), ref, rtol=1e-4, atol=1e-4)


def test_mapping_math():
    m = Mapping(world_size=16, dp_size=2, cp_size=1, tp_size=4, pp_size=2,
                moe_tp_size=2, moe_ep_size=2)
    assert m.pp_layers(5) == [[0, 1, 2], [3, 4]]
    assert m.ep_experts(6) == [[0, 1, 2], [3, 4, 5]]
    # rank 0..15 coords roundtrip
    seen = set()
    for r in range(16):
        seen.add(m.coords(r))
    assert len(seen) == 16
    with pytest.raises(ValueError):
        Mapping(world_size=8, tp_size=3)


@pytest.mark.devices_8
def test_multislice_mapping_mesh():
    """Multi-slice (DCN) topology: dp crosses slices, inner axes stay on
    one slice's ICI; the full sharded decode step compiles and matches
    the single-slice mesh result (same devices, same program — only the
    device ORDER encodes the DCN/ICI split)."""
    import numpy as np

    from flashinfer_tpu.comm import Mapping
    from flashinfer_tpu.models import (
        LlamaConfig, init_llama_params, make_sharded_decode_step,
    )

    m = Mapping(world_size=8, dp_size=2, tp_size=4, num_slices=2)
    assert m.dcn_axis_name == "dp"
    mesh = m.make_mesh()
    # each dp row is one slice: 4 contiguous devices
    assert mesh.devices.shape == (2, 1, 4, 1)
    flat = [d.id for d in mesh.devices.reshape(-1)]
    assert flat == sorted(flat)
    # invalid splits raise with the ICI rationale
    with pytest.raises(ValueError, match="ICI"):
        Mapping(world_size=8, dp_size=1, tp_size=8, num_slices=2)

    cfg = LlamaConfig.tiny(num_layers=1, num_kv_heads=4, num_qo_heads=8,
                           vocab_size=128, hidden_size=128,
                           intermediate_size=256)
    step, mesh2, _ = make_sharded_decode_step(m, cfg, mesh=mesh)
    params = init_llama_params(jax.random.PRNGKey(0), cfg)
    B, PPR, PS = 4, 2, 8
    num_pages = (B // 2) * PPR + 1
    caches = [
        (
            jnp.zeros((2, num_pages, cfg.num_kv_heads, PS, cfg.head_dim),
                      cfg.dtype),
            jnp.zeros((2, num_pages, cfg.num_kv_heads, PS, cfg.head_dim),
                      cfg.dtype),
        )
        for _ in range(cfg.num_layers)
    ]
    table = jnp.tile(
        jnp.arange((B // 2) * PPR, dtype=jnp.int32).reshape(B // 2, PPR),
        (2, 1))
    lens = jnp.full((B,), PS, jnp.int32)
    toks = jnp.zeros((B,), jnp.int32)
    logits, _ = step(params, toks, lens, caches, table, lens)
    assert np.isfinite(np.asarray(logits)).all()


def test_multislice_uneven_population_rejected():
    """Mixed/uneven slice populations must be rejected — a contiguous
    block spanning two slices would silently put tp collectives on DCN
    (the review repro: slice ids [0,0,0,1,1,1,1,1])."""
    import types

    from flashinfer_tpu.comm import Mapping

    fake = [types.SimpleNamespace(slice_index=s, id=i)
            for i, s in enumerate([0, 0, 0, 1, 1, 1, 1, 1])]
    m = Mapping(world_size=8, dp_size=2, tp_size=4, num_slices=2)
    with pytest.raises(ValueError, match="slice populations"):
        m.make_mesh(devices=fake)
