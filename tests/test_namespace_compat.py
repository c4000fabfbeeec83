"""Behavior tests for the round-4 submodule-surface completion: the names
are machine-checked in test_compat_surface; here the substantive ones are
checked against oracles (reference files cited per test)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


def test_bgmv_moe_matches_loop_oracle():
    """Multi-LoRA MoE delta (reference fused_moe/bgmv_moe.py:199):
    delta[t] = sum_k w * x[t] @ A[lora, e_k].T @ B[lora, e_k].T."""
    from flashinfer_tpu.fused_moe import bgmv_moe

    rng = np.random.default_rng(0)
    T, K, E, L, H, r, O = 6, 2, 4, 3, 32, 4, 16
    x = rng.standard_normal((T, H)).astype(np.float32)
    A = rng.standard_normal((L, E, r, H)).astype(np.float32) * 0.1
    B = rng.standard_normal((L, E, O, r)).astype(np.float32) * 0.1
    ids = rng.integers(0, E, (T, K))
    wts = rng.random((T, K)).astype(np.float32)
    lora = rng.integers(0, L, (T,))
    # SORTED schedule (the vLLM-style expert-grouped order): slots carry
    # per-pair weights aligned with the permutation — the ordering that
    # exposes any token-major weight-indexing assumption
    flat_e = ids.reshape(-1)
    order = np.argsort(flat_e, kind="stable")
    sorted_token_ids = np.repeat(np.arange(T), K)[order]
    expert_ids = flat_e[order]
    pair_weights = wts.reshape(-1)[order]
    out = bgmv_moe(
        jnp.asarray(x), [jnp.asarray(A)], [jnp.asarray(B)],
        jnp.asarray(sorted_token_ids), jnp.asarray(expert_ids),
        jnp.asarray(lora), jnp.asarray(pair_weights), E,
    )
    # a [T, K] routing matrix is ambiguous under a sorted schedule: loud
    with pytest.raises(ValueError, match="per-pair"):
        bgmv_moe(
            jnp.asarray(x), [jnp.asarray(A)], [jnp.asarray(B)],
            jnp.asarray(sorted_token_ids), jnp.asarray(expert_ids),
            jnp.asarray(lora), jnp.asarray(wts), E,
        )
    ref = np.zeros((T, O), np.float32)
    for t in range(T):
        for k in range(K):
            e = ids[t, k]
            h = x[t] @ A[lora[t], e].T
            ref[t] += wts[t, k] * (h @ B[lora[t], e].T)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("interleave", [False, True])
def test_mono_moe_matches_routed_fused_moe(interleave):
    """mono_moe (reference monomoe.py:280) == routing + fused_moe, with
    the SM90 gate/up column interleave undone."""
    from flashinfer_tpu.fused_moe import fused_moe, mono_moe, route_renormalize

    rng = np.random.default_rng(1)
    T, E, K, H, I = 12, 4, 2, 32, 16
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, H, 2 * I)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, I, H)) * 0.1, jnp.float32)
    wts, ids = route_renormalize(logits, K)
    ref = fused_moe(x, w1, w2, wts, ids, E)
    # reference layout: output-major [E, out, in]; interleave alternates
    # gate/up columns of the up weight
    w1_ref = jnp.swapaxes(w1, 1, 2)  # [E, 2I, H]
    if interleave:
        inter = jnp.zeros_like(w1_ref)
        inter = inter.at[:, 0::2].set(w1_ref[:, :I])
        inter = inter.at[:, 1::2].set(w1_ref[:, I:])
        w1_ref = inter
    out = mono_moe(
        x, logits, w1_ref, None, jnp.swapaxes(w2, 1, 2), None, K,
        scoring_func="softmax", renormalize=True, interleave_up=interleave,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
    )


def test_mhc_fused_ops_match_kernel_transcription():
    """mhc_post + mhc_pre_big_fuse vs a numpy transcription of the CUDA
    kernels (csrc/mhc/mhc_post.cu, mhc_pre_big_fuse.cu)."""
    from flashinfer_tpu.mhc import (
        mhc_post, mhc_pre_big_fuse, mhc_pre_big_fuse_with_prenorm,
    )

    rng = np.random.default_rng(2)
    T, H = 5, 32
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((T, 4, H)), jnp.float32)
    post = jnp.asarray(rng.random((T, 4)), jnp.float32)
    comb = jnp.asarray(rng.random((T, 4, 4)), jnp.float32)
    out = mhc_post(x, res, post, comb)
    ref = (np.asarray(x)[:, None, :] * np.asarray(post)[:, :, None]
           + np.einsum("toh,ton->tnh", np.asarray(res), np.asarray(comb)))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    dot = jnp.asarray(rng.standard_normal((T, 24)), jnp.float32)
    sq = jnp.asarray(rng.random((T,)) * 50 + 1, jnp.float32)
    scale = jnp.asarray([0.5, 0.7, 0.9], jnp.float32)
    base = jnp.asarray(rng.standard_normal((24,)) * 0.1, jnp.float32)
    pm, cm, li = mhc_pre_big_fuse(dot, sq, res, scale, base, k=128)
    d, s, b_ = np.asarray(dot, np.float64), np.asarray(scale), np.asarray(base)
    for t in range(T):
        rstd = 1.0 / np.sqrt(float(sq[t]) / 128 + 1e-6)
        raw = (d[t, 8:] * rstd * s[2] + b_[8:]).reshape(4, 4)
        m = np.exp(raw - raw.max(axis=1, keepdims=True))
        m = m / m.sum(axis=1, keepdims=True) + 1e-6
        m = m / (m.sum(axis=0, keepdims=True) + 1e-6)
        for _ in range(1, 20):
            m = m / (m.sum(axis=1, keepdims=True) + 1e-6)
            m = m / (m.sum(axis=0, keepdims=True) + 1e-6)
        np.testing.assert_allclose(np.asarray(cm)[t], m, rtol=1e-4,
                                   atol=1e-5)
        pre = 1 / (1 + np.exp(-(d[t, :4] * rstd * s[0] + b_[:4]))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(li)[t], (pre[:, None] * np.asarray(res)[t]).sum(0),
            rtol=1e-4, atol=1e-4,
        )
        pbt = 1 / (1 + np.exp(-(d[t, 4:8] * rstd * s[1] + b_[4:8])))
        np.testing.assert_allclose(np.asarray(pm)[t, :, 0], pbt,
                                   rtol=1e-4, atol=1e-5)
    # prenorm twin derives sqrsum from residual (K = HC * H)
    pm2, cm2, li2 = mhc_pre_big_fuse_with_prenorm(dot, res, scale, base)
    sq2 = (np.asarray(res) ** 2).sum(axis=(1, 2))
    pm3, _, _ = mhc_pre_big_fuse(dot, jnp.asarray(sq2), res, scale, base,
                                 k=4 * H)
    np.testing.assert_allclose(np.asarray(pm2), np.asarray(pm3),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.devices_8
def test_moe_ep_fleet_matches_fused_moe_ep():
    """Fleet/MoEEpSplitLayer (reference moe_ep split mode) over a mesh ==
    calling fused_moe_ep directly."""
    from flashinfer_tpu import moe_ep as ep_mod
    from flashinfer_tpu.fused_moe import fused_moe_ep, route_renormalize

    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, h, inter = 16, 8, 2, 32, 32
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((T, h)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, h, 2 * inter)) * 0.1,
                     jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, inter, h)) * 0.1, jnp.float32)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    wts, ids = route_renormalize(logits, K)

    params = ep_mod.FleetParams(
        ep_size=ep, num_experts=E, axis="tp",
        algorithm=ep_mod.EpAlgorithm.ALLTOALL_EXACT,
    )

    def layer_fn(x, w1, w2, wts, ids):
        fleet = ep_mod.create_fleet(params)
        layer = ep_mod.MoEEpSplitLayer(
            fleet, ep_mod.MoEEpTensors(w_gate_up=w1, w_down=w2)
        )
        return layer(x, wts, ids)

    def direct_fn(x, w1, w2, wts, ids):
        return fused_moe_ep(
            x, w1, w2, wts, ids, E, axis="tp", dispatch="alltoall_exact"
        )

    specs = dict(
        in_specs=(P("tp"),) * 5, out_specs=P("tp"), check_vma=False,
    )
    out = jax.jit(jax.shard_map(layer_fn, mesh=mesh, **specs))(
        x, w1, w2, wts, ids)
    ref = jax.jit(jax.shard_map(direct_fn, mesh=mesh, **specs))(
        x, w1, w2, wts, ids)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # validators catch real misconfigurations
    with pytest.raises(ep_mod.MoEEpConfigError):
        ep_mod.validate_fleet_params(
            ep_mod.FleetParams(ep_size=3, num_experts=8))
    assert ep_mod.available_backends() == ["xla-collective"]
    assert not ep_mod.have_nccl_ep()


@pytest.mark.devices_8
def test_comm_moe_a2a_dispatch_combine_roundtrip():
    """moe_a2a dispatch + identity-expert + combine == the weighted sum
    of each token with itself (reference comm moe_alltoall semantics)."""
    from flashinfer_tpu.comm.compat import moe_a2a_combine, moe_a2a_dispatch

    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, H = 16, 8, 2, 32
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, E, (T, K)), jnp.int32)
    wts = jnp.asarray(rng.random((T, K)), jnp.float32)

    def fn(x, ids, wts):
        recv_x, recv_eid, valid = moe_a2a_dispatch(
            x, ids, wts, E, axis="tp", capacity_factor=float(ep))
        flat = recv_x.reshape(-1, H)  # identity "expert"
        return moe_a2a_combine(flat, ids, wts, E, axis="tp",
                               capacity_factor=float(ep))

    out = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("tp"),) * 3, out_specs=P("tp"),
        check_vma=False,
    ))(x, ids, wts)
    ref = np.asarray(x) * np.asarray(wts).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_comm_allreduce_compat_names():
    """trtllm/vllm AR names run on the real collectives (single-axis
    smoke via a size-1 mesh call path is covered by devices_8 tests of
    allreduce itself; here: the sanitize/mask helpers)."""
    from flashinfer_tpu.comm.compat import (
        moe_a2a_active_rank_mask, moe_a2a_sanitize_expert_ids,
    )

    ids = jnp.asarray([[0, 5], [9, -1]], jnp.int32)
    clean = moe_a2a_sanitize_expert_ids(ids, num_experts=8)
    assert np.asarray(clean).tolist() == [[0, 5], [-1, -1]]
    mask = moe_a2a_active_rank_mask(clean, num_experts=8, ep_size=4)
    assert np.asarray(mask).tolist() == [True, False, True, False]


def test_logits_processor_compiler_surface():
    from flashinfer_tpu.logits_processor import (
        CompileError, LegalizationError, Sample, Softmax, TaggedTensor,
        Temperature, TensorType, TopP, compile_pipeline,
        legalize_processors,
    )

    pipe = compile_pipeline([Temperature(), Softmax(), TopP(), Sample()])
    out = pipe(
        jnp.zeros((2, 16), jnp.float32), key=jax.random.PRNGKey(0),
        temperature=1.0, top_p=0.9,
    )
    assert out.shape == (2,)
    with pytest.raises(CompileError):
        compile_pipeline([TopP()])  # TopP needs probs
    with pytest.raises(LegalizationError):
        legalize_processors([TopP()])
    t = TaggedTensor.logits(jnp.zeros((2, 4)))
    assert t.type == TensorType.LOGITS
