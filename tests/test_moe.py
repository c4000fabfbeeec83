"""MoE tests: routing methods vs eager references, fused MoE vs dense
per-expert loop, EP vs single-device (mirrors reference tests/moe strategy)."""

import jax
import jax.numpy as jnp

import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import flashinfer_tpu.fused_moe as moe


def _moe_ref(x, w1, w2, weights, ids):
    """Eager loop reference."""
    xn = np.asarray(x, np.float32)
    T, K = ids.shape
    out = np.zeros((T, w2.shape[-1]), np.float32)
    for t in range(T):
        for j in range(K):
            e = int(ids[t, j])
            h = xn[t] @ np.asarray(w1[e], np.float32)
            d = h.shape[-1] // 2
            a = h[:d] / (1 + np.exp(-h[:d])) * h[d:]
            out[t] += float(weights[t, j]) * (a @ np.asarray(w2[e], np.float32))
    return out


@pytest.mark.quick
def test_route_topk_and_renormalize():
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, 16))
    w, ids = moe.route_topk(logits, 4)
    p = np.asarray(jax.nn.softmax(logits, -1))
    for t in range(5):
        np.testing.assert_array_equal(
            np.sort(np.asarray(ids[t])), np.sort(np.argsort(-p[t])[:4])
        )
    w2, ids2 = moe.route_renormalize(logits, 4)
    np.testing.assert_allclose(np.asarray(w2).sum(-1), 1.0, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2))


def test_route_deepseek_v3_group_limit():
    T, E, G = 4, 32, 8
    logits = jax.random.normal(jax.random.PRNGKey(1), (T, E))
    bias = jax.random.normal(jax.random.PRNGKey(2), (E,)) * 0.1
    w, ids = moe.route_deepseek_v3(logits, bias, top_k=4, n_group=G,
                                   topk_group=2, routed_scaling_factor=2.5)
    scores = np.asarray(jax.nn.sigmoid(logits))
    biased = scores + np.asarray(bias)[None]
    for t in range(T):
        g = biased[t].reshape(G, E // G)
        grp_score = np.sort(g, -1)[:, -2:].sum(-1)
        allowed_groups = set(np.argsort(-grp_score)[:2])
        for e in np.asarray(ids[t]):
            assert e // (E // G) in allowed_groups
    # weights renormalized from unbiased scores * scale
    sel = np.take_along_axis(scores, np.asarray(ids), 1)
    ref_w = sel / sel.sum(-1, keepdims=True) * 2.5
    np.testing.assert_allclose(np.asarray(w), ref_w, rtol=1e-5)


def test_route_llama4():
    logits = jax.random.normal(jax.random.PRNGKey(3), (6, 8))
    w, ids = moe.route_llama4(logits)
    np.testing.assert_array_equal(
        np.asarray(ids)[:, 0], np.argmax(np.asarray(logits), -1)
    )
    np.testing.assert_allclose(
        np.asarray(w)[:, 0],
        np.asarray(jax.nn.sigmoid(np.max(np.asarray(logits), -1))),
        rtol=1e-5,
    )


@pytest.mark.parametrize("T,E,K", [(16, 8, 2), (7, 4, 3)])
def test_fused_moe_matches_loop(T, E, K):
    h, inter = 32, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    logits = jax.random.normal(jax.random.PRNGKey(3), (T, E))
    weights, ids = moe.route_renormalize(logits, K)
    out = moe.fused_moe(x, w1, w2, weights, ids, E)
    ref = _moe_ref(x, w1, w2, np.asarray(weights), np.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_fused_moe_empty_expert():
    """Experts receiving zero tokens must not corrupt results."""
    T, E, K, h, inter = 4, 8, 1, 16, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h))
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    ids = jnp.zeros((T, K), jnp.int32)  # everything to expert 0
    weights = jnp.ones((T, K), jnp.float32)
    out = moe.fused_moe(x, w1, w2, weights, ids, E)
    ref = _moe_ref(x, w1, w2, np.ones((T, K)), np.zeros((T, K), int))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


@pytest.mark.devices_8
def test_fused_moe_ep_alltoall_matches_single_device():
    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, h, inter = 16, 8, 2, 32, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    logits = jax.random.normal(jax.random.PRNGKey(3), (T, E))
    weights, ids = moe.route_renormalize(logits, K)
    single = moe.fused_moe(x, w1, w2, weights, ids, E)

    def fn(x, w1, w2, wts, ids):
        # generous capacity: no drops -> exact match with single device
        return moe.fused_moe_ep(
            x, w1, w2, wts, ids, E, axis="tp", dispatch="alltoall",
            capacity_factor=float(ep),  # cap = T_local*K: cannot overflow
        )

    out = jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P("tp"), P("tp")),
            out_specs=P("tp"),
            check_vma=False,
        )
    )(x, w1, w2, weights, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(single), rtol=2e-3, atol=2e-3
    )


@pytest.mark.devices_8
def test_fused_moe_ep_matches_single_device():
    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, h, inter = 16, 8, 2, 32, 32
    assert T % ep == 0 and E % ep == 0
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    logits = jax.random.normal(jax.random.PRNGKey(3), (T, E))
    weights, ids = moe.route_renormalize(logits, K)

    single = moe.fused_moe(x, w1, w2, weights, ids, E)

    def fn(x, w1, w2, wts, ids):
        return moe.fused_moe_ep(x, w1, w2, wts, ids, E, axis="tp")

    out = jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P("tp"), P("tp")),
            out_specs=P("tp"),
            check_vma=False,
        )
    )(x, w1, w2, weights, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(single), rtol=2e-3, atol=2e-3
    )


def test_fused_moe_int8_matches_bf16():
    """Native int8 MXU grouped GEMM path vs bf16 within quant tolerance."""
    from flashinfer_tpu.fused_moe import fused_moe, route_renormalize
    from flashinfer_tpu.quantization import quantize_int8

    T, E, K, H, I = 32, 4, 2, 64, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, H), jnp.bfloat16)
    w1 = jax.random.normal(jax.random.fold_in(key, 1), (E, H, 2 * I),
                           jnp.bfloat16) * 0.1
    w2 = jax.random.normal(jax.random.fold_in(key, 2), (E, I, H),
                           jnp.bfloat16) * 0.1
    logits = jax.random.normal(jax.random.fold_in(key, 3), (T, E))
    wts, ids = route_renormalize(logits, K)

    ref = np.asarray(fused_moe(x, w1, w2, wts, ids, E), np.float32)
    w1q, w1s = quantize_int8(w1, axis=1)
    w2q, w2s = quantize_int8(w2, axis=1)
    out = fused_moe(x, w1q, w2q, wts, ids, E, w1_scale=w1s, w2_scale=w2s)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=5e-2, atol=5e-2)


def test_moe_layer_int8_variant():
    from flashinfer_tpu.fused_moe import (
        MoE, MoEConfig, QuantConfig, QuantVariant, RoutingConfig,
    )

    T, E, K, H, I = 16, 4, 2, 64, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, H), jnp.bfloat16)
    rw = jax.random.normal(jax.random.fold_in(key, 1), (H, E), jnp.bfloat16)
    w1 = jax.random.normal(jax.random.fold_in(key, 2), (E, H, 2 * I),
                           jnp.bfloat16) * 0.1
    w2 = jax.random.normal(jax.random.fold_in(key, 3), (E, I, H),
                           jnp.bfloat16) * 0.1
    cfg_bf = MoEConfig(num_experts=E, hidden_size=H, intermediate_size=I,
                       routing=RoutingConfig(top_k=K))
    cfg_i8 = MoEConfig(num_experts=E, hidden_size=H, intermediate_size=I,
                       routing=RoutingConfig(top_k=K),
                       quant=QuantConfig(variant=QuantVariant.INT8))
    ref = np.asarray(MoE(cfg_bf, rw, w1, w2)(x), np.float32)
    out = np.asarray(MoE(cfg_i8, rw, w1, w2)(x), np.float32)
    np.testing.assert_allclose(out, ref, rtol=6e-2, atol=6e-2)


def test_fused_moe_gmm_backend_matches_ragged():
    """Pallas gather-GMM pipeline vs the ragged_dot oracle (bf16)."""
    from flashinfer_tpu import fused_moe as moe

    rng = np.random.default_rng(5)
    T, E, K, h, inter = 48, 6, 2, 128, 128
    x = jnp.asarray(rng.standard_normal((T, h)), jnp.bfloat16)
    w1 = jnp.asarray(rng.standard_normal((E, h, 2 * inter)) / np.sqrt(h),
                     jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((E, inter, h)) / np.sqrt(inter),
                     jnp.bfloat16)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    wts, ids = moe.route_renormalize(logits, K)
    ref = moe.fused_moe(x, w1, w2, wts, ids, E, backend="ragged")
    out = moe.fused_moe(x, w1, w2, wts, ids, E, backend="gmm")
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


@pytest.mark.parametrize(
    "tiles", [(32, 128, 128), ((16, 256, 128), (32, 128, 128))]
)
def test_fused_moe_gmm_tiles_override(tiles):
    """Explicit / per-GEMM gmm_tiles produce the same result as defaults
    (the tile shape is a pure schedule choice)."""
    from flashinfer_tpu import fused_moe as moe

    rng = np.random.default_rng(11)
    T, E, K, h, inter = 48, 6, 2, 128, 128
    x = jnp.asarray(rng.standard_normal((T, h)), jnp.bfloat16)
    w1 = jnp.asarray(rng.standard_normal((E, h, 2 * inter)) / np.sqrt(h),
                     jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((E, inter, h)) / np.sqrt(inter),
                     jnp.bfloat16)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    wts, ids = moe.route_renormalize(logits, K)
    ref = moe.fused_moe(x, w1, w2, wts, ids, E, backend="gmm")
    out = moe.fused_moe(x, w1, w2, wts, ids, E, backend="gmm",
                        gmm_tiles=tiles)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_fused_moe_gmm_backend_int8():
    """int8 gmm path (per-token quant before routing) vs int8 ragged path."""
    from flashinfer_tpu import fused_moe as moe
    from flashinfer_tpu.quantization import quantize_int8

    rng = np.random.default_rng(9)
    T, E, K, h, inter = 32, 4, 2, 128, 128
    x = jnp.asarray(rng.standard_normal((T, h)), jnp.bfloat16)
    w1 = jnp.asarray(rng.standard_normal((E, h, 2 * inter)) / np.sqrt(h),
                     jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((E, inter, h)) / np.sqrt(inter),
                     jnp.bfloat16)
    w1q, w1s = quantize_int8(w1, axis=1)
    w2q, w2s = quantize_int8(w2, axis=1)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    wts, ids = moe.route_renormalize(logits, K)
    ref = moe.fused_moe(x, w1q, w2q, wts, ids, E, w1_scale=w1s,
                        w2_scale=w2s, backend="ragged")
    out = moe.fused_moe(x, w1q, w2q, wts, ids, E, w1_scale=w1s,
                        w2_scale=w2s, backend="gmm")
    # both are int8 pipelines but quantize activations at different points
    # (per-token vs per-sorted-row); tolerances cover the requant delta
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=8e-2, atol=8e-2,
    )


@pytest.mark.devices_8
def test_fused_moe_ep_alltoall_capacity_drops():
    """Forced overflow (capacity_factor=0.5): dropped routes contribute
    zero, the dropped count surfaces, and kept routes stay exact."""
    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, h, inter = 16, 8, 2, 32, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    # adversarial routing: every token's top choice is expert 0 -> rank 0's
    # bucket overflows on every source rank at capacity_factor=0.5
    ids = jnp.stack(
        [jnp.zeros((T,), jnp.int32),
         jnp.arange(T, dtype=jnp.int32) % E],
        axis=1,
    )
    weights = jnp.full((T, K), 0.5, jnp.float32)
    cf = 0.5

    def fn(x, w1, w2, wts, ids):
        return moe.fused_moe_ep(
            x, w1, w2, wts, ids, E, axis="tp", dispatch="alltoall",
            capacity_factor=cf, return_dropped=True,
        )

    out, dropped = jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P("tp"), P("tp")),
            out_specs=(P("tp"), P("tp")),
            check_vma=False,
        )
    )(x, w1, w2, weights, ids)

    # host oracle replicating the kernel's drop rule per source rank:
    # stable argsort by destination rank, bucket index >= cap drops
    t_local = T // ep
    e_local = E // ep
    cap = max(1, int(np.ceil(t_local * K / ep * cf)))
    kept_mask = np.zeros((T, K), bool)
    ids_np = np.asarray(ids)
    for r in range(ep):
        flat = ids_np[r * t_local:(r + 1) * t_local].reshape(-1)
        dst = flat // e_local
        order = np.argsort(dst, kind="stable")
        within = np.arange(len(order)) - np.searchsorted(
            dst[order], dst[order], side="left"
        )
        kept_sorted = within < cap
        kept_flat = np.zeros(len(order), bool)
        kept_flat[order] = kept_sorted
        kept_mask[r * t_local:(r + 1) * t_local] = kept_flat.reshape(
            t_local, K
        )
    total_dropped = int((~kept_mask).sum())
    assert total_dropped > 0, "test must actually force overflow"
    assert int(np.asarray(dropped).sum()) == total_dropped

    ref = _moe_ref(
        np.asarray(x), np.asarray(w1), np.asarray(w2),
        np.asarray(weights) * kept_mask, ids_np,
    )
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


@pytest.mark.devices_8
def test_fused_moe_ep_alltoall_exact_no_drop_under_overflow():
    """The exact dispatch under the SAME adversarial routing that makes
    the capacity mode drop: zero drops, and the output matches the
    single-device oracle BIT-FOR-BIT in f32 (K=2: two-addend combine is
    order-free; per-route expert rows are row-independent dots)."""
    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, h, inter = 16, 8, 2, 32, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    # every token's top choice is expert 0: rank 0's bucket overflows on
    # every source rank at capacity_factor=0.5 (multiple rounds needed)
    ids = jnp.stack(
        [jnp.zeros((T,), jnp.int32),
         jnp.arange(T, dtype=jnp.int32) % E],
        axis=1,
    )
    weights = jnp.full((T, K), 0.5, jnp.float32)
    single = moe.fused_moe(x, w1, w2, weights, ids, E)

    def fn(x, w1, w2, wts, ids):
        return moe.fused_moe_ep(
            x, w1, w2, wts, ids, E, axis="tp", dispatch="alltoall_exact",
            capacity_factor=0.5, return_dropped=True,
        )

    out, dropped = jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P("tp"), P("tp")),
            out_specs=(P("tp"), P("tp")),
            check_vma=False,
        )
    )(x, w1, w2, weights, ids)

    assert int(np.asarray(dropped).sum()) == 0
    # bit-for-bit is the contract (VERDICT r3 #4); if a future XLA changes
    # gemm blocking across batch shapes this may need an ulp bound
    diff = np.abs(np.asarray(out) - np.asarray(single))
    assert diff.max() == 0.0, f"exact dispatch deviated, max abs {diff.max()}"


@pytest.mark.devices_8
def test_fused_moe_ep_alltoall_exact_balanced_routing():
    """Balanced routing (the one-round fast case) through the exact
    dispatch matches the single-device oracle."""
    ep = 4
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    T, E, K, h, inter = 16, 8, 3, 32, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (T, h), jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (E, h, 2 * inter)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (E, inter, h)) * 0.1
    logits = jax.random.normal(jax.random.PRNGKey(3), (T, E))
    weights, ids = moe.route_renormalize(logits, K)
    single = moe.fused_moe(x, w1, w2, weights, ids, E)

    def fn(x, w1, w2, wts, ids):
        return moe.fused_moe_ep(
            x, w1, w2, wts, ids, E, axis="tp", dispatch="alltoall_exact",
        )

    out = jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("tp"), P("tp"), P("tp"), P("tp"), P("tp")),
            out_specs=P("tp"),
            check_vma=False,
        )
    )(x, w1, w2, weights, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(single), rtol=2e-3, atol=2e-3
    )


@pytest.mark.devices_8
@pytest.mark.parametrize("ep", [2, 4, 8])
@pytest.mark.parametrize("seed", range(2))
def test_fused_moe_ep_alltoall_exact_fuzz(seed, ep):
    """Randomized routing distributions x capacity factors through the
    exact dispatch at EVERY ep degree (2/4/8 — explicit, so e_local=1
    and the multi-round ep=8 exchange are guaranteed covered): skewed
    zipf-ish routing, random K — always zero drops and oracle-exact
    (f32 allclose at K>2, where the K-way combine order may differ from
    the oracle by an ulp)."""
    rng = np.random.default_rng(200 + seed * 8 + ep)
    mesh = Mesh(np.array(jax.devices()[:ep]), ("tp",))
    K = int(rng.integers(1, 4))
    T = ep * int(rng.integers(2, 7))
    E = ep * int(rng.choice([1, 2, 4]))
    h = inter = 32
    x = jnp.asarray(rng.standard_normal((T, h)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((E, h, 2 * inter)) * 0.1,
                     jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((E, inter, h)) * 0.1, jnp.float32)
    # skewed routing: zipf-weighted expert popularity forces uneven buckets
    pop = 1.0 / (1 + np.arange(E)) ** float(rng.uniform(0.5, 2.0))
    ids = jnp.asarray(
        rng.choice(E, size=(T, K), p=pop / pop.sum()), jnp.int32)
    wts = jnp.asarray(rng.random((T, K)), jnp.float32)
    cf = float(rng.choice([0.25, 0.5, 1.0, 2.0]))
    single = moe.fused_moe(x, w1, w2, wts, ids, E)

    def fn(x, w1, w2, wts, ids):
        return moe.fused_moe_ep(
            x, w1, w2, wts, ids, E, axis="tp", dispatch="alltoall_exact",
            capacity_factor=cf, return_dropped=True,
        )

    out, dropped = jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P("tp"),) * 5, out_specs=(P("tp"), P("tp")),
            check_vma=False,
        )
    )(x, w1, w2, wts, ids)
    assert int(np.asarray(dropped).sum()) == 0
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(single), rtol=1e-5, atol=1e-5,
        err_msg=f"ep={ep} K={K} T={T} E={E} cf={cf}",
    )
