"""Sampling family tests: distribution-support checks + renorm exactness
(mirrors reference tests/test_sampling.py strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flashinfer_tpu as fi


def _rand_probs(key, batch, vocab):
    logits = jax.random.normal(key, (batch, vocab)) * 2
    return jax.nn.softmax(logits, axis=-1)


def test_softmax_temperature():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 111))
    t = jnp.array([0.5, 1.0, 2.0, 1.3])
    out = fi.softmax(logits, t)
    ref = jax.nn.softmax(np.asarray(logits) / np.asarray(t)[:, None], axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.quick
def test_sampling_from_probs_support():
    batch, vocab = 16, 64
    probs = np.zeros((batch, vocab), np.float32)
    allowed = np.random.default_rng(0).integers(0, vocab, (batch, 5))
    for b in range(batch):
        probs[b, allowed[b]] = 1 / 5
    samples = fi.sampling_from_probs(jnp.array(probs), jax.random.PRNGKey(0))
    for b in range(batch):
        assert samples[b] in allowed[b]


@pytest.mark.parametrize("top_p", [0.1, 0.5, 0.9])
def test_top_p_renorm(top_p):
    probs = _rand_probs(jax.random.PRNGKey(0), 8, 128)
    out = np.asarray(fi.top_p_renorm_probs(probs, top_p))
    p = np.asarray(probs)
    for b in range(8):
        order = np.argsort(-p[b])
        cum = np.cumsum(p[b][order])
        k = int(np.searchsorted(cum, top_p) + 1)
        mask = np.zeros(128, bool)
        mask[order[:k]] = True
        kept = np.where(mask, p[b], 0)
        ref = kept / kept.sum()
        np.testing.assert_allclose(out[b], ref, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(out[b].sum(), 1.0, rtol=1e-5)


@pytest.mark.parametrize("top_k", [1, 5, 64])
def test_top_k_renorm(top_k):
    probs = _rand_probs(jax.random.PRNGKey(1), 8, 64)
    out = np.asarray(fi.top_k_renorm_probs(probs, top_k))
    p = np.asarray(probs)
    for b in range(8):
        thresh = np.sort(p[b])[::-1][top_k - 1]
        kept = np.where(p[b] >= thresh, p[b], 0)
        ref = kept / kept.sum()
        np.testing.assert_allclose(out[b], ref, rtol=1e-4, atol=1e-6)


def test_top_k_mask_logits():
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 100))
    out = np.asarray(fi.top_k_mask_logits(logits, 10))
    for b in range(4):
        assert (out[b] > -1e29).sum() == 10


def test_top_k_sampling_stays_in_top_k():
    probs = _rand_probs(jax.random.PRNGKey(3), 8, 256)
    p = np.asarray(probs)
    for i in range(10):
        s = np.asarray(
            fi.top_k_sampling_from_probs(probs, jax.random.PRNGKey(i), 5)
        )
        for b in range(8):
            topk = set(np.argsort(-p[b])[:5].tolist())
            assert int(s[b]) in topk


def test_min_p_sampling():
    probs = _rand_probs(jax.random.PRNGKey(4), 4, 64)
    p = np.asarray(probs)
    for i in range(5):
        s = np.asarray(fi.min_p_sampling_from_probs(probs, jax.random.PRNGKey(i), 0.5))
        for b in range(4):
            assert p[b, s[b]] >= 0.5 * p[b].max() - 1e-6


def test_chain_speculative_sampling_all_accept():
    """When draft == target, all draft tokens must be accepted."""
    batch, n, vocab = 4, 3, 32
    probs = np.asarray(_rand_probs(jax.random.PRNGKey(0), batch * n, vocab)).reshape(
        batch, n, vocab
    )
    draft = jnp.array(probs)
    target = jnp.concatenate(
        [draft, _rand_probs(jax.random.PRNGKey(9), batch, vocab)[:, None]], axis=1
    )
    tok = jax.random.categorical(
        jax.random.PRNGKey(1), jnp.log(draft), axis=-1
    ).astype(jnp.int32)
    out, acc, emitted = fi.chain_speculative_sampling(
        draft, tok, target, jax.random.PRNGKey(2)
    )
    np.testing.assert_array_equal(np.asarray(acc), n)
    np.testing.assert_array_equal(np.asarray(emitted), n)
    np.testing.assert_array_equal(np.asarray(out[:, :n]), np.asarray(tok))
    assert (np.asarray(out[:, n]) >= 0).all()


def test_chain_speculative_sampling_all_reject():
    """Disjoint supports: first draft token must be rejected, output token
    drawn from target at position 0, rest padded with -1."""
    batch, n, vocab = 3, 2, 16
    draft = np.zeros((batch, n, vocab), np.float32)
    draft[..., 0] = 1.0
    target = np.zeros((batch, n + 1, vocab), np.float32)
    target[..., 5] = 1.0
    tok = jnp.zeros((batch, n), jnp.int32)
    out, acc, emitted = fi.chain_speculative_sampling(
        jnp.array(draft), tok, jnp.array(target), jax.random.PRNGKey(0)
    )
    np.testing.assert_array_equal(np.asarray(acc), 0)
    np.testing.assert_array_equal(np.asarray(emitted), 0)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), 5)
    np.testing.assert_array_equal(np.asarray(out[:, 1:]), -1)


# ---- sorting-free threshold kernel (ops/sampling_kernels.py) -------------


class TestThresholdSelect:
    """Single-pass VMEM bisection kernel vs the sort-based XLA oracles.

    With continuous random inputs ties are measure-zero, so kept sets (and
    hence outputs) must agree up to fp tolerance."""

    def _probs(self, seed, batch=4, vocab=1000):
        rng = np.random.default_rng(seed)
        p = rng.random((batch, vocab)).astype(np.float32) ** 3
        return jnp.asarray(p / p.sum(-1, keepdims=True))

    def test_top_k_renorm(self):
        from flashinfer_tpu.ops.sampling_kernels import threshold_select
        from flashinfer_tpu.sampling import _top_k_renorm_probs_xla

        p = self._probs(0)
        k = jnp.asarray([1, 7, 40, 999], jnp.float32)
        out = threshold_select(p, k, k, mode="top_k")
        ref = _top_k_renorm_probs_xla(p, k.astype(jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)

    def test_top_p_renorm(self):
        from flashinfer_tpu.ops.sampling_kernels import threshold_select
        from flashinfer_tpu.sampling import _top_p_renorm_probs_xla

        p = self._probs(1)
        tp = jnp.asarray([0.1, 0.5, 0.9, 1.0], jnp.float32)
        out = threshold_select(p, tp, tp, mode="top_p")
        ref = _top_p_renorm_probs_xla(p, tp)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)

    def test_top_k_logits_mask(self):
        from flashinfer_tpu.ops.sampling_kernels import threshold_select
        from flashinfer_tpu.sampling import _top_k_mask_logits_xla

        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((3, 777)) * 4, jnp.float32)
        k = jnp.asarray([1, 10, 200], jnp.float32)
        out = threshold_select(x, k, k, mode="top_k_logits")
        ref = _top_k_mask_logits_xla(x, k.astype(jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("joint", [False, True])
    def test_top_k_top_p(self, joint):
        from flashinfer_tpu.ops.sampling_kernels import threshold_select
        from flashinfer_tpu.sampling import _top_k_top_p_filter_xla

        p = self._probs(3)
        k = jnp.asarray([5, 50, 400, 1000], jnp.float32)
        tp = jnp.asarray([0.3, 0.8, 0.95, 1.0], jnp.float32)
        mode = "top_k_top_p_joint" if joint else "top_k_top_p_seq"
        out = threshold_select(p, k, tp, mode=mode)
        ref = _top_k_top_p_filter_xla(p, k.astype(jnp.int32), tp, joint)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-7)

    def test_greedy_edges(self):
        """top_k=0 / top_p=0 mean greedy (reference edge semantics)."""
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        p = self._probs(4, batch=2, vocab=300)
        z = jnp.zeros((2,), jnp.float32)
        for mode in ("top_k", "top_p"):
            out = np.asarray(threshold_select(p, z, z, mode=mode))
            assert (out > 0).sum(-1).tolist() == [1, 1]
            np.testing.assert_array_equal(out.argmax(-1), np.asarray(p).argmax(-1))

    def test_top_k_logits_with_neg_inf_masked_tokens(self):
        """Pre-masked (-inf / -1e30 sentinel) logits must not poison the
        bisection range: banned tokens stay excluded, k finite survivors."""
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        rng = np.random.default_rng(7)
        x = rng.standard_normal((2, 256)).astype(np.float32) * 4
        x[0, 50:] = -np.inf  # structured-decoding ban pattern
        x[1, 100:] = -1e30  # this module's own sentinel (chained calls)
        k = jnp.asarray([5, 7], jnp.float32)
        out = np.asarray(
            threshold_select(jnp.asarray(x), k, k, mode="top_k_logits")
        )
        kept = out > -1e20
        assert kept[0].sum() == 5 and kept[1].sum() == 7
        # the kept sets are the finite top-k
        assert set(np.nonzero(kept[0])[0]) == set(np.argsort(-x[0])[:5])
        assert set(np.nonzero(kept[1])[0]) == set(np.argsort(-x[1])[:7])
        # fully-masked row: nothing kept, no nan
        x2 = np.full((1, 128), -np.inf, np.float32)
        out2 = np.asarray(threshold_select(
            jnp.asarray(x2), jnp.ones((1,)), jnp.ones((1,)), mode="top_k_logits"
        ))
        assert (out2 <= -1e20).all() and not np.isnan(out2).any()

    def test_public_api_backend_param(self):
        import flashinfer_tpu as fi

        p = self._probs(5)
        out_p = fi.top_k_renorm_probs(p, 10, backend="pallas")
        out_x = fi.top_k_renorm_probs(p, 10, backend="xla")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   rtol=1e-5, atol=1e-7)


    def test_threshold_near_uniform_ties(self):
        """Epsilon-tie contract at LLM vocab scale (ADVICE r2): on a
        near-uniform distribution every token within f32 bisection
        resolution of the cut is kept, so the kept count may exceed k —
        but only by the tied band, and never below k, and the kept set
        must still contain the true top-k."""
        from flashinfer_tpu.ops.sampling_kernels import threshold_select

        rng = np.random.default_rng(7)
        vocab = 128 * 1024
        # near-uniform: probs differ only in the ~1e-7 relative range where
        # the f32 threshold can no longer separate neighbors
        base = np.full((2, vocab), 1.0, np.float32)
        jitter = rng.random((2, vocab)).astype(np.float32) * 1e-5
        p = base + jitter
        p = p / p.sum(-1, keepdims=True)
        k = 40
        out = np.asarray(threshold_select(
            jnp.asarray(p), jnp.full((2,), float(k), jnp.float32),
            jnp.full((2,), 1.0, jnp.float32), mode="top_k",
        ))
        kept = out > 0
        for row in range(2):
            n_kept = int(kept[row].sum())
            assert n_kept >= k, f"kept {n_kept} < k={k}"
            # tied-band bound: threshold error <= range * 2^-32 of the
            # bisection span; count tokens within one f32 ulp-band of the
            # k-th value and require kept <= k + that band
            kth = np.sort(p[row])[::-1][k - 1]
            band = np.abs(p[row] - kth) <= np.spacing(kth) * 4
            assert n_kept <= k + int(band.sum()), (
                f"kept {n_kept} exceeds k + tie band {k}+{int(band.sum())}"
            )
            # the true top-k values are all kept (no false drops)
            top_idx = np.argsort(-p[row])[:k]
            strict_top = p[row][top_idx] > kth + np.spacing(kth) * 4
            assert kept[row][top_idx[strict_top]].all()
        # renormalized output still sums to 1
        np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-3)


def test_sampling_epilogue_matches_pipe():
    """serve.sample_next_tokens == the LogitsPipe chain it mirrors, over
    several keys."""
    from flashinfer_tpu.logits_processor import (
        LogitsPipe, Sample, Softmax, Temperature, TopK, TopP,
    )
    from flashinfer_tpu.serve import SamplingConfig, sample_next_tokens

    pipe = LogitsPipe([Temperature(), Softmax(), TopK(), TopP(), Sample()])
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 257),
                               jnp.float32) * 3.0
    sampling = SamplingConfig(temperature=0.8, top_k=40, top_p=0.95)
    for i in range(4):
        k = jax.random.PRNGKey(i)
        ref = pipe(logits, key=k, temperature=0.8, top_k=40, top_p=0.95)
        got = sample_next_tokens(logits, k, sampling)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # stage-skipping configs legalize too
    greedy_ish = sample_next_tokens(
        logits, jax.random.PRNGKey(9), SamplingConfig())
    assert greedy_ish.shape == (4,)
