"""Norm op correctness vs eager numpy references.

Mirrors the reference test pattern (tests/norm/): build inputs, run op,
compare to an eager fp32 reference with tolerances."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flashinfer_tpu as fi


def ref_rmsnorm(x, w, eps, bias=0.0):
    x = np.asarray(x, np.float32)
    w = np.asarray(w, np.float32) + bias
    var = (x * x).mean(-1, keepdims=True)
    return x / np.sqrt(var + eps) * w


@pytest.mark.quick
@pytest.mark.parametrize("batch", [1, 19, 128])
@pytest.mark.parametrize("hidden", [128, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_rmsnorm(batch, hidden, dtype, backend):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (batch, hidden), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (hidden,), dtype)
    out = fi.rmsnorm(x, w, eps=1e-6, backend=backend)
    ref = ref_rmsnorm(x, w, 1e-6)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_gemma_rmsnorm(backend):
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256,), jnp.float32)
    out = fi.gemma_rmsnorm(x, w, backend=backend)
    ref = ref_rmsnorm(x, w, 1e-6, bias=1.0)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_add_rmsnorm(backend, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 512), dtype)
    r = jax.random.normal(jax.random.PRNGKey(1), (32, 512), dtype)
    w = jax.random.normal(jax.random.PRNGKey(2), (512,), dtype)
    out, new_r = fi.fused_add_rmsnorm(x, r, w, backend=backend)
    s = np.asarray(x, np.float32) + np.asarray(r, np.float32)
    ref = ref_rmsnorm(s, w, 1e-6)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(new_r, np.float32), s, rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, rtol=tol, atol=tol)


def test_layernorm():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(1), (128,), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (128,), jnp.float32)
    out = fi.layernorm(x, g, b)
    xn = np.asarray(x)
    ref = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
        xn.var(-1, keepdims=True) + 1e-5
    ) * np.asarray(g) + np.asarray(b)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.quick
@pytest.mark.parametrize("rows, want", [
    (1024, 256), (512, 256), (64, 64),   # the block divides: as before
    (640, 128), (384, 128),              # ragged at 256: the candidate below
    (300, 256), (19, 19),                # no candidate divides: as before
])
def test_row_block_divides_the_rows_where_a_candidate_can(rows, want):
    """A ragged last block costs the kernel VMEM stack beside its blocks
    (``[640, 8192]`` at 256 rows is refused inside a step), so rows that a
    smaller candidate divides take that one; the result is the same."""
    from flashinfer_tpu import norm

    assert norm._tuned_row_block(rows, 8192, jnp.bfloat16, "rmsnorm",
                                 None) == want
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fi.rmsnorm(x, w, eps=1e-6, backend="pallas")),
        ref_rmsnorm(x, w, 1e-6), rtol=1e-5, atol=1e-5)
