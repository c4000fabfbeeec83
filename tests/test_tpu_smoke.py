"""Real-TPU smoke tests: Mosaic-compile the Pallas kernels on hardware.

These auto-skip off-TPU (tpu_only marker) and run on the chip with
``FLASHINFER_TPU_TEST_ON_TPU=1 python -m pytest tests/test_tpu_smoke.py
tests/test_tpu_hw.py -p no:xdist`` — one process, because a chip belongs
to one process.  Keep shapes small — each test is one compile.  What
Mosaic accepts is also asked off-chip, on every tier-1 run, by
tests/test_mosaic_compile.py; only the chip says what the kernels compute.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flashinfer_tpu as fi
from flashinfer_tpu.testing import attention_ref

pytestmark = pytest.mark.tpu_only


def test_paged_decode_kernel_compiles():
    from flashinfer_tpu.ops import paged_decode_attention, xla_paged_decode

    B, HQ, HKV, D, PS, P = 4, 8, 2, 128, 16, 8
    kc = jax.random.normal(jax.random.PRNGKey(0), (32, HKV, PS, D), jnp.bfloat16)
    vc = jax.random.normal(jax.random.PRNGKey(1), (32, HKV, PS, D), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(2), (B, HQ, D), jnp.bfloat16)
    pt = jax.random.randint(jax.random.PRNGKey(3), (B, P), 0, 32)
    lens = jnp.array([100, 17, 128, 1], jnp.int32)
    o = paged_decode_attention(q, kc, vc, pt, lens, sm_scale=0.0883, kv_layout="HND")
    ref = xla_paged_decode(
        q, jnp.swapaxes(kc, 1, 2), jnp.swapaxes(vc, 1, 2), pt, lens,
        sm_scale=0.0883,
    )
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_flash_kernel_compiles_small():
    from flashinfer_tpu.ops import flash_attention

    T, H, KVH, D = 256, 8, 2, 128
    q = jax.random.normal(jax.random.PRNGKey(0), (T, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (T, KVH, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (T, KVH, D), jnp.bfloat16)
    seg = jnp.zeros((T,), jnp.int32)
    pos = jnp.arange(T)
    out = flash_attention(q, k, v, seg, seg, pos, pos, causal=True, sm_scale=0.0883)
    ref = attention_ref(q, k, v, causal=True, sm_scale=0.0883)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=3e-2, atol=3e-2
    )


def test_mla_decode_kernel_compiles():
    from flashinfer_tpu.ops.mla_decode import (
        mla_paged_decode_attention, xla_mla_paged_decode,
    )

    B, H, d_ckv, d_kpe, PS = 2, 16, 512, 64, 16
    ckv = jax.random.normal(jax.random.PRNGKey(0), (16, PS, d_ckv), jnp.bfloat16)
    kpe = jax.random.normal(jax.random.PRNGKey(1), (16, PS, d_kpe), jnp.bfloat16)
    qn = jax.random.normal(jax.random.PRNGKey(2), (B, H, d_ckv), jnp.bfloat16)
    qp = jax.random.normal(jax.random.PRNGKey(3), (B, H, d_kpe), jnp.bfloat16)
    pt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    lens = jnp.array([60, 33], jnp.int32)
    sm = 1 / np.sqrt(d_ckv + d_kpe)
    o = mla_paged_decode_attention(qn, qp, ckv, kpe, pt, lens, sm_scale=sm)
    ref = xla_mla_paged_decode(qn, qp, ckv, kpe, pt, lens, sm_scale=sm)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


def test_bsr_kernel_compiles():
    w = fi.BlockSparseAttentionWrapper(backend="pallas")
    M = N = 256
    ind = np.array([0, 1, 3], np.int32)
    idx = np.array([0, 0, 1], np.int32)
    w.plan(ind, idx, M, N, 128, 128, 4, 4, 128)
    q = jax.random.normal(jax.random.PRNGKey(0), (M, 4, 128), jnp.bfloat16)
    out = w.run(q, q, q)
    assert np.isfinite(np.asarray(out, np.float32)).all()
