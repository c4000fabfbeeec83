"""serve/kv_write.py: a step's K/V rows go into the paged pool through the
pages the step touches, and the pool afterwards holds, bit for bit, what the
plain two-axis scatter leaves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flashinfer_tpu.serve.kv_write import (append_rows, page_cap,
                                           touched_pages)

PAGES, HKV, PS, D = 96, 2, 8, 16
MAX_BATCH = 48


def _chunk(start, n, pages):
    """Rows of one request: positions ``start .. start + n`` over its page
    list (``start`` not page-aligned continues a half-filled page)."""
    pos = np.arange(start, start + n)
    return np.asarray(pages)[pos // PS], pos % PS


def _step(shape):
    """(token_page, token_slot, rung) of one step's flat axis."""
    if shape == "chunk":
        # continues page 7 at slot 5, crosses 9 and 11, ends inside 13
        page, slot = _chunk(5, 23, [7, 9, 11, 13])
        rung = 32
    elif shape == "lanes48":
        # 48 one-token lanes, each at its own depth on its own page
        page = np.arange(1, 49)[::-1].copy()
        slot = (np.arange(48) * 5) % PS
        rung = 48
    elif shape == "mixed":
        # a chunk, then lanes, then pad rows to scratch page 0
        cp, cs = _chunk(14, 19, [40, 41, 42, 43, 44])
        page = np.concatenate([cp, [3, 90, 17, 2]])
        slot = np.concatenate([cs, [7, 0, 3, 4]])
        rung = 64
    elif shape == "own_page":
        # every row on a page of its own: the page list's worst case
        page = np.random.default_rng(3).permutation(np.arange(1, PAGES))[:64]
        slot = np.arange(64) % PS
        rung = 64
    pad = rung - len(page)
    return (np.concatenate([page, np.zeros(pad, int)]).astype(np.int32),
            np.concatenate([slot, np.zeros(pad, int)]).astype(np.int32),
            rung)


def _values(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.quick
@pytest.mark.parametrize("shape", ["chunk", "lanes48", "mixed", "own_page"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32, jnp.int8],
                         ids=["bf16", "f32", "int8"])
def test_append_rows_bitwise_vs_plain_scatter(dtype, shape):
    token_page, token_slot, rung = _step(shape)
    rng = np.random.default_rng(len(shape))
    kc, vc = (_values(rng, (PAGES, HKV, PS, D), dtype) for _ in range(2))
    k_w, v_w = (np.array(_values(rng, (rung, HKV, D), dtype))
                for _ in range(2))
    # the engine's pad rows are one row repeated (token 0 at position 0), so
    # the scratch slot they share has one value under either write
    n_real = int(np.count_nonzero(token_page))
    k_w[n_real:] = k_w[n_real:n_real + 1]
    v_w[n_real:] = v_w[n_real:n_real + 1]
    k_w, v_w = jnp.asarray(k_w), jnp.asarray(v_w)
    want_k = kc.at[token_page, :, token_slot, :].set(k_w)
    want_v = vc.at[token_page, :, token_slot, :].set(v_w)

    cap = page_cap(rung, PS, MAX_BATCH)
    assert len(set(token_page.tolist())) <= cap

    @jax.jit
    def write(kc, vc, k_w, v_w, token_page, token_slot):
        touched = touched_pages(token_page, cap=cap)
        return append_rows(kc, vc, k_w, v_w, touched, token_slot)

    got_k, got_v = write(kc, vc, k_w, v_w, token_page, token_slot)
    assert got_k.dtype == kc.dtype and got_v.dtype == vc.dtype
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    # and it wrote something: the rows are where they belong
    np.testing.assert_array_equal(
        np.asarray(got_k)[token_page[0], :, token_slot[0]],
        np.asarray(k_w)[0])


def test_page_cap_bounds_every_schedule():
    """``page_cap`` against schedules drawn to be hard for it: up to
    ``max_batch`` requests, each starting on a page's last slot."""
    rng = np.random.default_rng(0)
    for rung, ps, max_batch in [(64, 16, 64), (128, 16, 64), (1024, 16, 64),
                                (16, 8, 4), (64, 8, 4), (32, 8, 8)]:
        cap = page_cap(rung, ps, max_batch)
        assert cap <= rung
        for _ in range(200):
            left, pages = rung, 0
            for _req in range(int(rng.integers(1, max_batch + 1))):
                if not left:
                    break
                # short chunks, so that many requests fit the rung
                n = int(rng.integers(1, min(left, 3 * rung // max_batch) + 1))
                start = ps - 1 if rng.random() < 0.7 else int(
                    rng.integers(0, ps))
                pages += (start + n - 1) // ps + 1
                left -= n
            pages += bool(left)            # the pad rows' scratch page
            assert pages <= cap, (rung, ps, max_batch, pages, cap)


def test_step_facts_count_the_pages_written():
    """``step()`` reports the pages its rows land on and the usable pool."""
    from flashinfer_tpu.models.llama import LlamaConfig, init_llama_params
    from flashinfer_tpu.serve.engine import (EngineConfig, EngineRequest,
                                             ServingEngine)

    cfg = LlamaConfig.tiny(num_layers=1, dtype=jnp.float32)
    eng = ServingEngine(
        cfg, init_llama_params(jax.random.PRNGKey(0), cfg),
        EngineConfig(num_pages=32, page_size=8, max_batch=4,
                     prefill_budget_tokens=32, max_seq_tokens=64))
    eng.submit(EngineRequest("a", list(range(1, 20)), max_new_tokens=7))
    eng.submit(EngineRequest("b", list(range(1, 9)), max_new_tokens=2))
    facts = []
    while len(facts) < 8:
        facts.append(eng.step())
    assert {f["kv_pages_pool"] for f in facts if f["tokens"]} == {31}
    # 19 rows from scratch lie on 3 pages and 8 rows on 1; then a lane each
    # ("b" opens its second page with row 8 and ends), then "a" alone
    assert [f["kv_pages_written"] for f in facts if f["tokens"]] == \
        [4, 2, 1, 1, 1, 1, 1]
