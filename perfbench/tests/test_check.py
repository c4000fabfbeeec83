"""The reference against the engine's own dense reference tier at
``LlamaConfig.tiny`` widths on the CPU; the check fails when a cache row is
perturbed or a served token is altered; the int8 control reads well above
the program."""

import numpy as np
import pytest

import check
import family
import weights


@pytest.fixture(scope="module")
def served():
    """Six greedy requests served by the engine's reference tier at tiny
    widths, with their cache rows read back as the harness reads them."""
    from flashinfer_tpu.serve import (EngineConfig, EngineRequest,
                                      SamplingConfig, ServingEngine)

    fam = family.load({})
    m = dict(fam.TINY, rope_theta=1e6, rms_norm_eps=1e-5,
             torch_dtype="bfloat16")
    seed = 2**31 + 5
    params = fam.make_params(seed, m)
    mcfg = fam.model_config(m)
    ecfg = EngineConfig(num_pages=96, page_size=16, max_batch=8,
                        prefill_budget_tokens=32, max_seq_tokens=160,
                        attention_backend="reference", role="prefill",
                        sampling=SamplingConfig(temperature=1.0, top_k=1))
    eng = ServingEngine(mcfg, params, ecfg)
    rng = np.random.default_rng(0)
    reqs = [EngineRequest(f"r{i}", rng.integers(1, 512, n).tolist(),
                          max_new_tokens=6)
            for i, n in enumerate((70, 33, 9, 100, 17, 48))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    samples = []
    for r in reqs:
        n = len(r.prompt) + len(r.out_tokens) - 1
        samples.append({
            "rid": r.rid, "tokens": (r.prompt + r.out_tokens)[:n],
            "prog_rows": fam.state_rows(eng, r, n),
            "logit_rows": [len(r.prompt) - 1 + j
                           for j in range(len(r.out_tokens))],
            "served": list(r.out_tokens)})
    return samples, fam, m, seed


def test_layer_params_are_the_bits_make_params_gave(served):
    _, _, m, seed = served
    whole = weights.make_params(seed, m)
    one = weights.layer_params(seed, m, 1)
    for name, a in one.items():
        assert np.array_equal(np.asarray(a.astype("float32")),
                              np.asarray(whole["layers"][1][name]
                                         .astype("float32"))), name
    ends = weights.ends_params(seed, m)
    assert np.array_equal(np.asarray(ends["lm_head"].astype("float32")),
                          np.asarray(whole["lm_head"].astype("float32")))


def test_reference_agrees_with_the_engine_and_int8_does_not(served):
    samples, fam, m, seed = served
    numbers, ctl = check.compare(samples, fam, m, seed, top_k=1, control=True)
    # bf16 storage and bf16 activations against float32: a few 1e-3
    assert 0 < numbers["kv_rel_rms"] < 0.008
    assert numbers["rows_not_finite"] == 0
    # greedy: the served token is the reference's best, to rounding
    assert numbers["token_below_topk"] < 0.05
    # the step below bf16 reads several times the program
    assert ctl["kv_rel_rms"] > 3 * numbers["kv_rel_rms"]


def test_a_perturbed_cache_row_fails(served):
    samples, fam, m, seed = served
    bad = [dict(s) for s in samples]
    rows = np.array(bad[0]["prog_rows"]["k"]).astype(np.float32)
    rows[1, 5] *= -1.0               # one K row of layer 1, sign flipped
    bad[0]["prog_rows"] = dict(bad[0]["prog_rows"], k=rows)
    sound, _ = check.compare(samples, fam, m, seed, top_k=1)
    numbers, _ = check.compare(bad, fam, m, seed, top_k=1)
    assert numbers["kv_rel_rms"] > 10 * sound["kv_rel_rms"]
    rows[1, 5] = np.nan
    numbers, _ = check.compare(bad, fam, m, seed, top_k=1)
    assert numbers["rows_not_finite"] > 0
    assert not check.verdict(dict(numbers, tokens_outside_vocab=0,
                                  traces_in_window=0, compiles_in_window=0),
                             check.limits(fam), say=lambda s: None)


def test_an_altered_token_fails(served):
    samples, fam, m, seed = served
    bad = [dict(s) for s in samples]
    bad[2]["served"] = [(t + 1) % 512 for t in bad[2]["served"]]
    numbers, _ = check.compare(bad, fam, m, seed, top_k=1)
    assert numbers["token_below_topk"] > 1.0


def test_verdict_holds_each_number_to_its_limit():
    limits = check.limits(family.load({}))
    assert limits == {"kv_rel_rms": 0.04, "token_below_topk": 0.4,
                      "rows_not_finite": 0, "tokens_outside_vocab": 0,
                      "traces_in_window": 0, "compiles_in_window": 0}
    good = {k: 0 for k in limits}
    assert check.verdict(good, limits, say=lambda s: None)
    for name in limits:
        worse = dict(good, **{name: limits[name] + 1})
        assert not check.verdict(worse, limits, say=lambda s: None)
    assert not check.verdict(dict(good, kv_rel_rms=float("nan")), limits,
                             say=lambda s: None)
