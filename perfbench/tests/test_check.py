"""The reference against the engine's own dense reference tier at
``LlamaConfig.tiny`` widths on the CPU; the check fails when a cache row is
perturbed or a served token is altered; the int8 control reads well above
the program.  On a synthetic sample: what the check tells of the tokens'
gaps, the gaps it keeps, the controls by name.  Every family's limits are
what they were, and ``afmoe``'s token limits follow from the committed
readings by ISSUE 47's criterion."""

import json
import math
import pathlib
import types

import numpy as np
import pytest

import check
import family
import weights

DATA = pathlib.Path(__file__).resolve().parent / "data"


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
    assert list(ctl) == ["int8"]
    assert ctl["int8"]["kv_rel_rms"] > 3 * numbers["kv_rel_rms"]
    # greedy traffic: the control's drawn token is its best one
    assert ctl["int8"]["drawn.token_below_topk"] \
        <= ctl["int8"]["token_below_topk"]
    # (at these toy widths int8 stays inside the chip's limits)
    assert check.control_verdict(ctl, check.limits(fam),
                                 say=lambda s: None) == {"int8": []}


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


# ---------------------------------------------------------------------------
# What the check tells, on a synthetic sample
# ---------------------------------------------------------------------------

VOCAB = 64


def _toy_family(limits=None, controls=("int8", "float32-off")):
    """A family whose reference's logits are known: at every asked row the
    logit of token t is ``-t`` (token 0 is the best, token t has rank t +
    1), a control's the reverse."""
    def reference_forward(seqs, m, seed, *, on_rows, precision, logit_rows):
        import jax.numpy as jnp

        base = -jnp.arange(VOCAB, dtype=jnp.float32)
        if precision != "float32":
            base = base[::-1]
        return [jnp.tile(base, (len(r), 1)) for r in logit_rows]

    return types.SimpleNamespace(
        UNITS_HELD=1, ROWS_NUMBER="toy_rel_rms", CONTROLS=controls,
        LIMITS=limits or {"toy_rel_rms": 1.0, "token_below_topk": 5.0},
        reference_forward=reference_forward)


def _toy_samples():
    # top_k 4: the cutoff is token 3 (logit -3); token t's gap is t - 3
    served = [[0, 3, 4, 10], [2] * 95 + [13]]
    return [{"rid": f"r{i}", "tokens": [1] * (10 * (i + 1) + len(s) - 1),
             "prog_rows": None,
             "logit_rows": [10 * (i + 1) - 1 + j for j in range(len(s))],
             "served": s} for i, s in enumerate(served)]


def test_the_token_table_ranks_and_labels_every_served_token():
    samples, fam = _toy_samples(), _toy_family()
    said, kept = [], {}
    numbers, ctl = check.compare(samples, fam, {}, 3, top_k=4, kept=kept,
                                 say=said.append)
    assert ctl is None and numbers["token_below_topk"] == 10.0
    t = kept["tokens"]
    assert len(t["gap"]) == 100 and kept["top_k"] == 4
    assert t["gap"][:4] == [-3.0, 0.0, 1.0, 7.0]
    assert t["rank"][:4] == [1, 4, 5, 11]          # a known rank
    assert t["index"][:5] == [0, 1, 2, 3, 0]       # a first token is 0
    assert t["position"][:5] == [9, 10, 11, 12, 19]
    assert t["prompt_len"][:5] == [10, 10, 10, 10, 20]
    assert t["request"][3:5] == ["r0", "r1"]
    assert not any(t["in_flight"]) and set(t["spread"]) == {3.0}
    # 100 gaps: 95 at -1, then -3, 0, 1, 7, 10
    stats = kept["stats"]
    assert stats == check.token_stats(t["gap"])
    assert stats["token_gap_p99"] == 7.0           # a known percentile
    assert stats["token_gap_p90"] == 0.0           # inside the top k: 0
    assert stats["token_outside_topk_share"] == pytest.approx(0.03)
    assert stats["token_over_0p1_share"] == pytest.approx(0.03)
    assert stats["token_gap_mean_positive"] == pytest.approx(0.18)
    json.dumps(kept)                               # --keep-gaps writes it
    dist = next(ln for ln in said if "percentiles 50 / 90 / 99" in ln)
    assert "3.00% outside the top 4" in dist
    assert "-1.0000 / -1.0000 / 7.0000" in dist
    widest = next(ln for ln in said if "check: widest gaps" in ln)
    first = widest.split("): ")[1].split("; ")
    assert len(first) == check.WIDEST_SAID
    assert first[0] == "r1, finished, 20, 95, 114, 14, 10.0000, 3.0000"
    assert first[1] == "r0, finished, 10, 3, 12, 11, 7.0000, 3.0000"


def test_a_family_holds_a_statistic_by_naming_it_in_its_limits():
    fam = _toy_family({"toy_rel_rms": 1.0, "token_below_topk": 20.0,
                       "token_gap_p99": 5.0})
    numbers, _ = check.compare(_toy_samples(), fam, {}, 3, top_k=4,
                               say=lambda s: None)
    assert list(numbers) == ["toy_rel_rms", "token_below_topk",
                             "token_gap_p99", "rows_not_finite"]
    said = []
    assert not check.verdict(numbers, check.limits(fam), said.append)
    assert "compared token_gap_p99: 7 (limit 5) NOT OK" in said
    assert "compared token_below_topk: 10 (limit 20) ok" in said


def test_controls_run_by_name_and_an_unknown_name_is_refused(capsys):
    fam = _toy_family()
    assert check.controls_of(fam, "") == check.controls_of(fam, "0") == ()
    assert check.controls_of(fam, "1") == check.controls_of(fam, True) \
        == ("int8",)
    assert check.controls_of(fam, "float32-off,int8") \
        == ("float32-off", "int8")
    with pytest.raises(ValueError, match=r"int8.*float32-off"):
        check.controls_of(fam, "float32-dense")
    kept = {}
    _, ctl = check.compare(
        _toy_samples(), fam, {}, 3, top_k=4, control="float32-off",
        sampling={"temperature": 0.8, "top_k": 4, "top_p": 0.95}, kept=kept,
        say=lambda s: None)
    # the reversed logits: its own 4th best is token 60, its drawn tokens
    # are of 60..63, each far outside the reference's top 4
    off = ctl["float32-off"]
    assert off["token_below_topk"] == 57.0
    assert 57.0 <= off["drawn.token_gap_p90"] <= 60.0
    assert off["drawn.token_outside_topk_share"] == 1.0
    drawn = kept["controls"]["float32-off"]
    assert drawn["numbers"] == off and len(drawn["tokens"]["gap"]) == 100
    assert min(drawn["tokens"]["rank"]) >= 61
    failed = check.control_verdict(ctl, check.limits(fam), lambda s: None)
    assert failed == {"float32-off": ["token_below_topk"]}
    # the harness refuses the name before it looks for a chip
    import run

    with open(DATA.parent.parent.parent / "BENCHMARK.json") as f:
        cell = json.load(f)["workloads"][0]["name"]
    assert run.main(["--workload", cell, "--control", "float32-dense"]) == 2
    err = capsys.readouterr().err
    assert "float32-dense" in err and "['int8']" in err


def test_tokens_are_drawn_as_the_traffic_samples():
    import jax.numpy as jnp

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(500, 32)),
                         jnp.float32)
    sampling = {"temperature": 0.8, "top_k": 5, "top_p": 0.9}
    a = check.draw_tokens(logits, sampling, np.random.default_rng([7, 1]))
    b = check.draw_tokens(logits, sampling, np.random.default_rng([7, 1]))
    assert a == b and len(set(a)) > 5
    order = np.argsort(-np.asarray(logits), -1)
    assert all(t in order[i, :5] for i, t in enumerate(a))
    # the top p leaves the tail of the top k out: the 5th best is rare or
    # absent, the best the most frequent
    place = [int(np.where(order[i] == t)[0][0]) for i, t in enumerate(a)]
    assert place.count(0) > place.count(1) > place.count(3)
    greedy = check.draw_tokens(logits, {"temperature": 0.8, "top_k": 1},
                               np.random.default_rng(0))
    assert greedy == order[:, 0].tolist()


# ---------------------------------------------------------------------------
# The families' limits
# ---------------------------------------------------------------------------

WERE = {"llama_dense": {"kv_rel_rms": 0.04, "token_below_topk": 0.4},
        "granite_hybrid": {"state_rel_rms": 0.07, "token_below_topk": 0.02},
        "glm_dsa": {"latent_rel_rms": 0.145, "token_below_topk": 1.2},
        "minicpm_sala": {"state_rel_rms": 0.34, "token_below_topk": 0.30},
        "afmoe": {"kv_rel_rms": 0.11}}


@pytest.mark.parametrize("name", sorted(WERE))
def test_a_familys_limits_are_what_they_were(name):
    """ISSUE 47 moves ``afmoe``'s token numbers and nothing else."""
    fam = family.load({"family": name})
    limits = {n: v for n, v in fam.LIMITS.items()
              if name != "afmoe" or n not in check.TOKEN_STATS}
    assert limits == WERE[name]
    assert fam.CONTROLS[0] == "int8" and not hasattr(fam, "CONTROL_PRECISION")
    assert set(fam.LIMITS) - set(check.TOKEN_STATS) == {fam.ROWS_NUMBER}


# ---------------------------------------------------------------------------
# afmoe's token limits, re-derived from the committed readings
# ---------------------------------------------------------------------------

with open(DATA / "afmoe_token_gaps.json") as _f:
    READINGS = json.load(_f)
OFF = "float32-nowindow"


def _afmoe_token_limits():
    fam = family.load({"family": "afmoe"})
    return {n: v for n, v in fam.LIMITS.items() if n in check.TOKEN_STATS}


def test_afmoes_token_limits_follow_from_the_committed_readings():
    """ISSUE 47's criterion (``gap_readings.derive``) over the committed
    sound runs, both controls and the readings of earlier PRs gives the
    limits ``afmoe.py`` holds, with the room it asks."""
    import gap_readings as gr

    held = _afmoe_token_limits()
    assert gr.derive(READINGS, OFF) == held
    seeds = [r["seed"] for r in READINGS["sound"]]
    assert len(set(seeds)) == len(seeds) >= 16
    assert {3800000603, 1041883974} <= set(seeds)   # the two that tripped
    record = READINGS["on_record"]
    assert {0.6925, 0.4535} <= set(record["sound_widest"])
    widest = [r["stats"]["token_below_topk"] for r in READINGS["sound"]]
    assert held["token_below_topk"] >= 2 * max(widest + record["sound_widest"])
    off = [r["token_below_topk"] for r in READINGS["controls"][OFF]]
    assert len(off) >= 2 and 1.5 * held["token_below_topk"] \
        <= min(off + record[f"{OFF}_widest"])
    # the number that repeats: a candidate is held only where the sound runs
    # spread under 2x and window off reads 2.25x the largest of them
    rows = gr.table(READINGS, OFF)
    assert set(rows) == set(check.TOKEN_STATS) - {"token_below_topk"}
    earned = {n for n, r in rows.items()
              if r["spread"] < 2 and r[f"{OFF}_ratio"] >= 2.25}
    assert set(held) - {"token_below_topk"} == (
        {max(earned, key=lambda n: rows[n][f"{OFF}_ratio"])} if earned
        else set())
    for name in set(held) - {"token_below_topk"}:
        sound = [r["stats"][name] for r in READINGS["sound"]]
        assert 1.5 * max(sound) <= held[name]
        assert all(1.5 * held[name] <= r["stats"][name]
                   for r in READINGS["controls"][OFF])
    # window off reads far over every candidate all the same (printed)
    assert all(r[f"{OFF}_ratio"] > 10 for r in rows.values())
    # int8 is the rows number's to catch: limit 0.11, as it was
    assert all(r["kv_rel_rms"] > 1.25 * 0.11
               for r in READINGS["controls"]["int8"])
    assert all(r["kv_rel_rms"] < 0.11 / 1.3 for r in READINGS["sound"])


@pytest.mark.parametrize("run", READINGS["sound"],
                         ids=lambda r: str(r["seed"]))
def test_a_committed_sound_run_reads_correct_under_the_limits(run):
    """Each run's committed tail gives its widest gap and its 99th
    percentile again, and every held number stands inside its limit with
    the room ISSUE 47 asks (2x for the widest gap, 1.5x for the others)."""
    held = _afmoe_token_limits()
    gaps, n = run["tail"]["gap"], run["tokens"]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == min(n, 50)
    stats = run["stats"]
    assert gaps[0] == round(stats["token_below_topk"], 4)
    assert max(gaps[n - math.ceil(0.99 * n)], 0.0) \
        == round(stats["token_gap_p99"], 4)
    for x, count in run["over"].items():
        if count < len(gaps):
            assert sum(g > float(x) for g in gaps) == count
    assert run["over"]["0"] == round(stats["token_outside_topk_share"] * n)
    for name, limit in held.items():
        assert stats[name] * (2 if name == "token_below_topk" else 1.5) \
            <= limit, name
    # a gap over 0 is a rank past k, and the widest are rounding at the
    # cutoff: ranks of a few hundred in a vocabulary of 200,192
    ranks = [r for r, g in zip(run["tail"]["rank"], gaps) if g > 0]
    assert all(READINGS["top_k"] < r < 400 for r in ranks)
