"""The stratified generator: the same multiset of lengths for every seed,
in another order, and the closed loop's protocol."""

import collections

import pytest

import traffic
from generators import closed_loop

SPEC = {"kind": "closed_loop", "clients": 4, "prompt_len": [8, 100],
        "output_len": [2, 12], "n_strata": 8}


def _drain(seed, n_requests):
    gen = traffic.make(SPEC, seed=seed, vocab=512, max_seq_tokens=160)
    out = gen.due(0.0, None)
    while len(out) < n_requests:
        out += gen.due(0.0, [out[len(out) - gen.clients]["rid"]])
    return out


def test_strata_are_log_uniform_quantiles():
    s = closed_loop.strata(32, 1024, 96)
    assert len(s) == 96 and s == sorted(s)
    assert 32 <= s[0] <= 34 and 1000 <= s[-1] <= 1024
    assert 170 <= s[48] <= 190          # the median of 32..1024 log-uniform


def test_same_multiset_other_order_for_another_seed():
    a, b = _drain(1, 4 + 16), _drain(2**31 + 7, 4 + 16)
    # past each client's first (desynchronised) request: two whole decks
    la = [(len(r["prompt"]), r["output_len"]) for r in a[4:]]
    lb = [(len(r["prompt"]), r["output_len"]) for r in b[4:]]
    assert sorted(p for p, _ in la) == sorted(p for p, _ in lb)
    assert sorted(o for _, o in la) == sorted(o for _, o in lb)
    assert la != lb
    assert a[5]["prompt"] != b[5]["prompt"]
    assert all(1 <= t < 512 for r in a for t in r["prompt"])


def test_same_seed_same_requests():
    assert _drain(5, 12) == _drain(5, 12)


def test_first_requests_are_desynchronised_and_next_follows_its_client():
    gen = traffic.make(SPEC, seed=3, vocab=512, max_seq_tokens=160)
    first = gen.due(0.0, None)
    assert [r["client"] for r in first] == [0, 1, 2, 3]
    deck = collections.Counter(o for _, o in gen.deck)
    assert all(1 <= r["output_len"] <= max(deck) for r in first)
    nxt = gen.due(1.0, [first[2]["rid"]])
    assert len(nxt) == 1 and nxt[0]["client"] == 2
    assert gen.due(2.0, []) == []


def test_shared_prefixes_are_data():
    spec = dict(SPEC, shared_prefix_len=16, shared_prefix_count=2)
    gen = traffic.make(spec, seed=3, vocab=512, max_seq_tokens=160)
    reqs = gen.due(0.0, None)
    assert reqs[0]["prompt"][:16] == reqs[2]["prompt"][:16]
    assert reqs[0]["prompt"][:16] != reqs[1]["prompt"][:16]


def test_traffic_that_cannot_fit_is_refused_at_the_start():
    with pytest.raises(ValueError):
        traffic.make(SPEC, seed=0, vocab=512, max_seq_tokens=90)


def test_every_committed_mix_loads():
    import pathlib

    for p in (pathlib.Path(traffic.HERE) / "traffic").glob("*.json"):
        spec = traffic.load(p.stem)
        traffic.make(spec, seed=0, vocab=32768, max_seq_tokens=2048)
