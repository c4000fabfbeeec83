"""counts.py against numbers worked by hand for both configurations, and
its functions of a step's facts, which readers call by name."""

import json
import pathlib

import pytest

import counts

CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "configs"


def _m(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


def test_mistral_7b_by_hand():
    m = _m("mistral-7b-v0.3")
    # q 4096x4096, k/v 4096x1024, o 4096x4096, gate/up/down 4096x14336:
    # 4096 * (2*4096 + 2*1024 + 3*14336) = 4096 * 53,248
    assert counts.layer_weight_elems(m) == 4096 * 53248 == 218_103_808
    assert 2 * counts.layer_weight_elems(m) == 416 * 2**20      # 416 MiB
    assert counts.head_elems(m) == 4096 * 32768
    # K and V, 16 layers, 8 heads x 128, bf16: 64 KiB a token
    assert counts.kv_bytes_per_token(m) == 64 * 1024
    # 16 layers + embedding + head: 7.0 GiB
    assert counts.weight_bytes(m) == 16 * 416 * 2**20 + 2 * 256 * 2**20
    # a decode step over 48 lanes of 250 tokens: weights + head + K/V
    assert counts.decode_step_min_bytes(m, 48 * 250) == \
        16 * 416 * 2**20 + 256 * 2**20 + 12000 * 65536


def test_mistral_nemo_by_hand():
    m = _m("mistral-nemo-12b")
    # q 5120x4096, k/v 5120x1024, o 4096x5120, mlp 3 x 5120x14336
    assert counts.layer_weight_elems(m) == 5120 * 53248 == 272_629_760
    assert 2 * counts.layer_weight_elems(m) == 520 * 2**20      # 520 MiB
    assert 2 * counts.head_elems(m) == 1280 * 2**20             # 1.25 GiB
    assert counts.kv_bytes_per_token(m) == 64 * 1024
    assert counts.weight_bytes(m) == 16 * 520 * 2**20 + 2560 * 2**20
    # a 512-token chunk attending itself causally, one lane sampled
    pairs = 512 * 513 // 2
    assert counts.step_min_flops(m, 512, pairs, 1) == \
        2 * 512 * 16 * 272_629_760 + 4 * pairs * 16 * 32 * 128 \
        + 2 * 5120 * 131072
    assert counts.step_min_flops(m, 512, pairs, 1) == pytest.approx(
        4.5e12, rel=0.02)


def test_counts_of_a_steps_facts_by_hand():
    m = _m("mistral-7b-v0.3")
    facts = {"tokens": 557, "prefill_tokens": 512, "sampled": 45,
             "ctx_tokens": 12000}
    # 557 rows through 16 layers of 218,103,808 multiply-adds, 2 each
    assert counts.dense_flops(m, facts) == 2 * 557 * 16 * 218_103_808
    assert counts.dense_flops(m, facts) / 197e12 == pytest.approx(
        19.733e-3, rel=1e-4)
    # the 16 layers' weights once: 6.5 GiB, 8.52 ms at 819 GB/s
    assert counts.dense_bytes(m, facts) == 16 * 416 * 2**20
    assert counts.dense_bytes(m, facts) / 819e9 == pytest.approx(
        8.52e-3, rel=1e-3)
    assert counts.decode_min_bytes(m, facts) == \
        counts.decode_step_min_bytes(m, 12000)
