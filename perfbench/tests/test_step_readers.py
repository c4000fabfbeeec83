"""The readers of the step's ledger phases and of the step's facts, on
hand-made inputs."""

import pytest

from readers import step_ratio, steploop_phases


def _step(phases, spans=None, **facts):
    rec = {"phases": phases, "device_us": 1000.0}
    if spans is not None:
        rec["spans"] = spans
    return dict({"tokens": 8, "sampled": 2, "ledger": rec}, **facts)


FIVE = {"admit": 0.001, "schedule": 0.002, "assemble": 0.003,
        "lower": 0.010, "dispatch": 0.004}


def test_phases_add_host_phases_and_children_per_step():
    steps = [
        _step(FIVE, {"lower.build": 0.006, "lower.upload": 0.004,
                     "dispatch.upload": 0.003, "dispatch.call": 0.001,
                     "fetch": 1.0, "post": 0.0005}),
        _step({**FIVE, "assemble": 0.005},
              {"lower.build": 0.008, "lower.upload": 0.002,
               "dispatch.upload": 0.003, "dispatch.call": 0.001,
               "fetch": 1.0, "post": 0.0015}),
        {"tokens": 0, "sampled": 0},            # an idle step: no record
    ]
    ctx = {"steps": steps}
    plan = {"phases": ["admit", "schedule", "assemble", "lower.build"],
            "stat": "median"}
    # 1+2+3+6 = 12 ms and 1+2+5+8 = 16 ms
    assert steploop_phases.read(ctx, plan) == pytest.approx(14.0)
    assert steploop_phases.read(ctx, dict(plan, stat="mean")) \
        == pytest.approx(14.0)
    up = {"phases": ["lower.upload", "dispatch.upload", "dispatch.call"],
          "stat": "median"}
    assert steploop_phases.read(ctx, up) == pytest.approx(7.0)  # 8 and 6
    assert steploop_phases.read(ctx, {"phases": ["post"], "stat": "mean"}) \
        == pytest.approx(1.0)


def test_phases_read_nothing_from_a_program_without_them():
    """The parent's ledger has the five phases and no ``spans``: a sum
    that needs a child reads nothing, and does not read a part of it."""
    ctx = {"steps": [_step(FIVE), _step(FIVE)]}
    assert steploop_phases.read(
        ctx, {"phases": ["admit", "lower.build"], "stat": "median"}) is None
    assert steploop_phases.read(
        ctx, {"phases": ["post"], "stat": "median"}) is None
    assert steploop_phases.read(
        ctx, {"phases": ["admit", "lower"], "stat": "median"}) \
        == pytest.approx(11.0)
    assert steploop_phases.read({"steps": []}, {"phases": ["admit"],
                                                "stat": "median"}) is None


def test_ratio_is_of_the_sums_not_a_mean_of_ratios():
    steps = [{"tokens": 100, "prefill_tokens": 90, "prefill_units": 10,
              "prefill_units_launched": 4096, "first_scheduled": 1,
              "queue_wait_s": 2.0},
             {"tokens": 50, "prefill_tokens": 0, "prefill_units": 0,
              "prefill_units_launched": 4096, "first_scheduled": 0,
              "queue_wait_s": 0.0},
             {"tokens": 50, "prefill_tokens": 30, "prefill_units": 6,
              "prefill_units_launched": 4096, "first_scheduled": 3,
              "queue_wait_s": 1.0},
             {"tokens": 0, "sampled": 0}]       # idle: no such facts
    ctx = {"steps": steps}
    assert step_ratio.read(ctx, {"num": "prefill_tokens", "den": "tokens",
                                 "scale": 100}) == pytest.approx(60.0)
    assert step_ratio.read(ctx, {"num": "prefill_units",
                                 "den": "prefill_units_launched",
                                 "scale": 100}) \
        == pytest.approx(100 * 16 / 12288)
    assert step_ratio.read(ctx, {"num": "queue_wait_s",
                                 "den": "first_scheduled", "scale": 1000}) \
        == pytest.approx(750.0)
    assert step_ratio.read(ctx, {"num": "prefill_tokens",
                                 "den": "tokens"}) == pytest.approx(0.6)


def test_ratio_reads_nothing_without_the_facts_or_with_an_empty_base():
    parent = [{"tokens": 100, "sampled": 4}, {"tokens": 50, "sampled": 4}]
    assert step_ratio.read({"steps": parent},
                           {"num": "prefill_tokens", "den": "tokens"}) is None
    none_first = [{"tokens": 4, "first_scheduled": 0, "queue_wait_s": 0.0}]
    assert step_ratio.read({"steps": none_first},
                           {"num": "queue_wait_s",
                            "den": "first_scheduled"}) is None
    assert step_ratio.read({"steps": []}, {"num": "a", "den": "b"}) is None
