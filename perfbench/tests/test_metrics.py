"""Percentile and gap arithmetic on hand-made timelines."""

import pytest

import metrics


def _req(t_submit, token_times, asked=None, t_end=None, failed=False):
    return {"t_submit": t_submit, "token_times": token_times,
            "asked": asked or len(token_times), "t_end": t_end,
            "failed": failed}


def test_percentile_is_nearest_rank():
    vals = list(range(1, 21))          # 1..20
    assert metrics.percentile(vals, 95) == 19
    assert metrics.percentile(vals, 50) == 10
    assert metrics.percentile(vals, 100) == 20
    assert metrics.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        metrics.percentile([], 95)


def test_window_counts_only_what_fell_inside_it():
    # window (10, 20]
    reqs = [
        # first token before the window, two tokens inside, ends inside
        _req(5.0, [9.0, 11.0, 13.0], t_end=13.0),
        # submitted before, first token inside: its TTFT counts (6 s)
        _req(8.0, [14.0, 15.0, 21.0]),
        # token stamped exactly at t_open belongs to the time before
        _req(9.0, [10.0, 12.0], t_end=12.0, failed=True),
        # wholly after
        _req(20.5, [22.0], t_end=22.0),
    ]
    vals, n = metrics.end_to_end(reqs, 10.0, 20.0)
    assert n["tokens"] == 2 + 2 + 1          # 11,13 | 14,15 | 12
    assert vals["out_tokens_per_s"][0] == pytest.approx(5 / 10.0)
    assert n["ttft_samples"] == 1
    assert vals["ttft_p50_ms"][0] == pytest.approx(6000.0)
    assert vals["ttft_p95_ms"][0] == pytest.approx(6000.0)
    # gaps whose later token is inside: 9->11, 11->13, 14->15, 10->12
    assert sorted(metrics.gaps_ms(reqs, 10.0, 20.0)) == \
        pytest.approx([1000.0, 2000.0, 2000.0, 2000.0])
    assert vals["itl_p95_ms"][0] == pytest.approx(2000.0)
    assert n["attempted"] == 2 and n["failed"] == 1


def test_empty_samples_leave_the_metric_out():
    vals, n = metrics.end_to_end([_req(0.0, [])], 0.0, 1.0)
    assert "ttft_p50_ms" not in vals and "itl_p95_ms" not in vals
    assert vals["out_tokens_per_s"][0] == 0.0
    with pytest.raises(ValueError):
        metrics.end_to_end([], 1.0, 1.0)
