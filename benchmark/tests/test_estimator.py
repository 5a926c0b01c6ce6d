"""The estimator: what one slow step, a failed step, an unaligned edge
and a periodic stall do to each number.

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import estimator  # noqa: E402

STEP = 0.378
TOKENS = 32 * 1024


def steps(gaps, start=100.0, **more):
    """Step records, the opening stamp first."""
    out = [{"t": start, "committed": True, "participants": 1}]
    for g in gaps:
        out.append({"t": out[-1]["t"] + g, "committed": True, "participants": 1, **more})
    return out


def test_steady_window():
    numbers = estimator.window(steps([STEP] * 134), 51.0, TOKENS)
    assert numbers["tokens_per_s"] == pytest.approx(TOKENS / STEP)
    assert numbers["step_p90_ms"] == pytest.approx(STEP * 1e3)
    assert numbers["intervals"] == 134


def test_one_stall_moves_the_rate_and_not_the_median():
    gaps = [STEP] * 130
    gaps[77] = STEP + 1.2  # a host stall of 1.2 s, as seen once on the v5e
    numbers = estimator.window(steps(gaps), 51.0, TOKENS)
    assert numbers["tokens_per_s"] == pytest.approx(
        130 * TOKENS / (130 * STEP + 1.2)
    )
    assert numbers["tokens_per_s"] < 0.98 * TOKENS / STEP
    assert numbers["step_median_ms"] == pytest.approx(STEP * 1e3)
    assert numbers["step_p90_ms"] == pytest.approx(STEP * 1e3)


def test_a_stall_that_returns_every_few_steps_moves_rate_and_tail():
    gaps = [STEP + (0.05 if i % 5 == 0 else 0.0) for i in range(130)]
    numbers = estimator.window(steps(gaps), 51.0, TOKENS)
    assert numbers["tokens_per_s"] < 0.98 * TOKENS / STEP
    assert numbers["step_p90_ms"] == pytest.approx((STEP + 0.05) * 1e3)


def test_a_step_that_did_not_commit_carries_no_tokens_and_all_its_time():
    records = steps([STEP] * 100)
    for r in records[10:20]:
        r["committed"] = False
    numbers = estimator.window(records, 51.0, TOKENS)
    assert numbers["group_commits"] == 90
    assert numbers["tokens_per_s"] == pytest.approx(0.9 * TOKENS / STEP)
    assert numbers["window_s"] == pytest.approx(100 * STEP)


def test_a_commit_counts_the_groups_it_averaged():
    records = steps([4.0] * 10, participants=4)
    records[5]["participants"] = 3
    numbers = estimator.window(records, 51.0, TOKENS)
    assert numbers["group_commits"] == 39
    assert numbers["tokens_per_s"] == pytest.approx(39 * TOKENS / 40.0)


def test_window_closes_on_a_step_boundary():
    records = steps([STEP] * 200, start=0.0)
    numbers = estimator.window(records[3:], 51.0, TOKENS)
    assert numbers["t_close"] <= records[3]["t"] + 51.0 < numbers["t_close"] + STEP
    assert numbers["window_s"] == pytest.approx(numbers["intervals"] * STEP)
    times = [r["t"] for r in records]
    assert estimator.close_index(times, -100.0, 1.0) == -1


def test_too_short_a_window_reports_nothing():
    assert estimator.window(steps([60.0]), 51.0, TOKENS) is None
    assert estimator.window([], 51.0, TOKENS) is None


def test_percentile_is_nearest_rank():
    assert estimator.percentile(range(1, 101), 0.9) == 90
    assert estimator.percentile([5.0], 0.9) == 5.0
