"""The window arithmetic: a rate over the whole window and a tail over all
gaps, on a stamp list with a stall in it."""

import statistics

import pytest

from chipbench import window


def _stamps():
    # 100 steps of 10 ms, then one stall of 510 ms, then 100 more of 10 ms
    out = [0.0]
    for i in range(201):
        out.append(out[-1] + (0.510 if i == 100 else 0.010))
    return out


def test_rate_is_all_samples_over_the_whole_window():
    stamps = _stamps()
    steps = len(stamps) - 1
    span = stamps[-1] - stamps[0]
    assert span == pytest.approx(2.51)
    assert window.rate_per_s(stamps, 256) == pytest.approx(steps * 256 / 2.51)
    # a median of step times would have hidden the stall
    assert 256 / (statistics.median(window.gaps_ms(stamps)) / 1e3) \
        > 1.2 * window.rate_per_s(stamps, 256)


def test_p95_is_over_all_gaps():
    gaps = window.gaps_ms(_stamps())
    assert len(gaps) == 201
    assert window.percentile(gaps, 95.0) == pytest.approx(10.0)
    assert window.percentile(gaps, 100.0) == pytest.approx(510.0)
    # with 12 of 201 steps stalled the tail has to show them
    slow = [0.0]
    for i in range(201):
        slow.append(slow[-1] + (0.5 if i % 17 == 0 else 0.01))
    assert window.percentile(window.gaps_ms(slow), 95.0) > 400.0


def test_percentile_interpolates_and_refuses_nothing():
    assert window.percentile([1.0, 2.0, 3.0, 4.0], 50.0) == pytest.approx(2.5)
    assert window.percentile([7.0], 95.0) == 7.0
    with pytest.raises(ValueError):
        window.percentile([], 95.0)
    with pytest.raises(ValueError):
        window.rate_per_s([1.0], 256)
