"""The end-to-end arithmetic: a rate over all the tokens and all the time of
the window, and percentiles that failed requests cannot improve."""

import math

import pytest

import estimators as est


@pytest.mark.parametrize("phase", [0.0, 0.07, 0.21, 0.33, 0.5, 0.58])
@pytest.mark.parametrize("window", [40.0, 50.0])
def test_rate_on_lumps_is_within_a_lump_whatever_the_phase(phase, window):
    """One fused call of 32 steps at 64 seats reports 2,048 tokens at once:
    all tokens over all the window is the true rate to within one lump over
    the window's tokens, wherever the window's edges fall."""
    period, lump = 0.584, 2048
    events = [(phase + i * period, lump) for i in range(200)]
    rate, n = est.window_rate(events, 10.0, 10.0 + window)
    true = lump / period
    assert abs(rate - true) / true <= period / window
    assert n == round(rate * window / lump)


def test_rate_is_all_tokens_over_all_the_window():
    events = [(0.01 * i, 3) for i in range(1000)]
    rate, n = est.window_rate(events, 1.0, 9.0)
    assert rate == pytest.approx(300.0) and n == 800
    # a stall at either edge, or in the middle, costs what it cost
    stalled = [e for e in events if not 1.0 <= e[0] < 3.0]
    assert est.window_rate(stalled, 1.0, 9.0)[0] == pytest.approx(225.0)
    late = [e for e in events if not 4.0 <= e[0] < 6.0]
    assert est.window_rate(late, 1.0, 9.0)[0] == pytest.approx(225.0)
    assert est.window_rate([], 0.0, 1.0) == (0.0, 0)
    # the end is outside the window, the start inside
    assert est.window_rate([(0.0, 5), (1.0, 7)], 0.0, 1.0) == (5.0, 1)


def test_percentiles_keep_failed_requests_at_infinity():
    ok = [float(i) for i in range(1, 96)]
    assert est.percentile(ok, 95) == 91.0
    with_failed = ok + [est.INF] * 5
    assert est.percentile(with_failed, 95) == 95.0
    assert math.isinf(est.percentile(ok + [est.INF] * 6, 95))
    assert est.percentile(with_failed, 50) == 50.0
    assert est.percentile([], 95) is None
    assert est.finite(est.INF) == 1e9 and est.finite(3.5) == 3.5


def test_tpot_is_a_mean_over_the_request():
    # 128 tokens in four lumps of 32: the gaps are zeros and stalls, the
    # mean is what the user saw
    assert est.tpot_ms(1.0, 1.0 + 3 * 0.584, 128) == \
        pytest.approx(3 * 584 / 127)
    assert est.tpot_ms(1.0, 1.0, 1) is None
