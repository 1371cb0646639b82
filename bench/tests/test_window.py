"""The window's arithmetic: it closes at the first answer at or after its
end, the rate is every row answered up to then over the time to it, and
percentiles are taken over every answer."""
import statistics

import numpy as np
import pytest

from bench.cell import Sent, window_metrics


def sent(t_sent, t_done, rows=10, phase="window", error=None):
    s = Sent(np.zeros((rows, 2), np.int32), np.ones(rows, np.int32),
             t_sent, phase, t_done=t_done)
    s.error = error
    return s


def test_rate_counts_rows_answered_up_to_the_close():
    t0, t1 = 100.0, 110.0
    out = window_metrics([
        sent(99.0, 100.5, phase="warmup"),   # warm-up: not counted
        sent(100.0, 101.0), sent(100.1, 102.0, rows=30),
        sent(108.0, 109.5),
        sent(109.0, 110.5, rows=20),         # first answer past t1: closes
        sent(109.5, 111.0),                  # after the close: out
        sent(105.0, 106.0, error="boom"),    # failed: out
    ], t0, t1)
    assert out["answered"] == 4
    assert out["closed_s"] == pytest.approx(10.5)
    assert out["updates_per_s"] == pytest.approx((10 + 30 + 10 + 20) / 10.5)


def test_answer_exactly_at_the_end_closes_the_window():
    out = window_metrics([sent(0.0, 5.0), sent(4.0, 10.0),
                          sent(6.0, 12.0)], 0.0, 10.0)
    assert out["answered"] == 2
    assert out["updates_per_s"] == pytest.approx(20 / 10.0)


def test_all_answered_before_the_end_keeps_the_whole_window():
    out = window_metrics([sent(0.0, 2.0), sent(1.0, 3.0)], 0.0, 10.0)
    assert out["answered"] == 2
    assert out["closed_s"] == pytest.approx(10.0)
    assert out["updates_per_s"] == pytest.approx(20 / 10.0)


@pytest.mark.parametrize("batches", [19, 20, 21])
def test_rate_has_no_whole_batch_steps(batches):
    """A steady stream of one answer every 2.43 s reads the same rate
    whether the window's end falls just before or just after an answer:
    no step of one batch in 20."""
    epoch, rows = 2.43, 2048
    t1 = 51.0 * batches / 21
    ss = [sent(i * epoch, (i + 1) * epoch, rows=rows) for i in range(40)]
    out = window_metrics(ss, 0.0, t1)
    assert out["updates_per_s"] == pytest.approx(rows / epoch)


def test_percentiles_over_all_samples():
    lat = np.random.default_rng(0).exponential(200.0, 500)
    ss = [sent(0.0, x / 1e3) for x in lat]
    out = window_metrics(ss, 0.0, 1e9)
    want = sorted(lat)
    assert out["delta_p50_ms"] == pytest.approx(statistics.median(want))
    # inclusive method: the 90th of 100 quantiles over every sample
    assert out["delta_p90_ms"] == pytest.approx(
        statistics.quantiles(want, n=100, method="inclusive")[89])
    assert np.percentile(want, 89) < out["delta_p90_ms"] < \
        np.percentile(want, 91)


@pytest.mark.parametrize("compiles,built,warm", [
    (0, 0, True), (11, 11, False), (0, 1, False), (2, 0, False)])
def test_a_window_that_compiles_gives_no_result(compiles, built, warm,
                                                capsys):
    from bench.run import window_was_warm
    assert window_was_warm({"compiles": compiles,
                            "backend_compiles": built}) is warm
    assert ("no longer covers" in capsys.readouterr().err) is not warm
