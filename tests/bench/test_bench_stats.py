"""The end-to-end arithmetic on a hand-built request table."""

import pytest

from bench import stats
from bench.drive import Record


def rec(rid, due, first=None, done=None, n=0, charged=0.0, failed=False,
        phase="window"):
    r = Record(rid, phase, due, 100, max(n, 1), submitted=due + 0.001,
               failed=failed)
    if first is not None:
        r.t_first, r.t_done, r.n_out = first, done, n
        r.output = [1] * n
        r.charged_s = charged
        r.tier = "edge"
    return r


WINDOW = (10.0, 20.0)


def table():
    return [
        rec(0, 9.0, 9.5, 10.5, 5, phase="pre"),      # before: tokens count
        rec(1, 10.0, 10.2, 11.2, 11, charged=0.02),   # 1.02 s, ttft .2
        rec(2, 12.0, 13.0, 13.0, 1),                  # one token: no tpot
        rec(3, 15.0, 15.1, 16.1, 3),
        rec(4, 19.0, 21.0, 22.0, 2),                  # finishes after
        rec(5, 18.0, failed=True),                    # refused
        rec(6, 25.0, 25.5, 26.0, 4, phase="post"),
    ]


def test_tails_are_over_every_request_due_in_the_window():
    out = stats.end_to_end(table(), WINDOW, stop=30.0)
    lat = [1.02, 1.0, 1.1, 3.0, 12.0]       # the refusal: 30 - 18
    ttft = [0.2, 1.0, 0.1, 2.0, 12.0]
    assert out["latency_p95_ms"] == pytest.approx(1e3 * stats.p95(lat))
    assert out["ttft_p95_ms"] == pytest.approx(1e3 * stats.p95(ttft))
    tpot = [1.0 / 10, 1.0 / 2, 1.0 / 1]
    assert out["tpot_p95_ms"] == pytest.approx(1e3 * stats.p95(tpot))


def test_tokens_are_those_finished_inside_the_window():
    out = stats.end_to_end(table(), WINDOW, stop=30.0)
    # requests 0, 1, 2, 3 finish in [10, 20): 5 + 11 + 1 + 3 tokens
    assert out["tokens_per_s"] == pytest.approx(20 / 10.0)


def test_counts_and_lateness():
    assert stats.counts(table(), WINDOW) == {"attempted": 5, "failed": 1}
    assert stats.lateness_p95_ms(table(), WINDOW) == pytest.approx(1.0)


def test_p95_is_numpy_linear():
    assert stats.p95(range(101)) == pytest.approx(95.0)
