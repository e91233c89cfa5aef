"""The knee sweep's verdicts: a rate is sustained on a seed while its
backlog stays flat and its tail near the lowest rate's; the knee is the
highest rate every seed sustains."""
from bench import knee


def _row(rate, p95, trend):
    return {"rate": rate, "query_p95_ms": p95, "backlog_trend": trend}


def test_a_growing_backlog_or_tail_ends_what_a_seed_sustains():
    rows = [_row(40e3, 10.0, 1.0), _row(60e3, 12.0, 1.05),
            _row(80e3, 25.0, 1.0), _row(100e3, 15.0, 1.4)]
    assert knee.sustained(rows) == {40e3: True, 60e3: True, 80e3: False,
                                    100e3: False}


def test_the_knee_is_what_every_seed_sustains_below_any_failure():
    a = [_row(40e3, 10.0, 1.0), _row(60e3, 11.0, 1.0), _row(80e3, 12.0, 1.0)]
    b = [_row(40e3, 10.0, 1.0), _row(60e3, 11.0, 1.3), _row(80e3, 12.0, 1.0)]
    assert knee.knee({1: a}) == 80e3
    assert knee.knee({1: a, 2: b}) == 40e3
    assert knee.knee({1: [_row(40e3, 10.0, 2.0)]}) is None
