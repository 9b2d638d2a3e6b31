"""The open-loop schedule and timing from the due time."""
import time

import numpy as np
import pytest

from bench import run, traffic


def test_schedule_same_gaps_every_seed_in_another_order():
    a = traffic.schedule(2**31 + 11, 2, 20.0, 30.0)
    b = traffic.schedule(2**31 + 12, 2, 20.0, 30.0)
    assert a.size == b.size == 600
    assert a[0] == 0.0 and (np.diff(a) > 0).all() and a[-1] < 30.0
    def gaps(due):  # the last gap runs to the end of the window
        return np.sort(np.append(np.diff(due), 30.0 - due[-1]))

    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)
    assert not np.allclose(np.diff(a), np.diff(b))
    np.testing.assert_array_equal(a, traffic.schedule(2**31 + 11, 2, 20.0,
                                                      30.0))


class _Ticket:
    def __init__(self, lat_ms):
        self.lat_ms = lat_ms

    def result(self, timeout=None):
        return type("R", (), {"latency_ms": self.lat_ms,
                              "tier_served": "exact"})()


def test_a_stall_makes_later_requests_late_from_their_due_time():
    t0 = time.perf_counter() + 0.02
    due = t0 + np.array([0.0, 0.01, 0.02])

    def submit(i, w):  # the first submit blocks for 80 ms
        if i == 0:
            time.sleep(0.08)
        return _Ticket(5.0)

    client = traffic.OpenLoop(due, submit, lambda: None)
    client.start()
    time.sleep(0.15)
    client.stop()
    assert [s.i for s in client.sent] == [0, 1, 2]
    cell = run.Cell.__new__(run.Cell)
    cell.exact = True
    win = run.Window(seconds=0.03)
    cell._collect(win, client, t0, t0 + 0.03, 0)
    lat = [r.latency_ms for r in win.requests]
    # request 0 answered 5 ms after it was sent, timed from its due time;
    # 1 and 2 waited behind the stalled submit, and their wait counts
    s0 = client.sent[0]
    assert lat[0] == pytest.approx((s0.sent - s0.due) * 1e3 + 5.0)
    assert lat[1] > 70.0 - 10.0 + 5.0 and lat[2] > 60.0 - 10.0
    assert win.lateness_s > 0.05


def test_a_failed_request_counts_as_missing_every_limit():
    win = run.Window(seconds=1.0)
    win.requests = [run.Request(row=i, due=0.0, window=None,
                                latency_ms=float(i)) for i in range(9)]
    win.requests += [run.Request(row=9 + i, due=0.0, window=None,
                                 latency_ms=float("inf"), failed=True)
                     for i in range(2)]
    assert run.reader("latency_p50_ms")(win) == 5.0
    win.requests += [run.Request(row=11 + i, due=0.0, window=None,
                                 latency_ms=float("inf"), failed=True)
                     for i in range(8)]  # now most requests failed
    assert run.reader("latency_p50_ms")(win) is None
