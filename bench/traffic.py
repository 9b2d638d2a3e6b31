"""Open-loop traffic: the arrival schedule, the client and the ingest feeder.

Every seed gets the same set of inter-arrival gaps, the quantiles of an
exponential distribution at the mix's rate, in an order the seed draws: the
offered work is the same on every seed and only its order changes. An
arrival is one request or a burst of several sent together. The
client submits each request when it is due, whatever the system is doing,
and times it from the moment it was due. The feeder offers the ingest
stream the same way, one batch per period.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

import numpy as np


def schedule(seed: int, stream: int, rate: float, seconds: float,
             burst: int = 1) -> np.ndarray:
    """Due offsets in [0, seconds) of ``round(rate * seconds / burst)``
    arrivals of ``burst`` requests each: exponential gaps at their quantile
    midpoints, shuffled by the seed, scaled so that the gaps fill exactly
    ``seconds``. Returns one offset per request."""
    rate = rate / burst
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng([seed, stream]).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return np.repeat(due * (seconds / gaps.sum()), burst)


@dataclasses.dataclass
class Sent:
    """One request as the client offered it."""
    i: int  # row of the query array
    due: float  # perf_counter time it was due
    sent: float  # perf_counter time just before submit()
    window: Optional[tuple]  # the (t0, t1) it asked for, or None
    ticket: object = None
    error: Optional[BaseException] = None


class OpenLoop:
    """Submit ``submit(i, window)`` at each due time on a thread of its own.

    ``window_of()`` gives the time window of a request at the moment it is
    sent (the stream cell's windows end at the newest acknowledged batch);
    requests due at the same time, a burst, share one. Requests whose
    submit raises are kept with the error."""

    def __init__(self, due: np.ndarray, submit: Callable,
                 window_of: Callable[[], Optional[tuple]]):
        self.due = due
        self.sent: list[Sent] = []
        self._submit = submit
        self._window_of = window_of
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-client",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        last_due, w = None, None
        for i, due in enumerate(self.due):
            wait = due - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if due != last_due:  # the requests of one burst share a window
                last_due, w = due, self._window_of()
            s = Sent(i=i, due=float(due), sent=time.perf_counter(), window=w)
            try:
                s.ticket = self._submit(i, w)
            except Exception as e:  # a refused request counts as failed
                s.error = e
            self.sent.append(s)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


class Feeder:
    """Offer ``batches[j]`` with timestamp ``ts0 + j`` at ``t0 + j * period``
    through ``ingest``; record when each call returned, the time spent in
    it, and the ingest lag read after it."""

    def __init__(self, batches: list, ts0: int, t0: float, period: float,
                 ingest: Callable, lag: Callable[[], int]):
        self.batches, self.ts0, self.t0, self.period = batches, ts0, t0, period
        self.acked_ts = ts0 - 1  # newest timestamp whose ingest returned
        self.log: list[tuple] = []  # (due, entered, returned, rows, lag)
        self.error: Optional[BaseException] = None
        self._ingest, self._lag = ingest, lag
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-feeder",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        try:
            for j, x in enumerate(self.batches):
                due = self.t0 + j * self.period
                wait = due - time.perf_counter()
                if wait > 0 and self._stop.wait(wait):
                    return
                if self._stop.is_set():
                    return
                t_in = time.perf_counter()
                self._ingest(x, np.full(x.shape[0], self.ts0 + j, np.int64))
                t_out = time.perf_counter()
                self.acked_ts = self.ts0 + j
                self.log.append((due, t_in, t_out, x.shape[0], self._lag()))
        except Exception as e:  # reported as a failed run by the caller
            self.error = e

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()
