"""Open-loop arrivals on a fixed timeline, and the record of what came back.

A copy of the timeline and recorder of ``xflow_tpu/serve/loadgen.py`` with its
two faults mended: a request's latency runs from the instant it was DUE, not
from the instant the generator got round to submitting it, so a stalled
generator shows as latency and not as a quiet server; and how late the
generator ran is reported beside it, so a starved generator is not read as a
fast server either.

One thread submits (the caller's).  It sleeps until each due instant and
never spins: a spinning thread would hold the interpreter lock against the
server's own threads.
"""

from __future__ import annotations

import functools
import time

import numpy as np

PENDING, ANSWERED, SHED, ERROR = 0, 1, 2, 3


def poisson_due(rng: np.random.Generator, rate: float, seconds: float) -> np.ndarray:
    """Due offsets in [0, seconds) of a Poisson process of ``rate`` a second."""
    n = int(rate * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    while due[-1] < seconds:
        more = np.cumsum(rng.exponential(1.0 / rate, n)) + due[-1]
        due = np.concatenate([due, more])
    return due[due < seconds]


class OpenLoop:
    """``submit(row) -> Future`` offered ``rows[i]`` at ``start + due[i]``.

    After ``run`` and ``drain``: ``status[i]``, ``late[i]`` (seconds the
    submit ran behind its due instant), ``latency[i]`` (seconds from the due
    instant to the answer; nan where none came) and ``answer[i]``.
    ``shed_error`` is the exception type that means "refused at the door",
    which is an outcome and not a failure.
    """

    def __init__(self, submit, rows, due: np.ndarray, shed_error: type):
        self.submit, self.rows, self.due = submit, rows, due
        self.shed_error = shed_error
        n = len(due)
        self.status = np.full(n, PENDING, np.int8)
        self.late = np.zeros(n)
        self.latency = np.full(n, np.nan)
        self.answer = np.full(n, np.nan)
        self.start = 0.0

    def run(self, start: float | None = None) -> None:
        clock, sleep = time.perf_counter, time.sleep
        self.start = clock() if start is None else start
        for i, offset in enumerate(self.due):
            due_at = self.start + offset
            wait = due_at - clock()
            if wait > 0:
                sleep(wait)
            self.late[i] = max(0.0, clock() - due_at)
            try:
                fut = self.submit(self.rows[i])
            except self.shed_error:
                self.status[i] = SHED
                continue
            except Exception:  # one failed request, not a dead generator
                self.status[i] = ERROR
                continue
            fut.add_done_callback(functools.partial(self._done, i, due_at))

    def _done(self, i: int, due_at: float, fut) -> None:
        """Runs on the server's thread that resolved the future."""
        done = time.perf_counter()
        err = fut.exception()
        if err is None:
            self.answer[i] = fut.result()
            self.latency[i] = done - due_at
            self.status[i] = ANSWERED
        else:
            self.status[i] = SHED if isinstance(err, self.shed_error) else ERROR

    def drain(self, timeout: float) -> int:
        """Wait up to ``timeout`` for what is still out; returns how many
        never came back."""
        deadline = time.perf_counter() + timeout
        while (self.status == PENDING).any() and time.perf_counter() < deadline:
            time.sleep(0.005)
        return int((self.status == PENDING).sum())
