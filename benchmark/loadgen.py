"""Open-loop load from a seed: the schedule, the sender, the latency rule.

Copied in idea from bench.py::_rate_window and repaired (PERF.md section 6):
the seed is an argument, every request is timed FROM WHEN IT WAS DUE (a stall
delays the requests behind it and they must show it), and how late the
generator itself ran is reported, so a starved generator is not read as a
fast server. One thread sends; completions are stamped by the server's own
threads through the future's callback.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Callable, NamedTuple

import numpy as np

OK, REJECTED, FAILED, UNRESOLVED = 0, 1, 2, 3


class Schedule(NamedTuple):
    due_s: np.ndarray      # (N,) seconds from the window's start, ascending
    session: np.ndarray    # (N,) session index of each arrival


def poisson_schedule(seed: int, rate_per_s: float, seconds: float, sessions: int) -> Schedule:
    """Independent users: exponential gaps at `rate_per_s`, each arrival a
    uniformly drawn session. The same seed gives the same schedule."""
    if rate_per_s <= 0 or seconds <= 0 or sessions < 1:
        raise ValueError("rate_per_s, seconds and sessions must be positive")
    rng = np.random.default_rng(seed)
    n = int(rate_per_s * seconds * 1.2) + 64
    due = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))
    while due[-1] < seconds:  # vanishingly rare: extend, still from the seed
        due = np.concatenate([due, due[-1] + np.cumsum(rng.exponential(1.0 / rate_per_s, size=n))])
    due = due[due < seconds]
    return Schedule(due, rng.integers(0, sessions, size=due.shape[0]))


class LoadResult(NamedTuple):
    latency_s: np.ndarray   # (N,) completion - due; nan unless status == OK
    status: np.ndarray      # (N,) OK | REJECTED | FAILED | UNRESOLVED
    late_s: np.ndarray      # (N,) actual send - due (generator lateness)
    elapsed_s: float        # first due to last completion or drain limit


def run_open_loop(
    submit: Callable[[int, int], Future],
    schedule: Schedule,
    rejected_type: type = (),
    drain_s: float = 5.0,
    clock: Callable[[], float] = time.perf_counter,
    sleep: Callable[[float], None] = time.sleep,
    span=None,
) -> LoadResult:
    """Send request i (`submit(i, session)` -> Future) at its due time,
    whether or not earlier ones have been answered. A request that is behind
    schedule goes out at once. `span(name)` is an optional context manager
    factory (the profiler's TraceAnnotation) put around each send."""
    n = schedule.due_s.shape[0]
    latency = np.full(n, np.nan)
    status = np.full(n, UNRESOLVED, np.int8)
    late = np.zeros(n)
    pending = [n]
    done = threading.Event()
    lock = threading.Lock()
    t0 = clock()
    due_abs = t0 + schedule.due_s

    def settle(i: int, code: int, t: float) -> None:
        if code == OK:
            latency[i] = t - due_abs[i]
        status[i] = code
        with lock:
            pending[0] -= 1
            if pending[0] == 0:
                done.set()

    def on_done(i: int, fut: Future) -> None:
        t = clock()
        exc = fut.exception()
        if exc is None:
            settle(i, OK, t)
        else:
            settle(i, REJECTED if isinstance(exc, rejected_type) else FAILED, t)

    for i in range(n):
        wait = due_abs[i] - clock()
        if wait > 0:
            sleep(wait)
        sent = clock()
        late[i] = sent - due_abs[i]
        try:
            if span is not None:
                with span("loadgen.send"):
                    fut = submit(i, int(schedule.session[i]))
            else:
                fut = submit(i, int(schedule.session[i]))
        except Exception as e:  # noqa: BLE001 - a refused submit is a failed request
            settle(i, REJECTED if isinstance(e, rejected_type) else FAILED, clock())
            continue
        fut.add_done_callback(lambda f, i=i: on_done(i, f))
    if n:
        done.wait(timeout=drain_s)
    return LoadResult(latency, status.copy(), late, clock() - t0)


def percentile_with_failures(res: LoadResult, q: float, fail_latency_s: float) -> float:
    """The q-th percentile of latency over ALL attempted requests, a request
    that was rejected, failed or never answered counting as `fail_latency_s`
    (a miss of any limit), never as an absent sample."""
    lat = np.where(res.status == OK, res.latency_s, fail_latency_s)
    return float(np.percentile(lat, q)) if lat.size else float("nan")


def generator_stalls(res: LoadResult, due_s: np.ndarray, over_s: float = 0.010, gap_s: float = 0.2):
    """Where the generator itself was held up: requests sent more than
    `over_s` late, grouped into stalls (a gap of `gap_s` in due time starts a
    new one) -> [(due time of the first, worst lateness in s), ...]."""
    out = []
    for i in np.flatnonzero(res.late_s > over_s):
        if out and due_s[i] - out[-1][2] <= gap_s:
            out[-1] = (out[-1][0], max(out[-1][1], float(res.late_s[i])), float(due_s[i]))
        else:
            out.append((float(due_s[i]), float(res.late_s[i]), float(due_s[i])))
    return [(a, b) for a, b, _ in out]
