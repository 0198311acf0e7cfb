"""The open-loop generator: same seed same schedule, latency from the due
time, lateness reported, failures counted as misses."""

from concurrent.futures import Future

import numpy as np
import pytest

from benchmark import loadgen


def test_schedule_is_deterministic_in_the_seed():
    a = loadgen.poisson_schedule(7, 500.0, 2.0, 64)
    b = loadgen.poisson_schedule(7, 500.0, 2.0, 64)
    c = loadgen.poisson_schedule(8, 500.0, 2.0, 64)
    assert np.array_equal(a.due_s, b.due_s) and np.array_equal(a.session, b.session)
    assert not np.array_equal(a.due_s[:10], c.due_s[:10])
    assert np.all(np.diff(a.due_s) > 0) and a.due_s[-1] < 2.0
    assert a.session.min() >= 0 and a.session.max() < 64


def test_schedule_rate_is_the_fixed_rate():
    s = loadgen.poisson_schedule(0, 2000.0, 10.0, 8)
    assert abs(len(s.due_s) / 10.0 - 2000.0) < 3 * np.sqrt(2000.0 / 10.0)
    with pytest.raises(ValueError):
        loadgen.poisson_schedule(0, 0.0, 1.0, 8)


class _FakeTime:
    def __init__(self):
        self.t = 100.0

    def clock(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def test_latency_is_timed_from_when_the_request_was_due():
    """The server stalls 50 ms on request 1; requests 2 and 3, due during the
    stall, go out late and must carry the wait (a clock started at the actual
    send would hide it)."""
    ft = _FakeTime()
    sched = loadgen.Schedule(np.array([0.010, 0.020, 0.030, 0.040]), np.zeros(4, int))

    def submit(i, session):
        if i == 1:
            ft.t += 0.050           # a stall inside submit: the generator is held up
        f = Future()
        ft.t += 0.001               # service time
        f.set_result(None)
        return f

    res = loadgen.run_open_loop(submit, sched, clock=ft.clock, sleep=ft.sleep)
    assert list(res.status) == [loadgen.OK] * 4
    assert res.latency_s == pytest.approx([0.001, 0.051, 0.042, 0.033])
    # and how late the generator ran is reported
    assert res.late_s == pytest.approx([0.0, 0.0, 0.041, 0.032])


def test_failures_and_rejections_count_as_misses():
    class Full(RuntimeError):
        pass

    ft = _FakeTime()
    sched = loadgen.Schedule(np.array([0.0, 0.001, 0.002, 0.003]), np.arange(4))
    kept = []

    def submit(i, session):
        f = Future()
        if i == 0:
            f.set_exception(Full())
        elif i == 1:
            f.set_exception(ValueError("boom"))
        elif i == 2:
            raise Full()
        else:
            kept.append(f)          # never answered
        return f

    res = loadgen.run_open_loop(submit, sched, Full, drain_s=0.01, clock=ft.clock, sleep=ft.sleep)
    assert list(res.status) == [loadgen.REJECTED, loadgen.FAILED, loadgen.REJECTED, loadgen.UNRESOLVED]
    assert loadgen.percentile_with_failures(res, 99.0, fail_latency_s=5.0) == 5.0


def test_real_clock_smoke_reports_small_lateness():
    sched = loadgen.poisson_schedule(1, 400.0, 0.25, 4)

    def submit(i, session):
        f = Future()
        f.set_result(None)
        return f

    res = loadgen.run_open_loop(submit, sched)
    assert (res.status == loadgen.OK).all() and res.elapsed_s >= sched.due_s[-1]
    assert np.all(res.late_s >= 0.0) and np.median(res.late_s) < 0.01


def test_a_stall_reaches_the_whole_window_tail_and_is_located():
    """30 s at 100 req/s, 5 ms latency; one 2 s stall delays 200 requests and
    fails 40. p99 over the whole window must carry it (the misses count as the
    whole window), and the generator's own hold-up is located."""
    due = np.arange(3000) / 100.0
    lat = np.full(3000, 0.005)
    late = np.zeros(3000)
    status = np.full(3000, loadgen.OK, np.int8)
    lat[1000:1200] = np.linspace(2.0, 0.005, 200)
    late[1000:1200] = np.linspace(1.9, 0.0, 200)
    late[2500] = 0.03
    status[1000:1040] = loadgen.REJECTED
    res = loadgen.LoadResult(lat, status, late, 30.0)
    assert loadgen.percentile_with_failures(res, 50.0, 30.0) == pytest.approx(0.005)
    assert loadgen.percentile_with_failures(res, 95.0, 30.0) > 0.4
    assert loadgen.percentile_with_failures(res, 99.0, 30.0) == 30.0   # 40 of 3000 missed: over 1 %
    stalls = loadgen.generator_stalls(res, due)
    assert [round(a, 2) for a, _ in stalls] == [10.0, 25.0]
    assert stalls[0][1] == pytest.approx(1.9) and stalls[1][1] == pytest.approx(0.03)


def test_no_stall_no_entry():
    res = loadgen.LoadResult(np.full(10, 0.005), np.zeros(10, np.int8), np.full(10, 0.001), 1.0)
    assert loadgen.generator_stalls(res, np.arange(10) / 10.0) == []
