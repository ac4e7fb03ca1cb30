"""The open loop's arithmetic with a fake clock and a fake engine:
latency runs from the due time, percentiles are over all requests, and
requests due in the window are drained after it."""

import dataclasses
import os
import pathlib
import sys
from collections import deque

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import openloop  # noqa: E402


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


@dataclasses.dataclass
class Req:
    rid: int
    image: object
    deadline_s: float | None = None
    submitted_s: float | None = None
    finished_s: float | None = None
    status: str = "pending"


class FakeEngine:
    """Serves every request admitted at a tick's start, ``tick_s`` later;
    a request whose deadline passed before its tick ends ``timeout``."""

    def __init__(self, clock, slots, tick_s):
        self.clock, self.tick_s = clock, tick_s
        self.queue, self.active, self.finished = deque(), [None] * slots, []

    def submit(self, req):
        req.submitted_s = self.clock()
        self.queue.append(req)

    def step(self):
        for s in range(len(self.active)):
            if self.active[s] is None and self.queue:
                self.active[s] = self.queue.popleft()
        self.clock.t += self.tick_s
        for s, req in enumerate(self.active):
            if req is None:
                continue
            late = (req.deadline_s is not None
                    and self.clock() - req.submitted_s > req.deadline_s)
            req.status, req.finished_s = ("timeout" if late else "ok"), \
                self.clock()
            self.finished.append(req)
            self.active[s] = None


def _drive(offsets, slots=1, tick_s=0.004, seconds=0.02, deadline_s=None):
    clock = FakeClock()
    eng = FakeEngine(clock, slots, tick_s)
    served = openloop.drive(eng, Req, np.asarray(offsets), [None],
                            deadline_s, seconds, clock=clock,
                            sleep=clock.sleep)
    return served, eng


def test_latency_runs_from_the_due_time():
    served, _ = _drive([0.0, 0.0005, 0.010])
    # The second request is due mid-tick and waits for it: 3.5 ms late to
    # the engine, 7.5 ms from due to done.
    assert openloop.latencies_ms(served) == pytest.approx([4.0, 7.5, 4.0])
    s = openloop.summary(served)
    assert s["lag_max_ms"] == pytest.approx(3.5)
    assert s["ticks"] == 3 and s["tick_max_ms"] == pytest.approx(4.0)


def test_percentiles_are_over_all_requests():
    offsets = np.arange(40) * 0.003
    served, _ = _drive(offsets, slots=2, tick_s=0.005, seconds=0.12)
    lat = openloop.latencies_ms(served)
    s = openloop.summary(served)
    assert len(lat) == 40 == s["due"] == s["ok"]
    assert s["serve_p50_ms"] == pytest.approx(np.percentile(lat, 50))
    assert s["serve_p95_ms"] == pytest.approx(np.percentile(lat, 95))


def test_requests_due_in_the_window_are_drained():
    # Arrivals every 1 ms, ticks of 10 ms for one slot: the queue grows
    # through the window and every request still ends.
    offsets = np.arange(20) * 0.001
    served, eng = _drive(offsets, slots=1, tick_s=0.010, seconds=0.02)
    s = openloop.summary(served)
    assert s["due"] == s["ok"] == 20 and s["failed"] == 0
    assert s["drained_s"] == pytest.approx(0.2)
    assert s["max_queue"] >= 10
    assert len(eng.finished) == 20


def test_a_request_past_its_deadline_counts_as_failed():
    offsets = np.arange(10) * 0.001
    served, _ = _drive(offsets, slots=1, tick_s=0.010, seconds=0.01,
                       deadline_s=0.035)
    s = openloop.summary(served)
    assert s["failed"] > 0 and s["ok"] + s["failed"] == 10
    assert len(openloop.latencies_ms(served)) == s["ok"]


def test_arrivals_fix_the_count_and_the_gaps_and_follow_the_seed():
    a = openloop.arrivals(7, 100.0, 3.0)
    b = openloop.arrivals(8, 100.0, 3.0)
    assert len(a) == len(b) == 300
    assert np.all(np.diff(a) >= 0) and a[0] == 0 and a[-1] < 3.0
    assert np.array_equal(a, openloop.arrivals(7, 100.0, 3.0))
    assert not np.array_equal(a, b)
    # the same gaps in another order; their mean is the rate's
    assert np.allclose(np.sort(np.diff(a)), np.sort(np.diff(b)), atol=0.02)
    assert np.mean(np.diff(a)) == pytest.approx(0.01, rel=0.02)
    big = 2 ** 33 + 7      # the high bits of a large seed count
    assert not np.array_equal(openloop.arrivals(big, 100.0, 3.0),
                              openloop.arrivals(7, 100.0, 3.0))


def test_padded_sizes():
    assert openloop.padded_sizes(16) == [1, 2, 4, 8, 16]
    assert openloop.padded_sizes(2) == [1, 2]
    assert openloop.padded_sizes(6) == [1, 2, 4, 6]


def test_a_stall_shows_as_a_long_tick_and_when_it_began():
    clock = FakeClock()
    eng = FakeEngine(clock, 1, 0.004)
    step = eng.step

    def stalling():
        if len(eng.finished) == 3:       # the fourth tick stalls 100 ms
            clock.t += 0.1
        step()
    eng.step = stalling
    served = openloop.drive(eng, Req, np.arange(8) * 0.005, [None], None,
                            0.04, clock=clock, sleep=clock.sleep)
    s = openloop.summary(served)
    assert s["long_ticks"] == 1
    assert s["tick_max_ms"] == pytest.approx(104.0)
    assert s["tick_max_at_s"] == pytest.approx(0.015)
