"""Open-loop serving driver: seeded arrivals, one thread, no generator.

Arrival times are fixed before the window from the seed.  The loop that
steps the engine also submits: each turn it submits every request that
is due, then runs one engine tick; when nothing is pending it sleeps
until the next arrival.  There is no generator thread or event loop to
compete with the engine for the interpreter lock.

Each request's latency runs from the moment it was due on the schedule
to the moment its result is on the host, so a stall also counts against
the requests that arrive during it.  Requests due in the window are
drained after it ends and counted in full.

Clock and sleep are injectable so that tests drive the loop with a fake
clock and a fake engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax
import numpy as np


def arrivals(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Offsets (s) of ``round(rate x seconds)`` arrivals in
    ``[0, seconds)``.  The gaps between arrivals are the quantiles of the
    exponential distribution of that rate (a Poisson stream's gaps), in
    an order drawn from the seed: every seed offers the same requests
    with the same set of gaps, and only their order differs."""
    n = int(round(rate_per_s * seconds))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return offsets * (seconds / gaps.sum())


def padded_sizes(slots: int) -> list[int]:
    """The scatter sizes the engine pads a dirty set of 1..slots to."""
    return sorted({min(1 << (k - 1).bit_length(), slots)
                   for k in range(1, slots + 1)})


class CompileCounter:
    """Counts backend compiles and traces while ``active``, from JAX's
    own duration events (a compile served from the persistent cache
    still traces)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.active = False
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if not self.active:
            return
        if event == self.COMPILE:
            self.compiles += 1
        elif event == self.TRACE:
            self.traces += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


class GcPauses:
    """Seconds spent in garbage collection while ``active``."""

    def __init__(self, clock=time.perf_counter):
        self.active = False
        self.seconds = 0.0
        self.collections = 0
        self._clock = clock
        self._t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = self._clock()
        elif self._t is not None:
            if self.active:
                self.seconds += self._clock() - self._t
                self.collections += 1
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)


def warm_up(engine, make_request, images, slots: int) -> None:
    """Bursts of 1..slots requests through the public API, so the
    forward and every padded scatter size compile before the window:
    after a burst of k its k slots are dirty, so the next burst of k+1
    uploads k+1 rows."""
    rid = -1
    for k in range(1, slots + 1):
        for _ in range(k):
            engine.submit(make_request(rid, images[(-rid) % len(images)]))
            rid -= 1
        while pending(engine):
            engine.step()


def pending(engine) -> bool:
    return bool(engine.queue) or any(a is not None for a in engine.active)


@dataclasses.dataclass
class Served:
    requests: list             # submitted requests, in arrival order
    due_s: np.ndarray          # absolute due time of each
    lag_s: np.ndarray          # submit time - due time
    tick_s: np.ndarray         # duration of each engine tick
    tick_at_s: np.ndarray      # window start -> each tick's start
    max_queue: int
    window_s: float            # arrival window (the run's --seconds)
    drained_s: float           # window start -> last request terminal


def drive(engine, make_request, offsets: np.ndarray, images,
          deadline_s: float, seconds: float, *, clock=time.perf_counter,
          sleep=time.sleep, span=None) -> Served:
    """Run the open loop over ``offsets`` and drain.  ``span(name)``
    gives a context manager around each tick and each wait (the
    profiler's annotations in a traced run)."""
    span = span or (lambda _name: contextlib.nullcontext())
    n = len(offsets)
    reqs, lags, ticks, tick_at = [], np.zeros(n), [], []
    max_queue, i = 0, 0
    t0 = clock()
    due = t0 + offsets
    while True:
        now = clock()
        while i < n and due[i] <= now:
            req = make_request(i, images[i % len(images)], deadline_s)
            engine.submit(req)
            lags[i] = now - due[i]
            reqs.append(req)
            i += 1
        if pending(engine):
            max_queue = max(max_queue, len(engine.queue))
            with span("engine.step"):
                ts = clock()
                engine.step()
                ticks.append(clock() - ts)
                tick_at.append(ts - t0)
        elif i < n:
            with span("generator.wait"):
                sleep(max(0.0, due[i] - clock()))
        else:
            break
    return Served(requests=reqs, due_s=due, lag_s=lags,
                  tick_s=np.asarray(ticks), tick_at_s=np.asarray(tick_at),
                  max_queue=max_queue, window_s=seconds,
                  drained_s=clock() - t0)


def latencies_ms(served: Served) -> np.ndarray:
    """Due -> result on the host, for every request that ended ``ok``."""
    return np.asarray([1e3 * (r.finished_s - d)
                       for r, d in zip(served.requests, served.due_s)
                       if r.status == "ok"])


LONG_TICK = 10.0     # a stall: a tick over ten times the median tick


def summary(served: Served) -> dict:
    """End-to-end numbers and the run's diagnostics.  Percentiles are
    over all requests of the window, never medians of chunks.  A stall
    shows as ``long_ticks`` (ticks over ``LONG_TICK`` times the median)
    and as when the longest tick began (``tick_max_at_s``)."""
    lat = latencies_ms(served)
    ok = len(lat)
    ticks = served.tick_s
    longest = int(np.argmax(ticks)) if len(ticks) else None
    return {
        "due": len(served.requests), "ok": ok,
        "failed": len(served.requests) - ok,
        "serve_p50_ms": float(np.percentile(lat, 50)) if ok else None,
        "serve_p95_ms": float(np.percentile(lat, 95)) if ok else None,
        "lag_p95_ms": (1e3 * float(np.percentile(served.lag_s, 95))
                       if len(served.lag_s) else 0.0),
        "lag_max_ms": (1e3 * float(np.max(served.lag_s))
                       if len(served.lag_s) else 0.0),
        "ticks": len(ticks),
        "tick_mean_ms": (1e3 * float(np.mean(ticks))
                         if len(ticks) else None),
        "tick_max_ms": (1e3 * float(ticks[longest])
                        if longest is not None else None),
        "tick_max_at_s": (float(served.tick_at_s[longest])
                          if longest is not None else None),
        "long_ticks": (int(np.sum(ticks > LONG_TICK * np.median(ticks)))
                       if len(ticks) else 0),
        "max_queue": served.max_queue,
        "drained_s": served.drained_s,
    }
