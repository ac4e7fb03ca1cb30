"""The program's own host spans (``repro.core.spans``), reduced for the
per-layer readers.

A traced run starts the profiler at the window's start and stops it at
the window's end, before the reference runs, and the program records
spans exactly while a trace runs: the records cover the window, and the
readers take them from the program's buffer after the run.  A record is
``(name, start_ns, end_ns, parent, rid)``, ``parent`` the index of the
enclosing span's record.  A program without ``repro.core.spans`` (an
older checkout) gives None, as does an untraced run or a span never
recorded.

Each ``caps.tick`` (one engine ``step()``) splits into the engine's own
Python (its time less its ``upload``, ``dispatch`` and ``fetch``
children) and those three children, so the four per-tick means add up
to the mean tick.
"""

from __future__ import annotations

import statistics

import program  # noqa: F401  (puts the checkout's src/ on the path)

try:
    from repro.core import spans as _spans
except ImportError:
    _spans = None

TICK = "caps.tick"
PHASES = ("upload", "dispatch", "fetch")


def records(ctx) -> list | None:
    """The window's span records; None for an untraced run or a program
    that records none."""
    if ctx.trace is None or _spans is None:
        return None
    return _spans.records()


def _durations_ms(recs, name: str) -> list[float]:
    return [(r[2] - r[1]) * 1e-6 for r in recs
            if r[0] == name and r[2] is not None]


def mean_ms(ctx, name: str) -> float | None:
    """Mean duration of ``name``'s records, in ms."""
    d = _durations_ms(records(ctx) or (), name)
    return statistics.fmean(d) if d else None


def median_ms(ctx, name: str) -> float | None:
    """Median duration of ``name``'s records, in ms."""
    d = _durations_ms(records(ctx) or (), name)
    return statistics.median(d) if d else None


def tick_split_ms(recs) -> dict | None:
    """Per ``caps.tick``, in ms: the mean tick, the engine's own time,
    and each phase of ``PHASES`` (its total over the ticks / ticks; a
    phase never recorded is None)."""
    ticks = {i: r for i, r in enumerate(recs)
             if r[0] == TICK and r[2] is not None}
    if not ticks:
        return None
    names = {f"{TICK}.{p}": p for p in PHASES}
    total = dict.fromkeys(PHASES, 0.0)
    seen = set()
    for name, s, e, parent, _ in recs:
        if name in names and parent in ticks and e is not None:
            total[names[name]] += (e - s) * 1e-6
            seen.add(names[name])
    n = len(ticks)
    tick = sum((r[2] - r[1]) * 1e-6 for r in ticks.values()) / n
    out = {"ticks": n, "tick": tick,
           "engine": tick - sum(total.values()) / n}
    out.update({p: total[p] / n if p in seen else None for p in PHASES})
    return out


def tick_ms(ctx, part: str) -> float | None:
    """``part`` (``engine`` or a phase) of ``tick_split_ms``."""
    recs = records(ctx)
    split = tick_split_ms(recs) if recs else None
    return None if split is None else split[part]
