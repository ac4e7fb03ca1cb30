"""Host spans of the program, on the profiler's clock.

Spans are on exactly while a JAX profiler trace runs (``enabled()``);
there is no other switch.  With no trace running, ``span`` returns one
shared no-op context manager and ``mark`` returns at once, so an
untraced caller pays one check per call.

While a trace runs, ``span(name)`` enters a
``jax.profiler.TraceAnnotation(name)``, so the span sits in the trace on
the device trace's clock, and also appends a record to a bounded
in-memory buffer.  ``mark`` records a span whose start was stamped
earlier (a request's queue wait); the profiler has no after-the-fact
API, so it goes to the buffer only.

A record is ``(name, start_ns, end_ns, parent, rid)``: times from
``time.perf_counter_ns()`` (``mark`` converts ``perf_counter`` seconds),
``parent`` the index, in the same buffer, of the span open around it on
the same thread (None at the top), and ``rid`` the request it belongs
to, or None.  A span still open has ``end_ns`` None.  Past ``LIMIT``
records, new ones are dropped and counted (``dropped()``).  Drain the
buffer (``drain()``) when no span is open: parents index the buffer.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

LIMIT = 1 << 20                      # records the buffer holds

_is_enabled = jax.profiler.TraceAnnotation.is_enabled
_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_buf: list = []
_dropped = 0


class _Stack(threading.local):
    def __init__(self):
        self.open: list = []         # buffer indices of this thread's spans


_stack = _Stack()


def enabled() -> bool:
    """Whether a JAX profiler trace is running (and spans are recorded)."""
    return _is_enabled()


def _append(rec: tuple) -> tuple[list, int] | None:
    """Append ``rec``; the buffer and its index there, or None where the
    buffer is full."""
    global _dropped
    with _lock:
        if len(_buf) >= LIMIT:
            _dropped += 1
            return None
        _buf.append(rec)
        return _buf, len(_buf) - 1


class _Span:
    __slots__ = ("name", "rid", "_ann", "_at", "_start", "_parent")

    def __init__(self, name: str, rid):
        self.name, self.rid = name, rid

    def __enter__(self):
        kw = {} if self.rid is None else {"rid": self.rid}
        self._ann = jax.profiler.TraceAnnotation(self.name, **kw)
        self._ann.__enter__()
        open_ = _stack.open
        self._parent = open_[-1] if open_ else None
        self._start = time.perf_counter_ns()
        self._at = _append((self.name, self._start, None, self._parent,
                            self.rid))
        open_.append(None if self._at is None else self._at[1])
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        _stack.open.pop()
        if self._at is not None:
            buf, i = self._at
            buf[i] = (self.name, self._start, end, self._parent, self.rid)
        self._ann.__exit__(*exc)         # never swallows an exception


def span(name: str, rid=None):
    """A context manager that records ``name`` while a trace runs."""
    if not _is_enabled():
        return _NULL
    return _Span(name, rid)


def mark(name: str, start_s: float, end_s: float, rid=None) -> None:
    """Record ``name`` from ``start_s`` to ``end_s`` (``perf_counter``
    seconds), stamped earlier, while a trace runs."""
    if not _is_enabled():
        return
    open_ = _stack.open
    _append((name, round(start_s * 1e9), round(end_s * 1e9),
             open_[-1] if open_ else None, rid))


def records() -> list:
    """The buffer's records, in the order their spans began."""
    with _lock:
        return list(_buf)


def dropped() -> int:
    """Records dropped since the last ``drain`` for a full buffer."""
    return _dropped


def drain() -> list:
    """The buffer's records; the buffer and the dropped count restart."""
    global _buf, _dropped
    with _lock:
        out, _buf, _dropped = _buf, [], 0
    return out
