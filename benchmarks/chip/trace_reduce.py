"""From a profiler trace to the benchmark's device numbers.

A trace is reduced first to plain events: device operations
``(device, name, start_ns, end_ns, pallas)`` from each TPU plane's "XLA
Ops" line, and host spans ``(name, start_ns, end_ns)`` that the
benchmark itself annotated (``SPANS``).  A TPU operation's event is
named with its whole HLO instruction; the plain name is the
instruction's (``primary_caps_routing.1``, ``fusion.12``), and
``pallas`` says whether it is a Pallas kernel (a ``tpu_custom_call``).
Everything after that works on the plain events, so the tests check it
on a small recorded fixture.

- busy time: the union of the device operations' intervals inside the
  window, averaged over the devices used;
- idle share: 1 - busy / window;
- kernel time: the summed durations of the operations whose name
  contains one of a kernel's patterns;
- idle gaps: each stretch of the window in which a device ran nothing,
  attributed to the host span that covers most of it.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import pathlib

WINDOW = "bench.window"
SPANS = ("engine.step", "generator.wait", "train.step", "train.sync",
         WINDOW)
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
NO_SPAN = "no host span"
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Trace:
    ops: list          # (device, name, start_ns, end_ns, pallas)
    spans: list        # (name, start_ns, end_ns)

    @property
    def window(self) -> tuple[float, float]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW!r} span, found {len(w)}")
        return w[0]

    def devices(self) -> list:
        return sorted({o[0] for o in self.ops})

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(ops=[tuple(o) for o in d["ops"]],
                   spans=[tuple(s) for s in d["spans"]])


def load_xplane(directory: str | pathlib.Path) -> Trace:
    """Plain events of the one ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData
    paths = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {directory}, "
                         f"found {len(paths)}")
    data = ProfileData.from_file(str(paths[0]))
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        name, pallas = op_name(e.name)
                        ops.append((dev, name, e.start_ns, e.end_ns, pallas))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in SPANS)
    return Trace(ops=ops, spans=spans)


def op_name(text: str) -> tuple[str, bool]:
    """(the HLO instruction's name, whether it is a Pallas kernel) of a
    device event named ``%name = shape op(...), ...``."""
    return text.split(" = ", 1)[0].lstrip("%"), PALLAS_MARK in text


def _merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds in the window in which an operation ran, averaged over
    the devices used."""
    lo, hi = trace.window
    devs = trace.devices()
    if not devs:
        return 0.0
    total = sum(e - s for d in devs
                for s, e in _merged([(o[2], o[3]) for o in trace.ops
                                     if o[0] == d], lo, hi))
    return total / len(devs) * 1e-9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window
    return (hi - lo) * 1e-9


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def op_seconds(trace: Trace, pallas: bool | None = None) -> dict[str, float]:
    """Device seconds by operation name inside the window, summed over
    devices and divided by their number; ``pallas`` keeps only the
    Pallas kernels (True) or only the other operations (False)."""
    lo, hi = trace.window
    out: dict[str, float] = {}
    ndev = max(len(trace.devices()), 1)
    for _, n, s, e, is_pallas in trace.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s and (pallas is None or pallas == is_pallas):
            out[n] = out.get(n, 0.0) + (e - s) * 1e-9 / ndev
    return out


def kernel_seconds(trace: Trace, patterns) -> float | None:
    """Device seconds of the Pallas kernels whose name contains one of
    ``patterns``; None where no kernel matches."""
    hits = [sec for n, sec in op_seconds(trace, pallas=True).items()
            if any(p in n for p in patterns)]
    return sum(hits) if hits else None


def xla_seconds(trace: Trace) -> float:
    """Device seconds of the operations that are not Pallas kernels."""
    return sum(op_seconds(trace, pallas=False).values())


def idle_gaps(trace: Trace) -> dict[str, float]:
    """Idle seconds of device 0's window by the host span (other than
    the window itself) that overlaps each gap most."""
    lo, hi = trace.window
    devs = trace.devices()
    busy = _merged([(o[2], o[3]) for o in trace.ops
                    if devs and o[0] == devs[0]], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < hi:
        gaps.append((t, hi))
    # The annotated spans come from one host thread, one after another,
    # so those that overlap a gap sit just before its end in start order,
    # and the first one found that ends before the gap ends the search.
    spans = sorted((s, e, n) for n, s, e in trace.spans if n != WINDOW)
    starts = [s for s, _, _ in spans]
    out: dict[str, float] = {}
    for gs, ge in gaps:
        best, name = 0.0, NO_SPAN
        k = bisect.bisect_left(starts, ge) - 1
        while k >= 0 and spans[k][1] > gs:
            s, e, n = spans[k]
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, name = ov, n
            k -= 1
        out[name] = out.get(name, 0.0) + (ge - gs) * 1e-9
    return out


def top(d: dict[str, float], k: int = 10) -> list[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]
