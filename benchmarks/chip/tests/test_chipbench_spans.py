"""The readers of the program's host spans (``host_spans.py`` and the six
metrics on it), on a recorded fixture: the span records of three engine
ticks of a small engine on the CPU (five requests into two slots; the
first tick compiles), then two training steps (the first compiles),
times shifted to start at 0.  By hand, in ns:

- ``caps.tick`` 446127859, 2809148, 2582863;
- their ``upload`` 75256675, 1280850, 1061077; ``dispatch`` 367339005,
  506701, 523447; ``fetch`` 2949207, 619812, 668204;
- ``caps.request.queue`` 140559, 168180, 446351574, 446373122,
  449167265 (median 446351574);
- ``caps.train.dispatch`` 1403049115, 221915.
"""

import importlib
import json
import os
import pathlib
import sys
import types

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

import host_spans  # noqa: E402
import spec  # noqa: E402

SERVE = ("queue_wait_ms.serve", "tick_engine_ms.serve",
         "tick_upload_ms.serve", "tick_dispatch_ms.serve",
         "tick_fetch_ms.serve")
READERS = SERVE + ("step_dispatch_ms.train",)
TICK_MS = (446127859 + 2809148 + 2582863) / 3e6
UPLOAD_MS = (75256675 + 1280850 + 1061077) / 3e6
DISPATCH_MS = (367339005 + 506701 + 523447) / 3e6
FETCH_MS = (2949207 + 619812 + 668204) / 3e6
WANT = {
    "queue_wait_ms.serve": 446351574 / 1e6,
    "tick_engine_ms.serve": TICK_MS - UPLOAD_MS - DISPATCH_MS - FETCH_MS,
    "tick_upload_ms.serve": UPLOAD_MS,
    "tick_dispatch_ms.serve": DISPATCH_MS,
    "tick_fetch_ms.serve": FETCH_MS,
    "step_dispatch_ms.train": (1403049115 + 221915) / 2e6,
}


def _records():
    text = (CHIP / "tests" / "fixtures" / "spans_cpu_3ticks.json").read_text()
    return [tuple(r) for r in json.loads(text)["records"]]


def _program(recs):
    return types.SimpleNamespace(records=lambda: list(recs))


def _read(name, trace=True):
    ctx = types.SimpleNamespace(trace=object() if trace else None, run={})
    return spec.metric_reader(name).read(ctx)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_hand_computed_value(monkeypatch, name):
    monkeypatch.setattr(host_spans, "_spans", _program(_records()))
    assert _read(name) == pytest.approx(WANT[name], rel=1e-12)


def test_tick_metrics_add_up_to_the_mean_tick(monkeypatch):
    monkeypatch.setattr(host_spans, "_spans", _program(_records()))
    parts = [_read(n) for n in SERVE if n != "queue_wait_ms.serve"]
    assert sum(parts) == pytest.approx(TICK_MS, rel=1e-12)
    split = host_spans.tick_split_ms(_records())
    assert split["ticks"] == 3 and split["tick"] == pytest.approx(TICK_MS)


def test_phase_never_recorded_reads_none(monkeypatch):
    # renamed in place: a record's position is what ``parent`` names
    no_fetch = [("other",) + r[1:] if r[0] == "caps.tick.fetch" else r
                for r in _records()]
    monkeypatch.setattr(host_spans, "_spans", _program(no_fetch))
    assert _read("tick_fetch_ms.serve") is None
    assert _read("tick_engine_ms.serve") == pytest.approx(
        TICK_MS - UPLOAD_MS - DISPATCH_MS)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_records(monkeypatch, name):
    monkeypatch.setattr(host_spans, "_spans", _program(_records()))
    assert _read(name, trace=False) is None
    monkeypatch.setattr(host_spans, "_spans", _program([]))
    assert _read(name) is None
    monkeypatch.setattr(host_spans, "_spans", None)
    assert _read(name) is None


@pytest.fixture
def without_program_spans(monkeypatch):
    """``host_spans`` imported where the program has no
    ``repro.core.spans`` (an older checkout)."""
    import repro.core
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    monkeypatch.delattr(repro.core, "spans", raising=False)
    try:
        yield importlib.reload(host_spans)
    finally:
        monkeypatch.undo()
        importlib.reload(host_spans)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_the_program_module(without_program_spans,
                                                   name):
    assert without_program_spans._spans is None
    assert _read(name) is None
