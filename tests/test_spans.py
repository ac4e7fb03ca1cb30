"""The program's host spans (``repro.core.spans``): off and empty with no
profiler running; under a profiler trace, one ``caps.tick`` per engine
tick with its five phases nested inside it, one ``caps.request.queue``
per admitted request, one ``caps.train.dispatch`` per training step, and
the same tick spans in the profiler's own trace on a host plane."""

import pathlib

import jax
import numpy as np
import pytest

from repro.core import capsnet, spans
from repro.core.capsnet import CapsNetConfig
from repro.serve import CapsRequest, CapsuleEngine
from repro.train.capsnet_loop import SMOKE, CapsLoopConfig, CapsTrainLoop

KEY = jax.random.PRNGKey(0)
CFG = CapsNetConfig(image_hw=14, conv1_channels=16, conv1_kernel=5,
                    pc_kernel=3, num_primary_groups=4, primary_dim=4,
                    class_dim=8, use_decoder=False)
PARAMS = capsnet.init_params(KEY, CFG)
PHASES = ("caps.tick.admit", "caps.tick.upload", "caps.tick.dispatch",
          "caps.tick.fetch", "caps.tick.finish")


class _Profiler:
    def __init__(self, directory: pathlib.Path):
        self.dir = directory
        self.running = True
        jax.profiler.start_trace(str(directory))

    def stop(self) -> None:
        if self.running:
            self.running = False
            jax.profiler.stop_trace()


@pytest.fixture
def profiler(tmp_path):
    """A profiler trace for the test; always stopped, and the span
    buffer drained, so no later test runs with spans on."""
    spans.drain()
    prof = _Profiler(tmp_path)
    try:
        yield prof
    finally:
        prof.stop()
        spans.drain()


def _serve(n_requests: int, slots: int = 2) -> tuple[CapsuleEngine, int]:
    imgs = np.asarray(jax.random.uniform(
        KEY, (n_requests, CFG.image_hw, CFG.image_hw, 1)))
    engine = CapsuleEngine(PARAMS, CFG, slots=slots)
    for i in range(n_requests):
        engine.submit(CapsRequest(rid=i, image=imgs[i]))
    steps = 0
    while engine.queue or any(a is not None for a in engine.active):
        engine.step()
        steps += 1
    return engine, steps


def _train(tmp_path, steps: int) -> None:
    loop = CapsTrainLoop(SMOKE, CapsLoopConfig(
        batch=4, backend="jnp", ckpt_dir=str(tmp_path / "ck")))
    state = loop._init_state()
    for i in range(steps):
        state, metrics = loop._run_step(state, loop._batch(i))
    jax.block_until_ready(metrics["loss"])


def test_nothing_recorded_without_a_profiler(tmp_path):
    spans.drain()
    assert not spans.enabled()
    _serve(3)
    _train(tmp_path, 2)
    assert spans.records() == []
    assert spans.span("caps.tick") is spans.span("caps.train.dispatch")


def test_tick_spans_nest_their_phases(profiler):
    _, steps = _serve(5)
    recs = spans.records()
    ticks = [i for i, r in enumerate(recs) if r[0] == "caps.tick"]
    assert len(ticks) == steps == 3
    for t in ticks:
        _, t0, t1, parent, _ = recs[t]
        assert parent is None and t0 < t1
        kids = [r for r in recs if r[3] == t]
        assert tuple(r[0] for r in kids) == PHASES
        for _, s, e, _, _ in kids:
            assert t0 <= s <= e <= t1
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))


def test_queue_wait_is_recorded_per_admitted_request(profiler):
    engine, _ = _serve(5)
    recs = spans.records()
    queue = {r[4]: r for r in recs if r[0] == "caps.request.queue"}
    assert sorted(queue) == list(range(5))
    for req in engine.finished:
        name, s, e, parent, rid = queue[req.rid]
        assert recs[parent][0] == "caps.tick.admit"
        assert (e - s) * 1e-9 == pytest.approx(
            req.admitted_s - req.submitted_s, abs=2e-9)
    assert max(r.admitted_s - r.submitted_s for r in engine.finished) > 0


def test_one_dispatch_span_per_train_step(profiler, tmp_path):
    _train(tmp_path, 3)
    recs = [r for r in spans.records() if r[0] == "caps.train.dispatch"]
    assert len(recs) == 3
    assert all(r[1] < r[2] and r[3] is None for r in recs)


def test_tick_spans_are_in_the_profiler_trace(profiler):
    _serve(3)
    profiler.stop()
    from jax.profiler import ProfileData
    [path] = sorted(profiler.dir.rglob("*.xplane.pb"))
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("caps.tick")]
    ticks = [(s, e) for n, s, e in events if n == "caps.tick"]
    assert len(ticks) == 2
    for phase in PHASES:
        inside = [(s, e) for n, s, e in events if n == phase]
        assert len(inside) == 2
        for s, e in inside:
            assert any(t0 <= s and e <= t1 for t0, t1 in ticks)


def test_span_closes_and_lets_an_exception_through(profiler):
    with pytest.raises(ValueError):
        with spans.span("caps.tick"):
            with spans.span("caps.tick.dispatch"):
                raise ValueError("forward failed")
    with spans.span("after"):
        pass
    (_, s0, e0, p0, _), (_, s1, e1, p1, _), after = spans.records()
    assert p0 is None and p1 == 0 and s0 <= s1 <= e1 <= e0
    assert after[3] is None


def test_full_buffer_counts_what_it_drops(profiler, monkeypatch):
    monkeypatch.setattr(spans, "LIMIT", 3)
    with spans.span("a"):
        with spans.span("b"):
            spans.mark("c", 1.0, 2.0, rid=7)
            spans.mark("d", 1.0, 2.0)
        with spans.span("e"):
            pass
    recs = spans.records()
    assert [r[0] for r in recs] == ["a", "b", "c"]
    assert recs[2] == ("c", 1_000_000_000, 2_000_000_000, 1, 7)
    assert spans.dropped() == 2
    assert len(spans.drain()) == 3
    assert spans.records() == [] and spans.dropped() == 0
