"""A serving cell: the engine under the open loop, then the check.

Set-up makes the parameters and a pool of request images from the seed,
builds the engine through its normal entry point and warms up every
shape the window can use.  The window offers ``rate_per_s`` requests a
second for ``seconds``; every request due in it is drained and counted.
Then the engine is freed and the plain reference, at "highest"
precision, classifies a seeded sample of the window's requests.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

import checks
import openloop


def _sample(seed: int, n: int, k: int) -> np.ndarray:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    return np.sort(rng.choice(n, size=min(n, k), replace=False))


def _chunk(ref, s: dict) -> int:
    """Images per reference call: the widest layer's votes within about
    256 MB."""
    widest = max(lay["in_caps"] * lay["num_caps"] * lay["caps_dim"]
                 for lay in ref.routing_stack(s))
    return max(1, min(256, (1 << 28) // (4 * widest)))


def reference_lengths(ref, s: dict, seed: int, pool: int, rids,
                      precision: str) -> dict:
    """rid -> the reference's capsule lengths of that request's image,
    made anew from the seed."""
    images = ref.request_images(seed, s, pool)
    chunk = _chunk(ref, s)
    fn = jax.jit(lambda p, x: ref.lengths(p, x, s, precision))
    params = ref.init_params(seed, s)
    out = {}
    rids = list(rids)
    for lo in range(0, len(rids), chunk):
        part = rids[lo:lo + chunk]
        batch = images[[r % pool for r in part]]
        if len(part) < chunk:        # one compiled shape for every chunk
            batch = np.concatenate(
                [batch, np.zeros((chunk - len(part), *batch.shape[1:]),
                                 batch.dtype)])
        got = np.asarray(jax.device_get(fn(params, batch)))
        out.update(zip(part, got[:len(part)]))
    return out


def run(cell, seed: int, seconds: float, *, ref, prog, span,
        counter, gc_pauses, mark_setup_done, memory_peak) -> dict:
    s, tr, wl = cell.sizes, cell.traffic, cell.params
    slots, pool = wl["slots"], tr["image_pool"]
    params = ref.init_params(seed, s)
    images = ref.request_images(seed, s, pool)
    engine = prog.make_engine(params, s, slots)

    def make_request(rid, image, deadline_s=None):
        return prog.CapsRequest(rid=rid, image=image, deadline_s=deadline_s)

    openloop.warm_up(engine, make_request, images, slots)
    offsets = openloop.arrivals(seed, wl["rate_per_s"], seconds)
    gc.collect()
    mark_setup_done()
    counter.active = gc_pauses.active = True
    with span("bench.window"):
        served = openloop.drive(engine, make_request, offsets, images,
                                tr["deadline_s"], seconds,
                                clock=time.perf_counter, span=span)
    counter.active = gc_pauses.active = False
    peak = memory_peak()
    summary = openloop.summary(served)
    rids = _sample(seed, len(served.requests), tr["check_sample"])
    answers = {int(r): (served.requests[r].status, served.requests[r].lengths,
                        served.requests[r].pred) for r in rids}
    run_info = dict(summary, slots=slots, window_s=seconds,
                    memory_peak_bytes=peak)
    del engine, params, served
    gc.collect()
    want = reference_lengths(ref, s, seed, pool, answers, "highest")
    return {"run": run_info, "readings": checks.compare_serve(answers, want),
            "attempted": summary["due"], "failed": summary["failed"],
            "end_to_end": {"serve_p50_ms": summary["serve_p50_ms"],
                           "serve_p95_ms": summary["serve_p95_ms"]}}


def control(cell, seed: int, seconds: float, *, ref) -> dict:
    """The control's readings: the reference at the next precision down
    in the program's place, on the same sample of requests a run of
    ``seconds`` compares."""
    s, tr, wl = cell.sizes, cell.traffic, cell.params
    n = len(openloop.arrivals(seed, wl["rate_per_s"], seconds))
    rids = _sample(seed, n, tr["check_sample"])
    low = reference_lengths(ref, s, seed, tr["image_pool"], rids, "high")
    answers = {r: ("ok", v, int(np.argmax(v))) for r, v in low.items()}
    want = reference_lengths(ref, s, seed, tr["image_pool"], rids, "highest")
    return checks.compare_serve(answers, want)
