"""Share of its roofline of the PrimaryCaps -> routing kernel and its
routing backward in training, in percent: the least time the chip could
take for their mathematics (the larger of FLOPs at the bf16 peak and
bytes at the HBM peak) over the kernels' device time in the traced
window.

Both Pallas calls are traced under the name of the jit that makes them
(``kernels/primary_routing.py``): the forward as
``jvp_jit_primary_caps_routing__.N`` and the routing backward as
``transpose_jvp_jit_primary_caps_routing___.N``, each with ``_nota``
after ``routing`` where the none-of-the-above category is on.  The
backward's other kernels (the producer's replay, the conv gradients)
are ``matmul_*`` calls of their own and are not counted here.

Counted per step, from config shapes: the forward as
``primary_routing_roofline.serve`` counts one call of the step's batch;
the routing backward as twice the forward routing's FLOPs
(``work.train_flops``' convention), with the capsules, the routing
weights and the cotangent read and their gradients written once each.
The backward replays the forward routing; that time is in the measured
time but not in the work, so the share reads low, never high."""

import peaks
import spec
import trace_reduce
import work

PATTERNS = ("primary_caps_routing",)


def layer_work(ref, s: dict, b: int) -> tuple[float, float]:
    """FLOPs and bytes of one training step of batch ``b``."""
    fwd_flops, fwd_moved = spec.metric_reader(
        "primary_routing_roofline.serve").layer_work(ref, s, b, 1)
    first = ref.routing_stack(s)[0]
    i, j, d, c = (first["in_caps"], first["num_caps"], first["caps_dim"],
                  first["in_dim"])
    bwd_flops = 2 * work.routing_flops(b, first)
    bwd_moved = work.F32 * (2 * b * i * c + 2 * i * j * d * c + b * j * d)
    return fwd_flops + bwd_flops, fwd_moved + bwd_moved


def read(ctx):
    if ctx.trace is None or not ctx.run.get("steps"):
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace, PATTERNS)
    if sec is None:
        return None
    flops, moved = layer_work(ctx.ref, ctx.cell.sizes, ctx.run["batch"])
    n = ctx.run["steps"]
    return peaks.roofline_share(n * flops, n * moved, sec, ctx.peaks)[0]
