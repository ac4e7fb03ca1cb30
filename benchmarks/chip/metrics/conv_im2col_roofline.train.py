"""Share of its roofline of the convolution matmul kernels
(``kernels/conv_im2col.py``, traced as ``matmul_bias_act.N`` and
``matmul_at_b.N``) in training, in percent: the least time the chip
could take for the convolution work those kernels do in every
configuration (the larger of FLOPs at the bf16 peak and bytes at the HBM
peak) over the kernels' device time in the traced window.

Counted per step: Conv1's forward and weight gradient, and PrimaryCaps'
weight and input gradients.  PrimaryCaps' forward is not counted: it
runs inside ``primary_routing``.  Where the backward replays it in these
kernels, that time is in the measured time but not in the work, so the
share reads low there, never high.  Bytes: each convolution's input,
weights (and bias) and output, once each."""

import peaks
import trace_reduce
import work

PATTERNS = ("matmul_bias_act", "matmul_at_b")


def layer_work(ref, s: dict, b: int) -> tuple[float, float]:
    cs = work.conv_sizes(ref, s)
    hw, c0 = s["image_hw"], s["in_channels"]
    o1, k1, _, c1 = cs["conv1"]
    o2, k2, _, c2 = cs["pc"]
    x0, y1 = b * hw * hw * c0, b * o1 * o1 * c1
    w1, y2, w2 = k1 * k1 * c0 * c1, b * o2 * o2 * c2, k2 * k2 * c1 * c2
    flops = 2 * work.conv_flops(b, *cs["conv1"]) \
        + 2 * work.conv_flops(b, *cs["pc"])
    moved = work.F32 * ((x0 + w1 + c1 + y1)      # Conv1 forward
                        + (x0 + y1 + w1)         # Conv1 dW
                        + (y1 + y2 + w2)         # PrimaryCaps dW
                        + (y2 + w2 + y1))        # PrimaryCaps dX
    return flops, moved


def read(ctx):
    if ctx.trace is None or not ctx.run.get("steps"):
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace, PATTERNS)
    if sec is None:
        return None
    flops, moved = layer_work(ctx.ref, ctx.cell.sizes, ctx.run["batch"])
    n = ctx.run["steps"]
    return peaks.roofline_share(n * flops, n * moved, sec, ctx.peaks)[0]
