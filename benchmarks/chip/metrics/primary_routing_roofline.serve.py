"""Share of its roofline of the PrimaryCaps -> routing kernel
(``kernels/primary_routing.py``; its Pallas call is traced as
``primary_caps_routing.N``) in
serving, in percent: the least time the chip could take for the layer's
mathematics (the larger of FLOPs at the bf16 peak and bytes at the HBM
peak) over the kernel's device time in the traced window.

The work is that of the requests served: the PrimaryCaps convolution of
each one's Conv1 activation, then the first routing layer's votes and
routing, with its activation in and its capsules out.  The weights
(PrimaryCaps' and the routing layer's) are read once per call, one call
an engine tick.  Rows of a part-full slot batch that serve no request
are not work: the kernel's time on them is in the measured time only."""

import peaks
import trace_reduce
import work

PATTERNS = ("primary_caps_routing",)


def layer_work(ref, s: dict, b: int, calls: int = 1) -> tuple[float, float]:
    """FLOPs and bytes of ``calls`` calls that serve ``b`` images in all."""
    cs = work.conv_sizes(ref, s)
    _, k, cin, cout = cs["pc"]
    c1 = cs["conv1"][0]
    first = ref.routing_stack(s)[0]
    i, j, d, c = (first["in_caps"], first["num_caps"], first["caps_dim"],
                  first["in_dim"])
    flops = work.conv_flops(b, *cs["pc"]) + work.routing_flops(b, first)
    moved = work.F32 * (b * (c1 * c1 * cin + j * d)
                        + calls * (k * k * cin * cout + cout + i * j * d * c))
    return flops, moved


def read(ctx):
    if ctx.trace is None or not ctx.run.get("ticks") or not ctx.run.get("ok"):
        return None
    sec = trace_reduce.kernel_seconds(ctx.trace, PATTERNS)
    if sec is None:
        return None
    flops, moved = layer_work(ctx.ref, ctx.cell.sizes, ctx.run["ok"],
                              ctx.run["ticks"])
    return peaks.roofline_share(flops, moved, sec, ctx.peaks)[0]
