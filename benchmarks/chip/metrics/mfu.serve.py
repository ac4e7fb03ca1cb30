"""Model FLOP utilisation of serving: the forward FLOPs of the requests
served in the traced window (config shapes, no reconstruction), over the
window and the chip's bf16 peak, in percent.  Padding rows of a part-full
slot batch are not counted: they serve no request."""

import trace_reduce
import work


def read(ctx):
    if ctx.trace is None or not ctx.run.get("ok"):
        return None
    flops = ctx.run["ok"] * work.serve_flops(ctx.ref, ctx.cell.sizes, 1)
    return 100.0 * flops / (trace_reduce.window_s(ctx.trace)
                            * ctx.peaks["bf16_flops"])
