"""Model FLOP utilisation of training: forward and backward FLOPs per
step (config shapes, no recomputation; see ``work.train_flops``) times
the steps of the traced window, over the window and the chip's bf16
peak, in percent."""

import trace_reduce
import work


def read(ctx):
    if ctx.trace is None or not ctx.run.get("steps"):
        return None
    s, b = ctx.cell.sizes, ctx.run["batch"]
    flops = ctx.run["steps"] * work.train_flops(ctx.ref, s, b)
    return 100.0 * flops / (trace_reduce.window_s(ctx.trace)
                            * ctx.peaks["bf16_flops"])
