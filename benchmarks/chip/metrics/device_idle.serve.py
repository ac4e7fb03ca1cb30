"""Share of the traced window in which the device ran no operation:
1 - (union of the device operations' intervals / window), in percent."""

import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(ctx.trace)
