"""Device milliseconds per engine tick spent in operations that are not
Pallas kernels (XLA's patch extraction, relayouts, copies),
from the trace."""

import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.run.get("ticks"):
        return None
    return 1e3 * trace_reduce.xla_seconds(ctx.trace) / ctx.run["ticks"]
