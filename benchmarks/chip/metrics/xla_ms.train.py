"""Device milliseconds per training step spent in operations that are not
Pallas kernels (XLA's patch extraction, relayouts, copies, the AdamW update),
from the trace."""

import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.run.get("steps"):
        return None
    return 1e3 * trace_reduce.xla_seconds(ctx.trace) / ctx.run["steps"]
