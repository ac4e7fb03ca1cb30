"""95th percentile of the traced window's request latencies, due time
to result on the host, in ms: the serving tail.  A host stall inside
the runtime's transfers (0.1-3 s, in some runs) sets it, so it is read
here beside the steady median rather than held to a bound."""


def read(ctx):
    return ctx.run.get("serve_p95_ms")
