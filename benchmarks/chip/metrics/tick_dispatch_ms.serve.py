"""Forward dispatch per serving tick, in ms: the ``caps.tick.dispatch``
records' total (the jitted forward's call, which only enqueues it) over
the traced window's ``caps.tick`` records."""

import host_spans


def read(ctx):
    return host_spans.tick_ms(ctx, "dispatch")
