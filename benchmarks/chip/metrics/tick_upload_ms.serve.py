"""Upload per serving tick, in ms: the ``caps.tick.upload`` records'
total (the dirty slots' host-to-device copy and the scatter's launch)
over the traced window's ``caps.tick`` records."""

import host_spans


def read(ctx):
    return host_spans.tick_ms(ctx, "upload")
