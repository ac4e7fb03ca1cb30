"""The serving engine's own Python per tick, in ms: each ``caps.tick``
record's duration less its ``upload``, ``dispatch`` and ``fetch``
children (admission, the index build, the result loop), averaged over
the traced window's ticks."""

import host_spans


def read(ctx):
    return host_spans.tick_ms(ctx, "engine")
