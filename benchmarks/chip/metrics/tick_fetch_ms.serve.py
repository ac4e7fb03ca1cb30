"""Result fetch per serving tick, in ms: the ``caps.tick.fetch`` records'
total (``jax.device_get``: the wait for the forward and the copy of the
lengths and predictions to the host) over the traced window's
``caps.tick`` records."""

import host_spans


def read(ctx):
    return host_spans.tick_ms(ctx, "fetch")
