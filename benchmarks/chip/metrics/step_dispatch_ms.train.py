"""Host time to dispatch one training step, in ms: the mean of the
program's ``caps.train.dispatch`` records of the traced window (the
jitted step's call with its donated state, which only enqueues it)."""

import host_spans


def read(ctx):
    return host_spans.mean_ms(ctx, "caps.train.dispatch")
