"""Median time a request waits in the serving engine's queue, from
``submit()`` to its admission into a slot at a tick's start, in ms: the
program's ``caps.request.queue`` records of the traced window."""

import host_spans


def read(ctx):
    return host_spans.median_ms(ctx, "caps.request.queue")
