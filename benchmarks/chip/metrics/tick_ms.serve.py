"""Mean duration of one serving engine tick (``CapsuleEngine.step``), on
the host clock: the benchmark's span around each ``step()`` call in the
window."""


def read(ctx):
    return ctx.run.get("tick_mean_ms")
