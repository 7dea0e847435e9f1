"""Milliseconds per executed tick the h2d stage spent in the uploads
themselves (the ``distributed.h2d`` calls; ``isla:h2d.put``): the loop's
``stage_seconds["h2d.put"]`` over the window, divided by the ticks in which
the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("h2d.put")
