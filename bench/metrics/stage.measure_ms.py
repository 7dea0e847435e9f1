"""Milliseconds per executed tick the draw stage spent evaluating a derived
measure over the drawn rows (``isla:draw.measure``): the loop's
``stage_seconds["draw.measure"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("draw.measure")
