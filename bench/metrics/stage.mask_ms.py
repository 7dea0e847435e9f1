"""Milliseconds per executed tick the draw stage spent on each key's zone
mask and GROUP BY codes (``isla:draw.mask``): the loop's
``stage_seconds["draw.mask"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("draw.mask")
