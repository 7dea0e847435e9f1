"""Milliseconds per executed tick the draw stage spent finding or building
the mode-group's device stores and stack (``_device_group``): the loop's
``stage_seconds["draw.stack"]`` (span ``isla:draw.stack``) over the window,
divided by the ticks in which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("draw.stack")
