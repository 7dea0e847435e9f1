"""Milliseconds per executed tick the draw stage spent in the block
samplers and the assembly of their columns, one ``isla:draw.sample`` span a
chunk: the loop's ``stage_seconds["draw.sample"]`` over the window, divided
by the ticks in which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("draw.sample")
