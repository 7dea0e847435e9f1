"""Milliseconds the program's compose stage took per executed tick: the
loop's ``stage_seconds["compose"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("compose")
