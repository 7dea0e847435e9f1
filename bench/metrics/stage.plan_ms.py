"""Milliseconds the program's plan stage took per executed tick: the
loop's ``stage_seconds["plan"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("plan")
