"""Milliseconds the program's draw stage took per executed tick: the
loop's ``stage_seconds["draw"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("draw")
