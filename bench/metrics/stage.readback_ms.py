"""Milliseconds the program's readback stage took per executed tick: the
loop's ``stage_seconds["readback"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("readback")
