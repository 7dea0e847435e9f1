"""Milliseconds the admission loop spent per executed tick outside the
executor's stages: the self time of the loop's ``isla:tick`` span, the
loop's ``stage_seconds["admit"]`` over the window, divided by the ticks in
which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("admit")
