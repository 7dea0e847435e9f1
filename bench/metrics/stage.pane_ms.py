"""Milliseconds per executed tick the h2d stage spent building the dense
panes (value, pad, GROUP BY, mask and hash panes; ``isla:h2d.pane``): the
loop's ``stage_seconds["h2d.pane"]`` over the window, divided by the ticks
in which the executor computed at least one answer."""


def read(ctx):
    return ctx.stage_ms("h2d.pane")
