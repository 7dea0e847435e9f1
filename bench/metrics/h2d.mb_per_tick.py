"""Megabytes (1e6 bytes) the executor uploaded per executed tick: the
loop's ``h2d_bytes`` counter (every array handed to ``distributed.h2d``)
over the window, divided by the ticks in which the executor computed at
least one answer."""


def read(ctx):
    nbytes = ctx.delta("h2d_bytes")
    if not nbytes or not ctx.executed_ticks:
        return None
    return nbytes / 1e6 / ctx.executed_ticks
