"""New samples the host drew per executed tick (the loop's
``samples_drawn`` counter over the window)."""


def read(ctx):
    if not ctx.executed_ticks:
        return None
    return ctx.delta("samples_drawn") / ctx.executed_ticks
