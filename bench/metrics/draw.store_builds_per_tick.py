"""Device moment stores built per executed tick: the loop's
``store_builds`` counter (one for each new key's ``DeviceMomentStore``)
over the window, divided by the ticks in which the executor computed at
least one answer."""


def read(ctx):
    builds = ctx.delta("store_builds")
    if builds is None or not ctx.executed_ticks:
        return None
    return builds / ctx.executed_ticks
