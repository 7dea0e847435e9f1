"""Milliseconds per executed tick the plan stage spent on the zone verdicts
and zone-bounded selectivity of conjunctive WHEREs the zone map had not
judged (``isla:plan.zone``): the loop's ``stage_seconds["plan.zone"]`` over
the window, divided by the ticks in which the executor computed at least
one answer."""


def read(ctx):
    return ctx.stage_ms("plan.zone")
