"""Device milliseconds of the fused tick programs (``fused_tick*`` and
``fused_solve*``, from the trace's program line) per executed tick."""


def read(ctx):
    if ctx.trace is None or not ctx.executed_ticks:
        return None
    tick_s, _ = ctx.trace.module_seconds("fused_tick")
    solve_s, _ = ctx.trace.module_seconds("fused_solve")
    if tick_s + solve_s <= 0:
        return None
    return 1e3 * (tick_s + solve_s) / ctx.executed_ticks
