"""Share of the window's answers the admission tier served without
executing them: subsumed by a cached answer or fanned out from a same-tick
duplicate (``IslaAdmissionLoop`` counters), in percent."""


def read(ctx):
    answered = ctx.delta("answered")
    if answered <= 0:
        return None
    return 100.0 * (ctx.delta("subsumed") + ctx.delta("deduped")) / answered
