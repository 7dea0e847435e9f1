"""Share of the uploaded value-pane slots that hold a drawn sample: 100 x
the loop's ``samples_drawn`` over its ``pane_slots`` in the window, in
percent (the rest is the padding of the power-of-two panes)."""


def read(ctx):
    slots = ctx.delta("pane_slots")
    if not slots:
        return None
    return 100.0 * ctx.delta("samples_drawn") / slots
