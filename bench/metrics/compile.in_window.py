"""Backend compiles (``jax.monitoring``) inside the measured window and its
drain.  Set-up warms every shape up, so this should read 0."""


def read(ctx):
    return float(ctx.compiles_in_window)
