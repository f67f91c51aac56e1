"""XLA backend compiles (and persistent-cache loads) counted inside the
measured window by a ``jax.monitoring`` listener. Set-up warms every
shape the window uses, so a sound run reads 0."""


def read(ctx):
    return ctx["compiles_in_window"]
