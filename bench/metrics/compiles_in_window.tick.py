"""Programs built inside the measured window: backend compiles plus
loads from the persistent compilation cache (``jax.monitoring`` events).
Set-up warms every shape the window uses, so this reads 0."""


def read(ctx):
    return ctx.compiles_in_window
