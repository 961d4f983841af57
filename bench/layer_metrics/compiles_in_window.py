"""Entry / jit: backend compiles counted inside the measured window
(jax.monitoring); every shape is warmed in set-up, so this reads 0."""


def read(ctx):
    return float(ctx.compiles_in_window)
