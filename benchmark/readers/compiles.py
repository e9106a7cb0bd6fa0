"""Backend compile requests JAX reported inside the measured window
(jax.monitoring); warm-up is meant to leave none."""


def read(ctx, params: dict):
    return len(ctx.compiles_in_window)
