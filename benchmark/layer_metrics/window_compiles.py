"""Programs that reached the backend compiler inside the window (counted
from jax.monitoring). Expected 0; the count is reported as it is."""


def read(ctx):
    return ctx["compiles_in_window"]
