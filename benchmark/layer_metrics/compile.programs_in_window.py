"""Compile layer: programs that reached the backend compiler (compiled, or
loaded from the persistent cache) inside the window, as jax.monitoring
reports them. Expected 0: every shape is warmed in set-up."""


def read(ctx):
    return ctx["programs_in_window"]
