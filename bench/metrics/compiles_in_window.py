"""Programs lowered (traced and compiled, or fetched from the persistent
cache) inside the window, from JAX's ``/jax/core/compile`` events. Should
read 0: set-up warms every shape the window uses."""


def read(ctx):
    return ctx.lowered_in_window
