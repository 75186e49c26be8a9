"""Process start to the backend answering ``jax.devices()`` (host clock)."""


def read(ctx):
    return ctx["setup"]["backend_init_s"]
