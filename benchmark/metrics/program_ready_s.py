"""Seconds jax spent making programs ready before the window (built by XLA
or loaded from the persistent cache), as COMPILES counts them."""


def read(ctx):
    return ctx.get("compile_seconds_setup")
