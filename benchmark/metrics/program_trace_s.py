"""Seconds jax spent TRACING programs before the window: Python running the
jitted functions into jaxprs, the outermost trace of each program only
(`COMPILES.trace_seconds` in the snapshot at the window's start). The
persistent cache is keyed by the lowered module, so a warm set-up pays this
in full. Nothing to read where the program's tracker keeps no such total."""


def read(ctx):
    return ctx["compiles_before"].get("trace_seconds")
