"""Seconds jax spent LOWERING programs before the window: jaxpr to the
StableHLO module that keys the persistent cache (`COMPILES.lower_seconds` in
the snapshot at the window's start), paid warm or cold. Nothing to read
where the program's tracker keeps no such total."""


def read(ctx):
    return ctx["compiles_before"].get("lower_seconds")
