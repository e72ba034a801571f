"""Seconds per whole frame: the whole window over the frames it completed."""


def read(ctx):
    done = sum(1 for f in ctx["frames"] if f["ok"])
    return ctx["window_s"] / done if done else None
