"""Rays traced per frame (camera, bounce and shadow rays), mean over frames."""


def read(ctx):
    r = [f["rays_traced"] for f in ctx["frames"] if f["ok"]]
    return sum(r) / len(r) if r else None
