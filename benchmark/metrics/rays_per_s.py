"""All rays traced in the window (every frame, all chips together) over all
the time of the window."""


def read(ctx):
    rays = sum(f.get("rays_traced", 0) for f in ctx["frames"])
    return rays / ctx["window_s"] if rays else None
