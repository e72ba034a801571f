"""Lights picked per ray traced, over the window's frames: telemetry counter
light_picks (one a live lane at a vertex that may scatter) over rays traced.
It moves only if the paths' lengths or the share of rays that are shadow
rays do. Nothing to read where the program does not count them."""


def read(ctx):
    picks = rays = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "light_picks" in c and f.get("rays_traced"):
            picks += c["light_picks"]
            rays += f["rays_traced"]
    return picks / rays if rays else None
