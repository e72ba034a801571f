"""(ray, node) pairs the stream tracer expanded in its top tree (telemetry
counter stream_pairs_expanded) per ray traced, over the window's frames: what
a deeper top tree costs a ray. Nothing to read where the program does not
count them."""


def read(ctx):
    pairs = rays = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "stream_pairs_expanded" in c and f.get("rays_traced"):
            pairs += c["stream_pairs_expanded"]
            rays += f["rays_traced"]
    return pairs / rays if rays else None
