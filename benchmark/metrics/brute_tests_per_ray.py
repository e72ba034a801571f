"""(ray, triangle) pairs the brute tracer tested (telemetry counter
brute_pairs_tested) per ray traced, over the window's frames: the triangle
count of the scene where every ray meets every triangle. Nothing to read
where the program does not count them, or where another tracer did the work."""


def read(ctx):
    pairs = rays = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and c.get("brute_pairs_tested") and f.get("rays_traced"):
            pairs += c["brute_pairs_tested"]
            rays += f["rays_traced"]
    return pairs / rays if rays else None
