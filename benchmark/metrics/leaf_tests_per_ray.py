"""(ray, treelet) block-slot leaf tests of the stream tracer (telemetry
counter stream_leaf_tests) per ray traced, over the window's frames. Nothing
to read where the program does not count them."""


def read(ctx):
    tests = rays = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "stream_leaf_tests" in c and f.get("rays_traced"):
            tests += c["stream_leaf_tests"]
            rays += f["rays_traced"]
    return tests / rays if rays else None
