"""Share of the shading vertices at a delta BSDF (smooth glass, mirror), in
%, over the window's frames: telemetry counters delta_vertices over
shading_vertices (valid hits of live lanes). The waves such vertices hold
open carry few lanes. A descriptor of the workload's light transport, not
a target: a correct program does not move it. Nothing to read where the program does not count them
(no delta material, or a program without the counters)."""


def read(ctx):
    delta = shading = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and c.get("shading_vertices"):
            delta += c.get("delta_vertices", 0)
            shading += c["shading_vertices"]
    return 100.0 * delta / shading if shading else None
