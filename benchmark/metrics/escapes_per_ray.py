"""Rays that left the scene and read the environment map, per ray traced,
over the window's frames: telemetry counter env_escapes (camera and bounce
rays of live lanes that hit nothing) over rays traced (shadow rays
included). A descriptor of the workload's light transport, not a target:
a correct program does not move it. Nothing to read where the program does not count them (no map,
or a program without the counters)."""


def read(ctx):
    escapes = rays = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "env_escapes" in c and f.get("rays_traced"):
            escapes += c["env_escapes"]
            rays += f["rays_traced"]
    return escapes / rays if rays else None
