"""Stream-tracer loop rounds (EXPANDs + FLUSHes; telemetry counter
stream_rounds) per pool wave (stats["n_waves"]), over the window's frames.
Nothing to read where the program does not count them."""


def read(ctx):
    rounds = waves = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "stream_rounds" in c and f["stats"].get("n_waves"):
            rounds += c["stream_rounds"]
            waves += f["stats"]["n_waves"]
    return rounds / waves if waves else None
