"""Share of pool slots that hold a live path when a wave is traced
(stats["mean_wave_occupancy"]), mean over the window's frames."""


def read(ctx):
    o = [f["stats"]["mean_wave_occupancy"] for f in ctx["frames"]
         if f["ok"] and "mean_wave_occupancy" in f["stats"]]
    return 100.0 * sum(o) / len(o) if o else None
