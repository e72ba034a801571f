"""Pool waves per frame (stats["n_waves"]), mean over the window's frames."""


def read(ctx):
    w = [f["stats"]["n_waves"] for f in ctx["frames"] if f["ok"] and "n_waves" in f["stats"]]
    return sum(w) / len(w) if w else None
