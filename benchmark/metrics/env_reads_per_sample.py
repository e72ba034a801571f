"""Elements of the environment map's 2D distribution read per sample of the
map, over the window's frames: telemetry counter env_table_reads (the two
searches' pivots and steps and the pdf's reads a sample, two an escape for
its MIS pdf: static factors times the counted env_samples and env_escapes,
taken on the host) over env_samples. Of the order of log2 of the map's rows and columns where the
distribution is searched; a whole row where it is gathered. Nothing to read
where the program does not count them (no map, or a program without the
counters)."""


def read(ctx):
    reads = samples = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and c.get("env_samples"):
            reads += c.get("env_table_reads", 0)
            samples += c["env_samples"]
    return reads / samples if samples else None
