"""Elements of the light tables read per light picked, over the window's
frames: telemetry counter light_table_reads (the distribution's table for
the pick and for the MIS pdf of a hit on an emitter, and the light rows:
static factors times lanes, counted where they happen) over light_picks.
Of the order of log2(light rows) plus one packed row where the table is
searched; every row of every column where the dense select runs. Nothing to
read where the program does not count them."""


def read(ctx):
    reads = picks = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and c.get("light_picks"):
            reads += c.get("light_table_reads", 0)
            picks += c["light_picks"]
    return reads / picks if picks else None
