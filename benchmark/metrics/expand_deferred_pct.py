"""Share of the stream tracer's expanded (ray, node) pairs that EXPAND put
back on the stack, because more of their children were hit than its sort
keeps rows: telemetry counters stream_pairs_deferred over
stream_pairs_expanded, over the window's frames. A pair put back pays a
second take, fetch and test when it is popped again. Nothing to read where
the program does not count them."""


def read(ctx):
    back = pairs = 0
    for f in ctx["frames"]:
        c = ((f.get("stats") or {}).get("telemetry") or {}).get("counters") or {}
        if f["ok"] and "stream_pairs_deferred" in c and c.get("stream_pairs_expanded"):
            back += c["stream_pairs_deferred"]
            pairs += c["stream_pairs_expanded"]
    return 100.0 * back / pairs if pairs else None
