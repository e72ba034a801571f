"""The program's own spans `scene/parse` (lexing and directives: the compile
it triggers at WorldEnd is a span of its own and taken out) and
`scene/ply_read`, summed by name over this process. They occur in set-up
only, and the recorder's clock is not ctx["t_start"]'s, so they are selected
by name, not by time. Nothing to read where the program keeps no spans."""

NAMES = ("scene/parse", "scene/ply_read")


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    got = [s for s in spans("scene/") if s.name in NAMES] if spans else []
    return sum(s.self_seconds for s in got) if got else None
