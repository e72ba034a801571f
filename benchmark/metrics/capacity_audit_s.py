"""The program's own span `render/capacity_audit` (the audit's second
program: built or loaded, then run once; memoised per scene and chunk, so it
occurs in warm-up only), summed by name over this process. Nothing to read
where the program keeps no spans."""


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    got = spans("render/capacity_audit") if spans else []
    return sum(s.seconds for s in got) if got else None
