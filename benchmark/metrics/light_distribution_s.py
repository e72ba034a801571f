"""Self seconds of the program's `scene/light_distribution` span: the power
distribution and the per-voxel table of the light-sampling strategy, built
on the host inside `scene/lights`, summed over this process (set-up only).
Nothing to read where the program keeps no such span."""


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    got = spans("scene/light_distribution") if spans else []
    return sum(s.self_seconds for s in got) if got else None
