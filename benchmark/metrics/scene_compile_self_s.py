"""Self seconds of the program's `scene/compile` span: what `compile_scene`
and `make_integrator` spend under no child span (`scene/shapes`,
`accel/sah_build`, `scene/upload` and the rest taken out), summed over this
process. Nothing to read where the program keeps no spans."""


def read(ctx):
    from tpu_pbrt.obs.trace import TRACE

    spans = getattr(TRACE, "spans", None)
    got = [s for s in spans("scene/compile") if s.name == "scene/compile"] if spans else []
    return sum(s.self_seconds for s in got) if got else None
