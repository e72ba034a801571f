"""Host clock around scene build + write + parse + compile_scene +
make_integrator in set-up (PLY read, SAH build, treelet pack, upload)."""


def read(ctx):
    return ctx.get("scene_compile_s")
