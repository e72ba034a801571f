"""tpu_pbrt — a TPU-native physically based renderer.

A from-scratch reimplementation of the capabilities of pbrt-v3 plus the
distributed master/worker tile renderer of jirenz/pbrt-v3-distributed,
designed TPU-first: scenes are compiled to flat SoA arrays in HBM and
rendered by JAX/XLA wavefront kernels, distributed over a device mesh via
shard_map with collective film merge.

Layer map (cf. SURVEY.md §1; upstream reference paths in module docstrings):
  scene/    — .pbrt front-end: lexer, parser, pbrt* API, ParamSet, factories
  core/     — math: transforms, spectrum, sampling, RNG, filters
  shapes/   — shape plugins tessellated/compiled to triangle SoA
  accel/    — SAH/LBVH build (host) + LinearBVHNode traversal (device)
  integrators/ — direct, path, volpath, bdpt, sppm, whitted, ao, mlt
  parallel/ — mesh/shard_map tile scheduler, film merge, checkpoint/resume
  utils/    — image I/O (EXR/PNG/PFM), stats, progress, logging
"""

__version__ = "0.1.0"

from tpu_pbrt.scene.api import (  # noqa: F401
    pbrt_init,
    pbrt_cleanup,
    parse_file,
    parse_string,
    render_file,
)
