"""Command-line entry point.

Capability match for pbrt-v3 src/main/pbrt.cpp: flag parsing into Options
(--nthreads, --outfile, --quick, --quiet, --cropwindow, ...) plus the
TPU-specific runtime tier (--mesh for the device mesh shape, --spp-chunk
for sample chunking) per SURVEY.md §5.6's two-tier config system.
"""

from __future__ import annotations

import argparse
import json
import sys

from tpu_pbrt.scene.api import Options, render_file
from tpu_pbrt.utils.error import PbrtError


def run_summary(scene: str, result) -> dict:
    """What one render ran on and what it cost, as `tpu_pbrt.main` prints
    it (one JSON line per scene unless --quiet): the device as jax
    reports it, which BVH builder ran, compile and render seconds, and
    whether anything was compiled or re-dispatched after the first
    chunk."""
    from tpu_pbrt.obs.compiles import process_report

    stats = result.stats
    return {
        "scene": scene,
        **process_report(),
        "completed_fraction": result.completed_fraction,
        "rays_traced": result.rays_traced,
        "render_seconds": round(result.seconds, 3),
        "phase_seconds": stats.get("phase_seconds"),
        "programs_after_first_chunk": stats.get("programs_after_first_chunk"),
        "redispatches": (stats.get("recovery") or {}).get("redispatches", 0),
        "wave_spread": (stats.get("telemetry") or {}).get("wave_spread"),
        "ray_spread": (stats.get("telemetry") or {}).get("ray_spread"),
    }


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpu-pbrt",
        description="TPU-native physically based renderer (pbrt-v3 scene compatible)",
    )
    p.add_argument("scenes", nargs="*", help=".pbrt scene file(s) to render")
    p.add_argument(
        "--serve",
        action="store_true",
        help="run as a persistent render service: scenes given on the "
        "command line are submitted as initial jobs, then a stdin/JSONL "
        "daemon accepts submit/poll/preempt/cancel ops (protocol: "
        "python -m tpu_pbrt.serve --help, README 'Render service')",
    )
    p.add_argument("--outfile", "-o", default="", help="output image filename (overrides scene Film)")
    p.add_argument("--quick", action="store_true", help="reduce samples/resolution for a fast preview")
    p.add_argument("--quiet", action="store_true", help="suppress progress/warning messages")
    p.add_argument("--verbose", "-v", action="store_true", help="verbose logging")
    p.add_argument(
        "--cropwindow",
        nargs=4,
        type=float,
        metavar=("X0", "X1", "Y0", "Y1"),
        help="render only this fraction of the image",
    )
    p.add_argument("--nthreads", type=int, default=0, help="host threads for scene compile (0 = all)")
    p.add_argument(
        "--mesh", default="",
        help="device mesh shape, e.g. '8' or '2,4': shard the render over "
        "that many devices (default: one device; more devices than jax "
        "sees is an error)",
    )
    p.add_argument("--spp-chunk", type=int, default=0, help="samples per render chunk (0 = auto)")
    p.add_argument("--checkpoint", default="", help="checkpoint file: resume from it if present, write to it while rendering")
    p.add_argument("--checkpoint-every", type=int, default=16, help="chunks between checkpoint writes")
    p.add_argument(
        "--multihost",
        action="store_true",
        help="initialize jax.distributed (multi-host pod rendering over DCN; "
        "also auto-enabled by JAX_COORDINATOR_ADDRESS)",
    )
    p.add_argument(
        "--trace",
        default="",
        metavar="OUT.json",
        help="export a Chrome-trace/Perfetto span timeline of the render "
        "phases (also settable via TPU_PBRT_TRACE_PATH); view at "
        "ui.perfetto.dev",
    )
    p.add_argument(
        "--profile",
        default="",
        metavar="DIR",
        help="run the renders under jax.profiler, write the trace into DIR "
        "and print device time by phase at exit (the table of `python -m "
        "tpu_pbrt.obs phases DIR/.../*.xplane.pb`)",
    )
    p.add_argument(
        "--metrics-path",
        default="",
        metavar="OUT.prom",
        help="write a Prometheus text snapshot of the host metrics "
        "registry (phase-time histograms etc.) on exit; also settable "
        "via TPU_PBRT_METRICS_PATH (TPU_PBRT_METRICS=0 disables)",
    )
    p.add_argument(
        "--faults",
        default="",
        metavar="PLAN",
        help="chaos fault-injection plan (tpu_pbrt.chaos grammar, e.g. "
        "'dispatch:poison@chunk=3,ckpt:torn@write=2'); also settable via "
        "TPU_PBRT_FAULTS — see `python -m tpu_pbrt.chaos --list`",
    )
    return p


def _print_phases(trace_dir: str) -> None:
    """Stop the profiler and print device time by phase (stderr: stdout
    carries one JSON line per scene)."""
    import jax

    from tpu_pbrt.obs import devtrace

    jax.profiler.stop_trace()
    path = devtrace.newest_xplane(trace_dir)
    red = devtrace.reduce_xplane(path) if path else None
    if red is None:
        print(f"tpu-pbrt: no device op in the profile under {trace_dir}",
              file=sys.stderr)
        return
    print(f"tpu-pbrt: {path}\n{devtrace.format_table(red)}", file=sys.stderr)


def main(argv=None) -> int:
    args = build_arg_parser().parse_args(argv)
    if not args.scenes and not args.serve:
        print("tpu-pbrt: no scene files (and no --serve)", file=sys.stderr)
        return 1
    opts = Options(
        n_threads=args.nthreads,
        quick_render=args.quick,
        quiet=args.quiet,
        verbose=args.verbose,
        image_file=args.outfile,
        crop_window=tuple(args.cropwindow) if args.cropwindow else None,
        mesh_shape=tuple(int(x) for x in args.mesh.split(",")) if args.mesh else None,
        spp_chunk=args.spp_chunk,
        checkpoint_path=args.checkpoint,
        checkpoint_every=args.checkpoint_every,
        multihost=args.multihost,
    )
    from tpu_pbrt.config import place_compile_cache
    from tpu_pbrt.obs.compiles import COMPILES
    from tpu_pbrt.obs.metrics import METRICS
    from tpu_pbrt.obs.trace import TRACE
    from tpu_pbrt.parallel.mesh import maybe_init_distributed

    place_compile_cache()
    COMPILES.install()

    # chaos BEFORE the telemetry arm-up: a fault plan that targets the
    # very first dispatch (or the trace exporter itself) must already be
    # installed when instrumentation comes online — and both before
    # jax.distributed, whose init is a dispatch-bearing phase
    if args.faults:
        from tpu_pbrt.chaos import CHAOS

        CHAOS.install(args.faults)
    if args.trace:
        TRACE.configure(args.trace)
    if args.metrics_path:
        METRICS.configure(args.metrics_path)
    maybe_init_distributed(opts)
    if args.serve:
        from tpu_pbrt.parallel.mesh import resolve_mesh
        from tpu_pbrt.serve import RenderService
        from tpu_pbrt.serve.__main__ import run_daemon

        try:
            mesh = resolve_mesh(opts.mesh_shape)
        except PbrtError as e:
            print(f"tpu-pbrt: {e}", file=sys.stderr)
            return 1
        service = RenderService(mesh=mesh, quiet=args.quiet)
        for i, scene in enumerate(args.scenes):
            # one --checkpoint path cannot be shared by several jobs
            # (interleaved writes would clobber each other and the
            # fingerprint guard would fail the second resume): key it
            # per scene when more than one is submitted
            ckpt = args.checkpoint
            if ckpt and len(args.scenes) > 1:
                ckpt = f"{ckpt}.{i}"
            job = service.submit(
                scene, options=opts,
                checkpoint_path=ckpt,
                checkpoint_every=args.checkpoint_every,
                outfile=args.outfile,
            )
            if not args.quiet:
                print(f"tpu-pbrt: submitted {scene} as {job}", file=sys.stderr)
        try:
            return run_daemon(service)
        finally:
            TRACE.maybe_export()
            METRICS.maybe_export()
    from tpu_pbrt.integrators.common import ChunkCompileError

    if args.profile:
        import jax

        # the persistent cache keys a program without its metadata, so a
        # cached program keeps the scope names it was BUILT with: a
        # profile has to come from programs built with today's names
        # (PERF.md, PR 25). Only here: with metadata in the key every
        # line moved in a traced file would rebuild every program.
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        prof = jax.profiler.ProfileOptions()
        prof.python_tracer_level = 0  # the program's spans, not every frame
        jax.profiler.start_trace(args.profile, profiler_options=prof)
    try:
        for scene in args.scenes:
            try:
                with TRACE.span("main/render_file", scene=scene):
                    result = render_file(scene, opts)
            except (PbrtError, ChunkCompileError) as e:
                print(f"tpu-pbrt: {e}", file=sys.stderr)
                return 1
            if result is not None and not args.quiet:
                print(json.dumps(run_summary(scene, result)))
        return 0
    finally:
        # render() exports incrementally; this export catches the outer
        # main/render_file spans — and runs on the FAILURE path too,
        # where the trace matters most
        TRACE.maybe_export()
        METRICS.maybe_export()
        if args.profile:
            _print_phases(args.profile)


if __name__ == "__main__":
    sys.exit(main())
