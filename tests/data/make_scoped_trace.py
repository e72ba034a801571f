#!/usr/bin/env python3
"""Record the small scoped TPU trace that tests/test_phases.py pins
`obs/devtrace.py` on. Run by hand on the machine with the chip:

    python3 tests/data/make_scoped_trace.py OUT_DIR

It renders the benchmark configuration's `rehearsal` preset (killeroo-class
at 32x32, 2 spp, a 2,308-triangle mesh: stream tracer, pool wavefront) once
to build the programs, then once more under `jax.profiler`, with the pool
as wide as the frame's work so that the drain is a few waves, and writes

    OUT_DIR/scoped_tpu_1dev.xplane.pb.gz     what the test reads (it gunzips it)
    OUT_DIR/scoped_tpu_1dev.raw.xplane.pb    as the profiler wrote it (3.9 MB)

The first is the second with everything the reduction never reads taken
out, gzipped, so that it stays under 200 KB: every plane but the first
device's and the host's; of the device plane every line but "XLA Ops", and
of its events their stats; of the host plane every event that is not one
of the program's spans; of each op's metadata every stat but `tf_op`,
`source`, `hlo_category`, `program_id`. No event of the "XLA Ops" line is
dropped and no name or time is altered. (Ungzipped it is about 0.5 MB: a
single flush of the stream tracer is some 2,400 device events, and the
names of 560 HLO ops, whole HLO lines, are 140 KB by themselves.)

The compile cache is pointed at a fresh directory: jax keeps op metadata
out of the cache key, so a program cached before a scope was renamed comes
back with the OLD names in its profile (PERF.md, PR 25).
"""

from __future__ import annotations

import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from tpu_pbrt.obs import devtrace as dt  # noqa: E402


def _put_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(field: int, wire: int, payload: bytes) -> bytes:
    head = _put_varint((field << 3) | wire)
    return head + (_put_varint(len(payload)) if wire == 2 else b"") + payload


def _raw(buf, field, wire, a, b) -> bytes:
    """The field as it stands in buf, re-encoded."""
    if wire == 0:
        return _field(field, 0, _put_varint(a))
    return _field(field, wire, bytes(buf[a:b]))


def _strip_event_metadata(buf, a, b, stat_names) -> bytes:
    """map entry <id, XEventMetadata>: drop the stats devtrace never reads."""
    out = b""
    for f, w, x, y in dt._fields(buf, a, b):
        if not (f == 2 and w == 2):
            out += _raw(buf, f, w, x, y)
            continue
        md = b""
        for f2, w2, x2, y2 in dt._fields(buf, x, y):
            if f2 == 5 and w2 == 2 and dt._stat(buf, x2, y2, stat_names)[0] not in dt._KEPT:
                continue
            md += _raw(buf, f2, w2, x2, y2)
        out += _field(2, 2, md)
    return out


def _line_name(buf, a, b) -> str:
    for f, w, x, y in dt._fields(buf, a, b):
        if f == 2 and w == 2:
            return dt._text(buf, x, y)
    return ""


def _strip_line(buf, a, b, keep_ids=None) -> bytes:
    """XLine: its events without their stats (XEvent field 4); with
    `keep_ids`, only the events whose metadata id is among them."""
    out = b""
    for f, w, x, y in dt._fields(buf, a, b):
        if f == 4 and w == 2:  # XLine.events
            ev = list(dt._fields(buf, x, y))
            if keep_ids is not None and not any(
                    f2 == 1 and w2 == 0 and v in keep_ids for f2, w2, v, _ in ev):
                continue
            out += _field(4, 2, b"".join(
                _raw(buf, f2, w2, p, q) for f2, w2, p, q in ev if f2 != 4))
        else:
            out += _raw(buf, f, w, x, y)
    return out


def strip(src: str, dst: str) -> None:
    buf = open(src, "rb").read()
    out = b""
    seen_device = False
    for f, w, a, b in dt._fields(buf, 0, len(buf)):
        if not (f == 1 and w == 2):
            continue
        fields = list(dt._fields(buf, a, b))
        name = next(dt._text(buf, x, y) for f2, w2, x, y in fields if f2 == 2 and w2 == 2)
        device = name.startswith("/device:TPU:")
        if not (name == "/host:CPU" or (device and not seen_device)):
            continue
        seen_device = seen_device or device
        stat_names, span_ids = {}, set()
        for f2, w2, x, y in fields:
            if f2 == 5 and w2 == 2:
                sid, sname = dt._id_and_name(buf, x, y)
                stat_names[sid] = sname
            elif f2 == 4 and w2 == 2 and not device:
                mid, mname = dt._id_and_name(buf, x, y)
                if dt._SPAN_RE.fullmatch(mname):
                    span_ids.add(mid)
        plane = b""
        for f2, w2, x, y in fields:
            if f2 == 3 and w2 == 2:  # lines
                if device and _line_name(buf, x, y) != dt.OPS_LINE:
                    continue
                plane += _field(3, 2, _strip_line(buf, x, y, None if device else span_ids))
            elif f2 == 4 and w2 == 2:
                if not device and dt._id_and_name(buf, x, y)[0] not in span_ids:
                    continue
                plane += _field(4, 2, _strip_event_metadata(buf, x, y, stat_names))
            elif f2 == 6 and w2 == 2:  # the plane's own stats: not read
                continue
            else:
                plane += _raw(buf, f2, w2, x, y)
        out += _field(1, 2, plane)
    with gzip.GzipFile(dst, "wb", compresslevel=9, mtime=0) as fh:
        fh.write(out)


def record(out_dir: str) -> str:
    """Render the rehearsal preset twice, the second time profiled."""
    os.environ.setdefault("TPU_PBRT_POOL", "2048")  # the frame's 2,048 paths: a few waves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="fresh_cache_", dir=out_dir)
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as harness

    import jax

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    ctx, driver, _ = harness.make_ctx(bench, "killeroo-frames-1chip", 2147483659, 0.0, False, "rehearsal")
    driver.setup(ctx)
    scene, integ = ctx["_scene"], ctx["_integ"]
    integ.render(scene)
    tmp = os.path.join(out_dir, "_profile")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        r = integ.render(scene)
    finally:
        jax.profiler.stop_trace()
    print("waves", r.stats.get("n_waves"), "rays", r.rays_traced, "device", jax.devices()[0].device_kind)
    raw = os.path.join(out_dir, "scoped_tpu_1dev.raw.xplane.pb")
    shutil.copy(dt.newest_xplane(tmp), raw)
    shutil.rmtree(tmp, ignore_errors=True)
    shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)
    return raw


def main(argv) -> int:
    out_dir = argv[1]
    os.makedirs(out_dir, exist_ok=True)
    raw = argv[2] if len(argv) > 2 else record(out_dir)  # argv[2]: strip a recorded file again
    dst = os.path.join(out_dir, "scoped_tpu_1dev.xplane.pb.gz")
    strip(raw, dst)
    print(raw, os.path.getsize(raw), "->", dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
