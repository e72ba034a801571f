"""Device time by phase, from a `jax.profiler` trace (`.xplane.pb`) alone.

    python -m tpu_pbrt.obs phases FILE.xplane.pb [--json]
    python -m tpu_pbrt.main --profile DIR scene.pbrt    (prints it at exit)

The ONE reduction of the program's own profile (ISSUE 25); the phase
names are `obs/phases.py`'s, the same tuple the program's
`jax.named_scope`s take theirs from.

What is read, and how:

- A `jax.named_scope` ends up in the HLO op's `op_name`, which the
  profiler stores as the `tf_op` stat of the op's `XEventMetadata`
  (beside `source` = file:line, `hlo_category`, `program_id`).
  `jax.profiler.ProfileData` does not surface event metadata, so
  `read_op_metadata` decodes the XSpace wire format itself: of each
  XPlane only `name` (field 2), `event_metadata` (4) and `stat_metadata`
  (5); `lines` (3), which hold every event, are skipped by their length.
- The events come from `ProfileData`, one pass per device line, and are
  joined to the metadata by name (on the chip XLA names an op by its
  whole HLO line). Where two programs hold the same HLO line under
  different `tf_op`, the name is ambiguous: it is attributed to the
  first and listed under `ambiguous`.
- Per device plane, the "XLA Ops" line: an op's SELF time is its
  duration less its children's (a `while` spans its body's ops), and
  goes to the DEEPEST vocabulary scope in its `tf_op`. Ops XLA made
  itself carry no `tf_op` (every `while` and `conditional`, layout
  copies, expanded scatters: 9 % of a killeroo frame): those are placed
  by NESTING (`reduce_device`), and the table says how much of each
  phase came that way. What nothing encloses is `unscoped`, stated,
  never spread. Busy time is the union of the intervals, so the phases
  sum to it.
- A fusion carries ITS ROOT's scope while its body may mix ops of two
  phases: per phase the share of its time that lies in fusions says how
  soft that edge is.
- Host plane: the program's spans (`obs/trace.py` opens a
  `TraceAnnotation` for each) with the part of their own time (children
  taken out) during which the device was idle.

Imports jax only inside `read_events`; all times in seconds.
"""

from __future__ import annotations

import mmap
import re
from bisect import bisect_right
from collections import Counter
from typing import Dict, Iterator, List, Optional, Tuple

from tpu_pbrt.obs import phases as ph

NS = 1e-9
OPS_LINE = "XLA Ops"
#: a span of `obs/trace.py`: `family/name`, no C++ scope operator
_SPAN_RE = re.compile(r"[a-z_]+/[A-Za-z0-9_+/\-]+")
_HLO_RE = re.compile(r"^%?([\w.\-]+) = ")
_JAX_PATH_RE = re.compile(r"^\w+\(.*?\)/")

Interval = Tuple[float, float]


# -- XSpace wire format (protobuf), only what `read_op_metadata` needs ------


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int) -> Iterator[Tuple[int, int, int, int]]:
    """(field, wire type, a, b) for each field of the message in
    buf[lo:hi]: a varint's value is `a`; a length-delimited field's
    payload is buf[a:b]; fixed-width fields give their offsets."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, wire, v, 0
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, wire, i, i + n
            i += n
        elif wire == 1:
            yield field, wire, i, i + 8
            i += 8
        elif wire == 5:
            yield field, wire, i, i + 4
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")


def _text(buf, a: int, b: int) -> str:
    return bytes(buf[a:b]).decode("utf-8", "replace")


def _map_value(buf, a: int, b: int) -> Tuple[int, int]:
    """A map<int64, Message> entry -> the value message's bounds."""
    for field, wire, x, y in _fields(buf, a, b):
        if field == 2 and wire == 2:
            return x, y
    return a, a


def _id_and_name(buf, a: int, b: int) -> Tuple[int, str]:
    """A map entry whose value is an XStatMetadata or an XEventMetadata
    (both: id = field 1, name = field 2) -> (id, name)."""
    mid, name = 0, ""
    for f, w, x, y in _fields(buf, *_map_value(buf, a, b)):
        if f == 1 and w == 0:
            mid = x
        elif f == 2 and w == 2:
            name = _text(buf, x, y)
    return mid, name


#: the XEventMetadata stats kept, by their XStatMetadata name
_KEPT = ("tf_op", "source", "hlo_category", "program_id")


def read_op_metadata(path: str) -> Dict[str, Dict[str, List[dict]]]:
    """-> plane name -> op name -> [{"tf_op", "source", "hlo_category",
    "program_id"}], one entry per XEventMetadata of that name (several
    programs can hold the same HLO line)."""
    out: Dict[str, Dict[str, List[dict]]] = {}
    with open(path, "rb") as fh:
        try:
            buf = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
        except ValueError:  # an empty file cannot be mapped
            return out
        with buf:
            for field, wire, a, b in _fields(buf, 0, len(buf)):
                if field == 1 and wire == 2:  # XSpace.planes
                    name, ops = _plane_metadata(buf, a, b)
                    if ops:
                        out[name] = ops
    return out


def _plane_metadata(buf, lo: int, hi: int) -> Tuple[str, Dict[str, List[dict]]]:
    name = ""
    stat_names: Dict[int, str] = {}
    event_md: List[Interval] = []
    for field, wire, a, b in _fields(buf, lo, hi):
        if wire != 2:
            continue
        if field == 2:
            name = _text(buf, a, b)
        elif field == 4:
            event_md.append(_map_value(buf, a, b))
        elif field == 5:
            sid, sname = _id_and_name(buf, a, b)
            stat_names[sid] = sname
        # field 3 (lines: every event of the plane) is stepped over
    ops: Dict[str, List[dict]] = {}
    for a, b in event_md:
        op_name, stats = "", {}
        for f, w, x, y in _fields(buf, a, b):
            if f == 2 and w == 2:
                op_name = _text(buf, x, y)
            elif f == 5 and w == 2:
                key, val = _stat(buf, x, y, stat_names)
                if key in _KEPT:
                    stats[key] = val
        if op_name and stats:
            ops.setdefault(op_name, []).append(stats)
    return name, ops


def _stat(buf, lo: int, hi: int, stat_names: Dict[int, str]):
    key, val = "", None
    for f, w, x, y in _fields(buf, lo, hi):
        if f == 1 and w == 0:
            key = stat_names.get(x, "")
        elif f == 5 and w == 2:
            val = _text(buf, x, y)
        elif f == 7 and w == 0:  # ref_value: an interned string
            val = stat_names.get(x, "")
        elif f in (3, 4) and w == 0:
            val = x
    return key, val


# -- events ------------------------------------------------------------------


def read_events(path: str):
    """-> (devices, host): devices = plane -> a callable giving a fresh
    iterator of (name, start, end) over the plane's "XLA Ops" line (the
    events are never held in a list: a whole frame is millions); host =
    [(line, name, start, end)] of the host plane's events that look like
    the program's spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []

    def ops(line):
        def events():
            for e in line.events:
                a = float(e.start_ns) * NS
                yield e.name, a, a + float(e.duration_ns) * NS
        return events

    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = ops(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if _SPAN_RE.fullmatch(e.name):
                        a = float(e.start_ns) * NS
                        host.append((line.name, e.name, a, a + float(e.duration_ns) * NS))
    return devices, host


class _Unsorted(Exception):
    """A line's events are not in start order."""


def self_times(events) -> List[Tuple[str, float, float, float]]:
    """events: (name, start, end) sorted by (start, -end) -> (name, start,
    end, self seconds) per event, in closing order (see `_sweep`)."""
    out: List[Tuple[str, float, float, float]] = []
    _sweep(events, lambda name: True, out.append)
    return out


def short_name(name: str) -> str:
    m = _HLO_RE.match(name)
    return m.group(1) if m else name[:80]


# -- the reduction ------------------------------------------------------------


def _sweep(events, has_path, on_close=None):
    """One pass over a line's events in (start, -end) order ->
    (per_op, parent_of, children_of, busy): per_op = name -> [self
    seconds, count]; for names WITHOUT a scope path of their own
    (`has_path(name)` false) the name of the op that encloses them, and
    for such names that enclose others, their children's names; busy =
    the union of the intervals.

    Self time: an event's duration less what later events cover of it;
    each instant of an event is taken from the INNERMOST open event that
    holds that instant, so the self times sum to the busy union even
    where two ops overlap without nesting (an async copy beside a
    fusion: 5 % of a killeroo frame would be counted twice otherwise)."""
    per_op: Dict[str, List[float]] = {}
    parent_of: Dict[str, Optional[str]] = {}
    children_of: Dict[str, set] = {}
    busy: List[Interval] = []
    stack: List[list] = []  # [name, end, self, has a path, start]
    last = float("-inf")

    def close(top) -> None:
        acc = per_op.get(top[0])
        if acc is None:
            per_op[top[0]] = [top[2], 1]
        else:
            acc[0] += top[2]
            acc[1] += 1
        if on_close is not None:
            on_close((top[0], top[4], top[1], top[2]))

    for name, a, b in events:
        if a < last:
            raise _Unsorted
        last = a
        if busy and a <= busy[-1][1]:
            if b > busy[-1][1]:
                busy[-1] = (busy[-1][0], b)
        else:
            busy.append((a, b))
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        pathed = has_path(name)
        if stack:
            top = stack[-1]
            if b <= top[1]:
                top[2] -= b - a
            else:  # it outlasts what it starts in: the rest comes off the next one out
                cover = a
                for held in reversed(stack):
                    if held[1] > cover:
                        upto = min(b, held[1])
                        held[2] -= upto - cover
                        cover = upto
                        if cover >= b:
                            break
            if not top[3]:
                children_of.setdefault(top[0], set()).add(name)
            if not pathed:
                parent_of.setdefault(name, top[0])
        elif not pathed:
            parent_of.setdefault(name, None)
        stack.append([name, b, b - a, pathed, a])
    while stack:
        close(stack.pop())
    return per_op, parent_of, children_of, busy


def _site(path: Tuple[str, ...]) -> Tuple[str, ...]:
    """Where the control-flow op that holds an op of this path stands:
    jax names a loop's body `<site>/while/body/...` and a branch
    `<site>/cond/branch_k_fun/...`, so the path is cut before its LAST
    `while` or `cond`; a path with neither loses its primitive."""
    for i in range(len(path) - 1, -1, -1):
        if path[i] in ("while", "cond"):
            return path[:i]
    return path[:-1]


def reduce_device(events, md: Dict[str, List[dict]], top: int = 3) -> dict:
    """One device's "XLA Ops" events (a callable giving an iterator) ->
    its phase table.

    An op XLA made itself (a `while`, a `conditional`, a layout copy, an
    expanded scatter) has no `tf_op`. It is placed BY NESTING, never by
    share: an op that encloses others stands where most of them say
    their loop or branch stands (`_site`: the pool's `while` encloses
    `.../pool/loop/while/body/...`, so it is `pool/loop`); an op that
    encloses none takes the path of the op that encloses it.
    `nested_seconds` says how much of a phase came that way (a loop's
    own time is the gaps between its body's ops: softer than an op's);
    what nothing encloses stays `unscoped`."""
    paths: Dict[str, Optional[Tuple[str, ...]]] = {}

    def own_path(name: str) -> Optional[Tuple[str, ...]]:
        if name not in paths:
            tf_op = (md.get(name) or [{}])[0].get("tf_op") or ""
            # jax's paths start at the program: `jit(chunk_fn)/...`; a bare
            # `gather:` is a name XLA gave an op of its own, not a path
            paths[name] = tuple(tf_op.rstrip(":").split("/")) if _JAX_PATH_RE.match(tf_op) else None
        return paths[name]

    def has_path(name: str) -> bool:
        return own_path(name) is not None

    try:
        per_op, parent_of, children_of, busy = _sweep(events(), has_path)
    except _Unsorted:  # the profiler writes start order; sort a line that is not
        evs = sorted(events(), key=lambda e: (e[1], -e[2]))
        per_op, parent_of, children_of, busy = _sweep(iter(evs), has_path)

    placed: Dict[str, Optional[Tuple[str, ...]]] = {}

    def from_children(name: str, seen=()) -> Optional[Tuple[str, ...]]:
        if own_path(name) is not None:
            return own_path(name)
        if name in placed:
            return placed[name]
        kids = [from_children(k, seen + (name,)) for k in children_of.get(name, ()) if k not in seen]
        sites = Counter(_site(k) for k in kids if k is not None)
        # ties go to the shorter path, so the vote does not hang on set order
        placed[name] = min(sites, key=lambda k: (-sites[k], len(k), k)) if sites else None
        return placed[name]

    def place(name: str) -> Optional[Tuple[str, ...]]:
        path = from_children(name)
        hops = 0
        while path is None and parent_of.get(name) is not None and hops < 64:
            name = parent_of[name]
            path = from_children(name)
            hops += 1
        return path

    phases: Dict[str, dict] = {}
    ambiguous: Dict[str, float] = {}
    for name, (own, count) in per_op.items():
        variants = md.get(name) or [{}]
        if len({v.get("tf_op") for v in variants}) > 1:
            ambiguous[short_name(name)] = own
        info = variants[0]
        path = own_path(name)
        nested = path is None
        if nested:
            path = place(name)
        phase = ph.deepest("/".join(path)) if path else ph.UNSCOPED
        row = phases.setdefault(phase, {
            "seconds": 0.0, "events": 0, "ops": 0, "fusion_seconds": 0.0,
            "nested_seconds": 0.0, "top": []})
        row["seconds"] += own
        row["events"] += count
        row["ops"] += 1
        if nested and phase != ph.UNSCOPED:
            row["nested_seconds"] += own
        if "fusion" in (info.get("hlo_category") or "") or " fusion(" in name:
            row["fusion_seconds"] += own
        row["top"].append((own, short_name(name), info.get("source") or ""))
    busy_s = sum(b - a for a, b in busy)
    for row in phases.values():
        row["top"] = [[n, s, src] for s, n, src in sorted(row["top"], reverse=True)[:top]]
        row["share"] = row["seconds"] / busy_s if busy_s else 0.0
    return {
        "busy_s": busy_s,
        "span_s": (busy[-1][1] - busy[0][0]) if busy else 0.0,
        "events": int(sum(c for _, c in per_op.values())),
        "phases": phases,
        "ambiguous": ambiguous,
        "_busy": busy,
    }


def _self_segments(spans: List[Tuple[str, float, float]]) -> Iterator[Tuple[str, float, float]]:
    """Nested (name, start, end) spans of one thread -> disjoint (name,
    a, b) pieces: each instant goes to the innermost span that holds it."""
    stack: List[list] = []  # [name, resume-from, end]
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            if top[2] > top[1]:
                yield top[0], top[1], top[2]
            if stack:
                stack[-1][1] = max(stack[-1][1], top[2])
        if stack and a > stack[-1][1]:
            yield stack[-1][0], stack[-1][1], a
        stack.append([name, a, b])
    while stack:
        top = stack.pop()
        if top[2] > top[1]:
            yield top[0], top[1], top[2]
        if stack:
            stack[-1][1] = max(stack[-1][1], top[2])


def _overlap(a: float, b: float, starts: List[float], gaps: List[Interval]) -> float:
    out = 0.0
    i = max(bisect_right(starts, a) - 1, 0)
    while i < len(gaps) and gaps[i][0] < b:
        out += max(0.0, min(b, gaps[i][1]) - max(a, gaps[i][0]))
        i += 1
    return out


def reduce_host(host, busy_by_device: Dict[str, List[Interval]]) -> List[dict]:
    """The program's host spans, by name: count, seconds, own seconds
    (children taken out) and the part of the own seconds during which a
    device was idle, mean over the devices. Idle = outside the device's
    busy union, from the first span's start to the last span's end."""
    by_line: Dict[str, list] = {}
    for line, name, a, b in host:
        by_line.setdefault(line, []).append((name, a, b))
    rows: Dict[str, dict] = {}
    for _, name, a, b in host:
        row = rows.setdefault(name, {"name": name, "count": 0, "seconds": 0.0,
                                     "self_seconds": 0.0, "device_idle_s": 0.0})
        row["count"] += 1
        row["seconds"] += b - a
    lo = min((a for _, _, a, _ in host), default=0.0)
    hi = max((b for _, _, _, b in host), default=0.0)
    gaps_by_device = []
    for busy in busy_by_device.values():
        # before the first op and after the last, as far as the spans reach
        edges = [(lo, lo)] + busy + [(hi, hi)]
        gaps = [(x[1], y[0]) for x, y in zip(edges, edges[1:]) if y[0] > x[1]]
        gaps_by_device.append(([g[0] for g in gaps], gaps))
    n_dev = max(len(gaps_by_device), 1)
    for spans in by_line.values():
        for name, a, b in _self_segments(spans):
            rows[name]["self_seconds"] += b - a
            for starts, gaps in gaps_by_device:
                rows[name]["device_idle_s"] += _overlap(a, b, starts, gaps) / n_dev
    return sorted(rows.values(), key=lambda r: -r["device_idle_s"])


def reduce_xplane(path: str, top: int = 3) -> Optional[dict]:
    """The whole reduction, or None where the trace holds no device op."""
    metadata = read_op_metadata(path)
    devices, host = read_events(path)
    per_device = {
        plane: reduce_device(evs, metadata.get(plane, {}), top)
        for plane, evs in devices.items()
    }
    per_device = {k: v for k, v in per_device.items() if v["events"]}
    if not per_device:
        return None
    busy = {plane: d.pop("_busy") for plane, d in per_device.items()}
    n = len(per_device)
    mean: Dict[str, dict] = {}
    for d in per_device.values():
        for phase, row in d["phases"].items():
            m = mean.setdefault(phase, {"seconds": 0.0, "fusion_seconds": 0.0,
                                        "nested_seconds": 0.0, "events": 0})
            for k in ("seconds", "fusion_seconds", "nested_seconds"):
                m[k] += row[k] / n
            m["events"] += row["events"]
    busy_s = sum(d["busy_s"] for d in per_device.values()) / n
    for row in mean.values():
        row["share"] = row["seconds"] / busy_s if busy_s else 0.0
    families: Dict[str, float] = {}
    for phase, row in mean.items():
        families[ph.family(phase)] = families.get(ph.family(phase), 0.0) + row["seconds"]
    idle_s = sum(d["span_s"] - d["busy_s"] for d in per_device.values()) / n
    ambiguous: Dict[str, float] = {}
    for d in per_device.values():
        for k, v in d["ambiguous"].items():
            ambiguous[k] = ambiguous.get(k, 0.0) + v / n
    return {
        "n_devices": n,
        "busy_s": busy_s,
        "idle_s": idle_s,
        "unscoped_share": mean.get(ph.UNSCOPED, {}).get("seconds", 0.0) / busy_s if busy_s else 0.0,
        "phases": mean,
        "families": families,
        "ambiguous": ambiguous,
        "devices": per_device,
        "host_spans": reduce_host(host, busy),
    }


# -- the table ----------------------------------------------------------------


def _phase_order(phases) -> List[str]:
    known = [p for p in ph.PHASES if p in phases]
    return known + sorted(p for p in phases if p not in known)


def format_table(red: dict) -> str:
    """The reduction as the text `python -m tpu_pbrt.obs phases` prints."""
    out = [
        f"{red['n_devices']} device(s): busy {red['busy_s']:.6f} s, idle "
        f"{red['idle_s']:.6f} s between first and last op (mean over devices)",
        f"unscoped: {100 * red['unscoped_share']:.2f} % of busy time",
        "",
        f"{'phase':<20}{'seconds':>12}{'share %':>9}{'in fusions %':>14}{'by nesting %':>14}{'events':>10}",
    ]
    for phase in _phase_order(red["phases"]):
        row = red["phases"][phase]
        fus = 100 * row["fusion_seconds"] / row["seconds"] if row["seconds"] else 0.0
        nest = 100 * row["nested_seconds"] / row["seconds"] if row["seconds"] else 0.0
        out.append(f"{phase:<20}{row['seconds']:>12.6f}{100 * row['share']:>9.2f}"
                   f"{fus:>14.1f}{nest:>14.1f}{row['events']:>10}")
    out += ["", "by family:"]
    for fam, s in sorted(red["families"].items(), key=lambda kv: -kv[1]):
        share = 100 * s / red["busy_s"] if red["busy_s"] else 0.0
        out.append(f"  {fam:<18}{s:>12.6f}{share:>9.2f}")
    for plane, d in red["devices"].items():
        out += ["", f"{plane}: busy {d['busy_s']:.6f} s of {d['span_s']:.6f} s, "
                    f"{d['events']} events; the largest ops of each phase:"]
        for phase in _phase_order(d["phases"]):
            row = d["phases"][phase]
            out.append(f"  {phase:<18}{row['seconds']:>12.6f} s")
            for name, s, src in row["top"]:
                out.append(f"      {s:>11.6f}  {name}  {src}")
    if red["ambiguous"]:
        out += ["", "ambiguous (one HLO line, several tf_op across programs; attributed to the first):"]
        out += [f"  {s:>11.6f}  {name}" for name, s in sorted(red["ambiguous"].items(), key=lambda kv: -kv[1])[:10]]
    if red["host_spans"]:
        out += ["", f"{'host span':<34}{'count':>7}{'seconds':>12}{'own s':>12}{'device idle s':>15}"]
        for r in red["host_spans"]:
            out.append(f"{r['name']:<34}{r['count']:>7}{r['seconds']:>12.6f}"
                       f"{r['self_seconds']:>12.6f}{r['device_idle_s']:>15.6f}")
    return "\n".join(out)


def newest_xplane(trace_dir: str) -> Optional[str]:
    """The `.xplane.pb` the profiler wrote last under `trace_dir`."""
    import glob
    import os

    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None
