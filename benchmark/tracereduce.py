"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to numbers.

Read with `jax.profiler.ProfileData`, nothing else. What is taken:

- device planes: `/device:TPU:<n>` (or any `/device:<KIND>:<n>` that has an
  "XLA Ops" line). Their "XLA Ops" line holds one event per executed HLO
  op, nested: a `while` spans its body's ops. Busy time is the UNION of
  those intervals, so nesting counts once; an op's own time is its duration
  less its children's (self time), and `top_ops` ranks by that.
  A CPU capture has no device plane: there the events that carry an
  `hlo_op` stat on the host's XLA threads are taken as one device, so that
  the rehearsal and the recorded test trace go through the same code. Such
  numbers are never reported as a device's.
- host plane `/host:CPU`: the benchmark's own `TraceAnnotation`s
  (`bench/...`) give the traced window and say whether a gap lies inside a
  frame or between frames; the other host events name what the host thread
  of that annotation was doing. The window is the `bench/frame` span; where
  the trace was stopped before the frame ended (a traffic mix that sets
  `trace_seconds`: a whole frame of a mesh cell is some 3 million device
  events), it runs from the `bench/frame_begin` marker to the last device
  event.
- the "XLA Modules" line of each device plane: one event per executed
  program. A program that lies WHOLLY inside the window and holds a
  collective op is a dispatch; collective time, per dispatch and as a share
  of a dispatch, counts only the collectives inside such programs, so a trace
  cut in the middle of a dispatch adds nothing of it (the wait comes at a
  dispatch's end), and is taken device by device (the wait at a
  collective differs by device: the quickest waits longest).

On the chip XLA names an op by its whole HLO line; `short_name` cuts that to
`name opcode [kind] output-shape`.

All times in seconds.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

SORT_RE = re.compile(r"(^|[^a-z])sort([^a-z]|$)")
COLLECTIVE_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"all_reduce|all_gather|reduce_scatter|collective_permute|all_to_all|psum)"
)
FRAME = "bench/frame"
FRAME_BEGIN = "bench/frame_begin"
_HLO_RE = re.compile(r"^%?([\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])?.*?\s([a-z][a-z0-9\-]*)\(")
_KIND_RE = re.compile(r"kind=(k\w+)")
NS = 1e-9
_SLACK = 1e-6  # an op that ends with its program ends within float rounding of it

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def self_time_events(events: List[Tuple[str, float, float]]) -> List[Tuple[str, float, float, float]]:
    """events: (name, start, end), possibly nested -> (name, start, end,
    self seconds) per event: its duration less its children's."""
    out: List[Tuple[str, float, float, float]] = []
    stack: List[List] = []  # [name, start, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][2] <= upto:
            out.append(tuple(stack.pop()))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        stack.append([name, a, b, b - a])
    close(float("inf"))
    return out


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """-> name -> self seconds, summed over the name's events."""
    out: Dict[str, float] = {}
    for name, _, _, own in self_time_events(events):
        out[name] = out.get(name, 0.0) + own
    return out


def short_name(name: str) -> str:
    """`%fusion.5 = f32[131072,8]{..} fusion(...), kind=kCustom, calls=...`
    -> `fusion.5 fusion kCustom f32[131072,8]`; other names stay."""
    m = _HLO_RE.match(name)
    if not m:
        return name[:120]
    kind = _KIND_RE.search(name)
    parts = [m.group(1), m.group(3)] + ([kind.group(1)] if kind else []) + ([m.group(2).lstrip("(")] if m.group(2) else [])
    return " ".join(parts)[:120]


def op_class(name: str) -> str:
    """By the op's short name (`name opcode ...`), so that a shape or an
    operand in the HLO line cannot class it."""
    low = " ".join(short_name(name).split(" ")[:2]).lower()
    if COLLECTIVE_RE.search(low):
        return "collective"
    if SORT_RE.search(low):
        return "sort"
    return "other"


def _events(line):
    for e in line.events:
        a = float(e.start_ns) * NS
        yield e, a, a + float(e.duration_ns) * NS


def read_planes(path: str) -> dict:
    """-> {"devices": {plane: [(name, start, end, class)]},
           "modules": {plane: [(name, start, end)]},
           "host": [(line, name, start, end)], "on_device": bool}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: list = []
    cpu_ops: list = []
    modules: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [(e.name, a, b) for e, a, b in _events(line)]
                if line.name != "XLA Ops":
                    continue
                cls: Dict[str, str] = {}
                evs = []
                for e, a, b in _events(line):
                    name = e.name
                    if name not in cls:
                        cls[name] = op_class(name)
                    evs.append((name, a, b, cls[name]))
                if evs:
                    devices[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e, a, b in _events(line):
                    if line.name.startswith("tf_XLA") and any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append((e.name, a, b, op_class(e.name)))
                    elif b > a or e.name.startswith("bench/"):
                        host.append((line.name, e.name, a, b))
    on_device = bool(devices)
    if not devices and cpu_ops:
        devices["/host:CPU (no device plane)"] = cpu_ops
    return {"devices": devices, "host": host, "on_device": on_device, "modules": modules}


def _window(host: list, devices: Optional[dict] = None) -> Optional[Tuple[str, float, float]]:
    frames = [(ln, a, b) for ln, name, a, b in host if name == FRAME]
    if frames:
        return max(frames, key=lambda f: f[2] - f[1])
    begins = [(ln, a) for ln, name, a, b in host if name == FRAME_BEGIN]
    ends = [b for evs in (devices or {}).values() for _, _, b, _ in evs]
    if begins and ends and max(ends) > begins[0][1]:
        return begins[0][0], begins[0][1], max(ends)
    return None


def _host_doing(host: list, line: str, t: float) -> str:
    """The innermost event on the annotation's host thread that covers t."""
    best, span = "", float("inf")
    where = "in frame"  # the gaps that are asked about lie inside the window
    for ln, name, a, b in host:
        if ln != line or not (a <= t <= b):
            continue
        if name.startswith("bench/"):
            where = "in frame" if name in (FRAME, FRAME_BEGIN) else "between frames"
        elif b - a < span:
            best, span = name, b - a
    return f"{where}: {best or 'no host event'}"


def reduce_trace(path: str, top: int = 10) -> Optional[dict]:
    """The traced frame's numbers, or None where the trace holds no device
    op or no `bench/frame` annotation (a reader that finds nothing returns
    nothing)."""
    return reduce_planes(read_planes(path), top)


def reduce_planes(planes: dict, top: int = 10) -> Optional[dict]:
    win = _window(planes["host"], planes["devices"])
    if not planes["devices"] or win is None:
        return None
    line, lo, hi = win
    busy, per_dev_busy = [], {}
    sort_s = coll_s = 0.0
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    coll_share, coll_per_dispatch, n_dispatches = [], [], []
    for plane, evs in planes["devices"].items():
        inside = [(n, max(a, lo), min(b, hi), c) for n, a, b, c in evs if b > lo and a < hi]
        u = union([(a, b) for _, a, b, _ in inside])
        b_s = total(u)
        busy.append(b_s)
        per_dev_busy[plane] = b_s
        own_events = self_time_events([(n, a, b) for n, a, b, _ in inside])
        cls = {n: c for n, _, _, c in inside}
        own: Dict[str, float] = {}
        for n, _, _, s in own_events:
            own[n] = own.get(n, 0.0) + s
        for n, s in own.items():
            ops[n] = ops.get(n, 0.0) + s
        sort_s += sum(s for n, s in own.items() if cls[n] == "sort")
        coll = sum(s for n, s in own.items() if cls[n] == "collective")
        coll_s += coll
        # collective self time inside programs that lie wholly in the window
        whole = [(a, b) for _, a, b in planes["modules"].get(plane, []) if lo <= a < b <= hi]
        coll_events = [(ea, eb, s) for n, ea, eb, s in own_events if cls[n] == "collective"]
        per_module = [
            (sum(s for ea, eb, s in coll_events if a - _SLACK <= ea and eb <= b + _SLACK), b - a)
            for a, b in whole
        ]
        per_module = [(c, d) for c, d in per_module if c > 0]
        n_dispatches.append(len(per_module))
        if per_module:
            coll_per_dispatch.append(sum(c for c, _ in per_module) / len(per_module))
            coll_share.append(sum(c for c, _ in per_module) / sum(d for _, d in per_module))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a > 0:
                k = _host_doing(planes["host"], line, 0.5 * (a + b))
                gaps[k] = gaps.get(k, 0.0) + (b - a)
    n = len(busy)
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "on_device": planes["on_device"],
        "n_devices": n,
        "window_s": hi - lo,
        "busy_s": sum(busy) / n,
        "per_device_busy_s": per_dev_busy,
        "sort_s": sort_s / n,
        "collective_s": coll_s / n,
        # collective self time (transfer and the wait for the slowest device)
        # per whole dispatch, and as a share of those dispatches' duration,
        # device by device
        "collective_s_per_dispatch": coll_per_dispatch,
        "collective_share": coll_share,
        "n_dispatches": n_dispatches,
        "whole_frame": any(name == FRAME for _, name, _, _ in planes["host"]),
        "device_ops": [[short_name(k), v / n] for k, v in rank(ops)],
        "idle_gaps": [[k, v / n] for k, v in rank(gaps)],
    }


def describe(path: str, limit: int = 8) -> str:
    """The trace's planes and lines with a few events each, for a first look."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name} ({len(lines)} lines)")
        for line in lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name} ({len(evs)} events)")
            for e in evs[:limit]:
                out.append(
                    f"    {e.name[:80]} start={e.start_ns:.0f} dur={e.duration_ns:.0f} "
                    f"{dict(list(e.stats)[:8])}"
                )
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    if sys.argv[1] == "describe":
        print(describe(sys.argv[2]))
    else:
        print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
