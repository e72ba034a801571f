"""The trace reduction on small recorded traces (tests/data, recorded by
tests/make_trace.py: three calls of one program inside `bench/frame`, 20 ms
of host sleep after each) gives known numbers: the busy union, the idle
share, the classing of ops and the attribution of gaps."""

import glob
import json
import os

import pytest

import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


def test_union_and_self_time_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == [(0, 3), (5, 6)]
    assert tr.total([(0, 3), (5, 6)]) == pytest.approx(4)
    # a while of 10 s that holds a sort of 4 s and a fusion of 3 s
    events = [("while", 0, 10), ("sort.1", 1, 5), ("fusion", 5, 8), ("copy", 11, 12)]
    assert tr.self_times(events) == pytest.approx({"while": 3, "sort.1": 4, "fusion": 3, "copy": 1})
    assert sorted(tr.self_time_events(events)) == [
        ("copy", 11, 12, 1), ("fusion", 5, 8, 3), ("sort.1", 1, 5, 4), ("while", 0, 10, 3)]


@pytest.mark.parametrize("name,cls", [
    ("sort.66", "sort"), ("%sort.3 = sort(...)", "sort"), ("fusion.12", "other"),
    ("all-reduce.1", "collective"), ("all-reduce-start.2", "collective"),
    ("all-gather.7", "collective"), ("collective-permute-done", "collective"),
    ("resort_fusion", "other"), ("assorted", "other"), ("psum.4", "collective"),
])
def test_op_classing(name, cls):
    assert tr.op_class(name) == cls


def _raster_busy(events, lo, hi, n=200_000):
    """Busy time by brute force: sample n instants of the window."""
    import numpy as np

    t = lo + (np.arange(n) + 0.5) * (hi - lo) / n
    busy = np.zeros(n, bool)
    for _, a, b, _ in events:
        busy |= (t >= a) & (t < b)
    return busy.mean() * (hi - lo)


@pytest.mark.parametrize("path", TRACES, ids=[os.path.basename(p) for p in TRACES])
def test_recorded_trace(path):
    red = tr.reduce_trace(path)
    planes = tr.read_planes(path)
    _, lo, hi = tr._window(planes["host"])
    assert red["window_s"] == pytest.approx(hi - lo)
    # three sleeps of 20 ms lie inside the frame: at least 60 ms are idle
    idle = red["window_s"] - red["busy_s"]
    assert 0.06 <= idle < red["window_s"]
    # busy union against a brute-force count, device by device
    for plane, evs in planes["devices"].items():
        want = _raster_busy(evs, lo, hi)
        assert red["per_device_busy_s"][plane] == pytest.approx(want, rel=2e-3, abs=2e-5)
    # the gaps add up to the idle time and are all inside the frame
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert all(k.startswith("in frame") for k, _ in red["idle_gaps"])
    # the program holds a sort, and the reduction says so
    assert red["sort_s"] > 0
    assert any(tr.op_class(k) == "sort" for k, _ in red["device_ops"])
    # self times never add up to more than the devices were busy
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] * 1.000001 + 1e-9 or not red["on_device"]
    if red["n_devices"] > 1:
        # three calls, each a whole program with one collective, on every device
        assert red["n_dispatches"] == [3] * red["n_devices"]
        assert all(0 < share < 1 for share in red["collective_share"])
        # per dispatch: the collectives' own time over the three, device by device
        for plane, got in zip(planes["devices"], red["collective_s_per_dispatch"]):
            coll = [b - a for _, a, b, c in planes["devices"][plane] if c == "collective"]
            assert len(coll) == 3 and got == pytest.approx(sum(coll) / 3, rel=1e-9)
    else:
        assert red["collective_s_per_dispatch"] == [] and red["collective_share"] == []
    # the numbers as they were when the trace was recorded
    with open(os.path.join(DATA, "expected.json")) as fh:
        want = json.load(fh)[os.path.basename(path)]
    for key, value in want.items():
        assert red[key] == pytest.approx(value, rel=1e-9), key


def test_collectives_of_a_cut_dispatch_are_not_counted():
    # two devices, dispatches of 0.8 s back to back, the trace stopped 1.2 s
    # into the frame: the second dispatch is cut. Device 0 waits 0.3 s at
    # its all-reduce, device 1 (the slowest) 0.1 s; the cut dispatch has
    # already spent 0.3 s in one on device 0.
    def device(wait):
        return [("while.1", 0.0, 0.8, "other"), ("all-reduce.6", 0.8 - wait, 0.8, "collective"),
                ("while.1", 0.8, 1.2, "other")]

    planes = {
        "devices": {"/device:TPU:0": device(0.3) + [("all-reduce.6", 0.9, 1.2, "collective")],
                    "/device:TPU:1": device(0.1)},
        # a program that had not ended when the profiler stopped has no
        # "XLA Modules" event; its finished ops are on the "XLA Ops" line
        "modules": {"/device:TPU:0": [("jit_chunk", 0.0, 0.8)],
                    "/device:TPU:1": [("jit_chunk", 0.0, 0.8)]},
        "host": [("main", tr.FRAME_BEGIN, 0.0, 0.0)], "on_device": True,
    }
    red = tr.reduce_planes(planes)
    assert red["window_s"] == pytest.approx(1.2) and not red["whole_frame"]
    assert red["n_dispatches"] == [1, 1]
    assert red["collective_s_per_dispatch"] == pytest.approx([0.3, 0.1])
    # the share is of the whole dispatch, not of the traced 1.2 s
    assert red["collective_share"] == pytest.approx([0.3 / 0.8, 0.1 / 0.8])
    assert red["collective_s"] == pytest.approx((0.6 + 0.1) / 2)


def test_nothing_to_read_returns_nothing(tmp_path):
    # a trace without a bench/frame annotation: the reader returns None,
    # and the harness leaves the trace metrics out of the line
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert tr.reduce_trace(found[0]) is None
