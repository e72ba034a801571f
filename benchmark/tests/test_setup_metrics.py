"""The six readers of set-up (ISSUE 35) on every cell's configuration at its
`rehearsal` preset, here on the CPU: each returns a number after the cell's
own set-up, `setup_unattributed_s` and the union of spans it subtracts add up
to `setup_s`, and a whole `--trace 1` run carries all six in its metrics.

Run by hand, like its siblings: python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import time

import pytest

import run as harness

NEW = ("program_trace_s", "program_lower_s", "programs_built_in_setup", "warmup_device_s",
       "scene_compile_self_s", "setup_unattributed_s")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def union_by_depth(spans, offset, t0, t1):
    """Seconds of [t0, t1] under at least one span, by counting how many are
    open at each endpoint: another algorithm than the reader's."""
    ends = []
    for s in spans:
        a, b = max(s.start + offset, t0), min(s.start + offset + s.seconds, t1)
        if b > a:
            ends += [(a, 1), (b, -1)]
    covered, depth, since = 0.0, 0, t0
    for t, step in sorted(ends):
        if depth > 0:
            covered += t - since
        depth, since = depth + step, t
    return covered


@pytest.mark.parametrize("cell", CELLS)
def test_every_reader_reads_a_number_after_the_cells_set_up(cell):
    from tpu_pbrt.obs.compiles import COMPILES
    from tpu_pbrt.obs.trace import TRACE

    ctx, driver, _ = harness.make_ctx(BENCH, cell, 35, 0.0, True, "rehearsal")
    ctx["t_start"] = time.monotonic()  # this cell's set-up alone, not the session's
    driver.setup(ctx)
    ctx["compiles_before"] = COMPILES.snapshot()
    got = {name: harness.load_module("metrics", name).read(ctx) for name in NEW}
    assert all(isinstance(v, (int, float)) for v in got.values()), got
    assert got["program_trace_s"] > 0 and got["program_lower_s"] > 0 and got["warmup_device_s"] > 0
    assert 0 <= got["scene_compile_self_s"] < 1.0
    assert 0 <= got["setup_unattributed_s"] < ctx["setup_s"]
    # the cells listed for each metric are these five
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == CELLS and m["moves"] == "setup_s" and m["better"] == "lower"
    offset = time.monotonic() - TRACE.now()
    spans = [s for s in TRACE.spans() if s.name != "render/slice"]
    union = union_by_depth(spans, offset, ctx["t_start"], ctx["t_start"] + ctx["setup_s"])
    assert got["setup_unattributed_s"] + union == pytest.approx(ctx["setup_s"], abs=1e-3)
    driver.release(ctx)


def test_a_traced_run_carries_all_six():
    code, result = harness.run_cell(
        ["--workload", "killeroo-frames-1chip", "--seed", "35", "--seconds", "0",
         "--trace", "1", "--preset", "rehearsal"]
    )
    assert code == 3  # a preset run never prints a result
    assert set(NEW) <= set(result["metrics"]), sorted(result["metrics"])
    before = result["notes"]["compiles_before"]
    assert {"trace_seconds", "lower_seconds", "retrieval_seconds", "cache_misses", "by_program"} <= set(before)


def test_a_program_without_the_new_fields_reads_nothing(monkeypatch):
    """What the parent commit gives these files: a snapshot without the
    stage totals, a recorder without `now`. No reader raises."""
    from tpu_pbrt.obs import trace

    class Old:
        def spans(self, prefix=""):
            return []

    monkeypatch.setattr(trace, "TRACE", Old())
    ctx = {"t_start": 0.0, "setup_s": 1.0, "compiles_before": {"programs": 3, "cache_misses": 0}}
    got = {name: harness.load_module("metrics", name).read(ctx) for name in NEW}
    assert got == {"program_trace_s": None, "program_lower_s": None, "programs_built_in_setup": 0,
                   "warmup_device_s": None, "scene_compile_self_s": None, "setup_unattributed_s": None}
