"""pallascheck (ISSUE 11 tentpole): static VMEM budgets and
grid-semantics verification of the fused Pallas kernels — adversarial
synthetic kernels (an injected parallel-dim accumulator race, a missing
init seed, an out-of-bounds dynamic store, a VMEM-oversized block — each
caught), the cap derivation against the committed defaults, the
vmem_budgets.json gate workflow over a temp file, the repo-level mirror
of the CLI gate, and the mutation tests: deleting `_flush_kernel`'s
`@pl.when(b == 0)` seed or flipping its grid dim to "parallel" must exit
non-zero with a diagnostic naming the entry point."""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import tpu_pbrt.accel.fusedwave as fw
from tpu_pbrt.accel.stream import clear_traverse_caches
from tpu_pbrt.analysis import pallascheck as pc
from tpu_pbrt.config import cfg

# ---------------------------------------------------------------------------
# synthetic kernel fixtures
# ---------------------------------------------------------------------------


def _accum_call(x, *, seed: bool, semantics=("arbitrary",)):
    """A miniature flush-shaped accumulator: constant-index_map output
    revisited across a 4-step grid, optionally seeded on step 0."""

    def kern(x_ref, o_ref):
        b = pl.program_id(0)
        if seed:
            @pl.when(b == 0)
            def _():
                o_ref[...] = jnp.zeros_like(o_ref)

        cur = o_ref[...]
        o_ref[...] = cur + x_ref[...]

    return pl.pallas_call(
        kern,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
        interpret=True,
    )(x)


def _kernels(fn, *args, entry="fixture"):
    jx = jax.make_jaxpr(fn)(*args)
    infos = pc.extract_kernels(jx, entry)
    assert infos, "fixture produced no pallas_call"
    findings = []
    for i in infos:
        findings.extend(pc.check_kernel(i))
    return infos, [f for f in findings if f.waived is None]


X = jnp.ones((4, 128), jnp.float32)


def test_parallel_dim_accumulator_race_flagged():
    """ISSUE 11 satellite: a revisited (constant index_map) output under
    a grid dim declared "parallel" is the megacore race pallascheck
    exists to catch."""
    _, findings = _kernels(
        lambda x: _accum_call(x, seed=True, semantics=("parallel",)), X
    )
    assert any(f.rule == "PC-RACE" for f in findings), findings


def test_sequential_accumulator_clean():
    _, findings = _kernels(
        lambda x: _accum_call(x, seed=True, semantics=("arbitrary",)), X
    )
    assert findings == [], findings


def test_missing_init_seed_flagged():
    """Reading the revisited accumulator with no grid-step-0 seed reads
    uninitialized VMEM on step 0."""
    _, findings = _kernels(lambda x: _accum_call(x, seed=False), X)
    assert any(f.rule == "PC-INIT" for f in findings), findings


def test_seed_survives_sequential_data_dependent_whens():
    """The stage-two megakernel shape: a step-0 seed followed by TWO
    sequential data-dependent @pl.when blocks each reading the
    accumulator must stay clean — the must-join over a cond must not
    clear init state the cond never touched (regression: branch-local
    alias ids leaking into the join)."""

    def call(x):
        def kern(x_ref, o_ref):
            b = pl.program_id(0)

            @pl.when(b == 0)
            def _():
                o_ref[...] = jnp.zeros_like(o_ref)

            @pl.when(x_ref[0, 0] > 0)
            def _():
                o_ref[...] = o_ref[...] + x_ref[...]

            @pl.when(x_ref[0, 1] > 0)
            def _():
                o_ref[...] = o_ref[...] * 2.0

        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            interpret=True,
        )(x)

    _, findings = _kernels(call, X)
    assert findings == [], findings


def test_swap_old_value_before_seed_flagged():
    """A swap's RETURNED old value consumed before the step-0 seed is a
    read of uninitialized VMEM — but the seed itself (a swap whose old
    value is discarded) must stay clean."""

    def call(x):
        def kern(x_ref, o_ref):
            old = jax.ref.swap(
                o_ref, (slice(None), slice(None)), x_ref[...]
            )
            o_ref[...] = old + x_ref[...]

        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            interpret=True,
        )(x)

    _, findings = _kernels(call, X)
    assert any(f.rule == "PC-INIT" for f in findings), findings


def test_oob_dynamic_store_flagged_and_clamped_clean():
    def call(x, clamp: bool):
        def kern(x_ref, o_ref):
            def lane(i, c):
                j = jnp.clip(i * 3, 0, 127) if clamp else i * 3
                o_ref[0, j] = x_ref[0, i]
                return c

            jax.lax.fori_loop(0, 128, lane, 0)

        return pl.pallas_call(
            kern,
            grid=(1,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            interpret=True,
        )(x)

    # i in [0, 127] -> 3*i reaches 381, provably outside the block
    _, findings = _kernels(lambda x: call(x, clamp=False), X[:1])
    oob = [f for f in findings if f.rule == "PC-OOB"]
    assert oob and "dim 1" in oob[0].detail, findings
    _, findings = _kernels(lambda x: call(x, clamp=True), X[:1])
    assert not any(f.rule == "PC-OOB" for f in findings), findings


def test_vmem_oversized_block_flagged():
    """A single block bigger than platform VMEM with headroom must fail
    the capacity check even with no committed budget involved."""
    big = jnp.zeros((2, 8, 1 << 19), jnp.float32)  # 16 MB blocks

    def call(x):
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        return pl.pallas_call(
            kern,
            grid=(2,),
            in_specs=[pl.BlockSpec((1, 8, 1 << 19), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, 8, 1 << 19), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((2, 8, 1 << 19), jnp.float32),
            interpret=True,
        )(x)

    infos, _ = _kernels(call, big)
    errors = pc.check_capacity({i.key: i for i in infos})
    assert errors and "PC-VMEM" in errors[0], errors


def test_double_buffer_charging():
    """Moving blocks are charged x2 (double-buffered), constant-index_map
    blocks once, scratch flat — the model the budget file commits."""

    def call(x):
        def kern(x_ref, c_ref, o_ref, scr):
            scr[...] = x_ref[...] + c_ref[...]
            o_ref[...] = scr[...]

        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[
                pl.BlockSpec((1, 128), lambda i: (i, 0)),  # moving
                pl.BlockSpec((1, 128), lambda i: (0, 0)),  # resident
            ],
            out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((1, 128), jnp.float32)],
            interpret=True,
        )(x, x[:1])

    infos, findings = _kernels(call, X)
    assert findings == [], findings
    (info,) = infos
    blk = 128 * 4
    assert info.vmem_bytes == 2 * blk + blk + 2 * blk + blk


# ---------------------------------------------------------------------------
# cap derivation (the hand-set caps as a checked consequence)
# ---------------------------------------------------------------------------


def test_derive_caps_reproduces_committed_defaults():
    """ISSUE 11 acceptance: --derive-caps reproduces the configured
    fused_max_rays=2^18 / fused_max_nodes=2^14 from the VMEM model (not
    from the constants), and the PC-CAPS check passes."""
    d = pc.derive_caps()
    for p in d["platforms"].values():
        assert p["max_rays"] >= cfg.fused_max_rays
        assert p["max_rays_pow2"] == cfg.fused_max_rays
        assert p["max_nodes"] >= cfg.fused_max_nodes
        assert p["max_nodes_pow2"] == cfg.fused_max_nodes
        # the docstring-era budget math survives as model coefficients:
        # 48 B/ray flush ((8,R) f32 table + two (R,) in + two (R,) out)
        assert p["flush_bytes_per_ray"] == 48
    assert pc.check_caps(d) == []


def test_caps_check_fails_when_cap_exceeds_model(monkeypatch):
    monkeypatch.setattr(cfg, "fused_max_rays", 1 << 22)
    errors = pc.check_caps()
    assert errors and "PC-CAPS" in errors[0] and "MAX_RAYS" in errors[0]


def test_wave_vmem_monotone():
    a = pc.wave_vmem(1 << 12, 256)
    b = pc.wave_vmem(1 << 13, 256)
    assert 0 < a < b


# ---------------------------------------------------------------------------
# the vmem_budgets.json gate workflow (temp file)
# ---------------------------------------------------------------------------


def _toy_entries(scale: int):
    def build():
        x = jnp.ones((4, 128 * scale), jnp.float32)

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def call(v):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[
                    pl.BlockSpec((1, 128 * scale), lambda i: (i, 0))
                ],
                out_specs=pl.BlockSpec((1, 128 * scale), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(
                    (4, 128 * scale), jnp.float32
                ),
                interpret=True,
            )(v)

        return jax.make_jaxpr(call)(x)

    return {"toy": build}


def test_budget_gate_update_workflow(tmp_path):
    path = tmp_path / "vmem_budgets.json"
    errors, _ = pc.run_pallascheck(
        update=False, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors and "no committed VMEM budget" in errors[0]
    errors, _ = pc.run_pallascheck(
        update=True, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors == [], errors
    errors, _ = pc.run_pallascheck(
        update=False, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors == [], errors
    # synthetic regression: blocks 4x bigger -> gate fails
    errors, _ = pc.run_pallascheck(
        update=False, budgets_path=path, entries=_toy_entries(4)
    )
    assert errors and "regressed" in errors[0], errors
    # --update-budgets clears it
    pc.run_pallascheck(
        update=True, budgets_path=path, entries=_toy_entries(4)
    )
    errors, _ = pc.run_pallascheck(
        update=False, budgets_path=path, entries=_toy_entries(4)
    )
    assert errors == [], errors


def test_budget_improvement_is_ratchet_warning(tmp_path):
    path = tmp_path / "vmem_budgets.json"
    pc.run_pallascheck(update=True, budgets_path=path,
                       entries=_toy_entries(4))
    errors, warnings = pc.run_pallascheck(
        update=False, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors == []
    assert any("improved" in w for w in warnings)


# ---------------------------------------------------------------------------
# the repo gate (tier-1 mirror of the CLI acceptance criterion)
# ---------------------------------------------------------------------------


def test_repo_fused_entry_points_clean():
    """ISSUE 11 acceptance: pallascheck runs clean over every fused
    entry point against the committed vmem_budgets.json, including the
    PC-CAPS derivation."""
    errors, _ = pc.run_pallascheck()
    assert errors == [], "\n".join(errors)


def _refresh_fused_caches():
    fw.fused_flush_chunk.clear_cache()
    fw.fused_expand.clear_cache()
    clear_traverse_caches()


@pytest.fixture
def _clean_fused_caches():
    """The mutation tests re-trace the REAL entry points with a mutated
    kernel; the module-level jit caches key on avals only, so they must
    be dropped around the mutation or later tests inline the mutant."""
    _refresh_fused_caches()
    yield
    _refresh_fused_caches()


def _stream_entry():
    from tpu_pbrt.analysis import audit

    return {
        "stream_intersect_fused": lambda: audit.stream_traversal_jaxpr(
            fused=True
        ),
    }


def test_mutation_deleting_flush_seed_is_caught(
    monkeypatch, _clean_fused_caches
):
    """ISSUE 11 acceptance: deleting the @pl.when(b == 0) accumulator
    seed in _flush_kernel exits non-zero with a PC-INIT diagnostic
    naming the entry point."""
    monkeypatch.setattr(fw, "_seed_accumulators", lambda *refs: None)
    _refresh_fused_caches()
    errors, _ = pc.run_pallascheck(
        entries=_stream_entry(), check_caps_too=False
    )
    init = [e for e in errors if "PC-INIT" in e]
    assert init and "stream_intersect_fused" in init[0], errors


def test_mutation_parallel_flush_dim_is_caught(
    monkeypatch, _clean_fused_caches
):
    """... and flipping the flush grid dim to "parallel" exits non-zero
    with a PC-RACE diagnostic naming the entry point."""
    monkeypatch.setattr(fw, "FLUSH_DIM_SEMANTICS", ("parallel",))
    _refresh_fused_caches()
    errors, _ = pc.run_pallascheck(
        entries=_stream_entry(), check_caps_too=False
    )
    race = [e for e in errors if "PC-RACE" in e]
    assert race and "stream_intersect_fused" in race[0], errors


# ---------------------------------------------------------------------------
# CLI plumbing (ISSUE 11 satellite: uniform stage flags, no fail-fast)
# ---------------------------------------------------------------------------


def test_cli_reports_every_failing_stage(monkeypatch):
    """A crashed stage must not stop the suite: every later stage still
    runs and every failing stage is reported before the non-zero exit."""
    import tpu_pbrt.analysis.__main__ as amain

    calls = []

    def fake_cost(update=False):
        calls.append("cost")
        raise RuntimeError("cost stage exploded")

    def fake_shard():
        calls.append("shardcheck")
        return (["SC-UNREDUCED fixture"], [])

    def fake_pallas(update=False):
        calls.append("pallascheck")
        return (["PC-RACE fixture"], [])

    import tpu_pbrt.analysis.cost as cost_mod
    import tpu_pbrt.analysis.pallascheck as pc_mod
    import tpu_pbrt.analysis.shardcheck as shard_mod

    monkeypatch.setattr(cost_mod, "run_cost", fake_cost)
    monkeypatch.setattr(shard_mod, "run_shardcheck", fake_shard)
    monkeypatch.setattr(pc_mod, "run_pallascheck", fake_pallas)
    rc = amain.main(["--no-audit", "--format", "json"])
    assert rc == 1
    assert calls == ["cost", "shardcheck", "pallascheck"]


def test_bench_report_vmem_headroom_column(tmp_path):
    """ISSUE 11 satellite: a post-PR-11 capture's vmem_headroom reaches
    the trajectory table, and pre-PR-11 captures (no field) render as
    absent instead of failing the schema gate."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_report", os.path.join(root, "tools", "bench_report.py")
    )
    br = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(br)

    line = {
        "metric": "m", "value": 1.0, "unit": "Mray/s", "vs_baseline": 0.01,
        "vmem_headroom": 0.42,
    }
    new = tmp_path / "BENCH_r42.json"
    new.write_text(json.dumps({"n": 42, "cmd": "x", "rc": 0, "parsed": line}))
    row = br.load_capture(str(new))
    assert row["vmem_headroom"] == 0.42
    # a pre-PR-11 capture: field absent, still loads
    del line["vmem_headroom"]
    older = tmp_path / "BENCH_r03.json"
    older.write_text(json.dumps({"n": 3, "cmd": "x", "rc": 0, "parsed": line}))
    old = br.load_capture(str(older))
    assert old["vmem_headroom"] is None
    assert ("vmem_headroom", "vmem_headroom") in br.COLUMNS


def test_cli_no_pallascheck_skips(monkeypatch):
    import tpu_pbrt.analysis.__main__ as amain
    import tpu_pbrt.analysis.pallascheck as pc_mod

    def boom(update=False):
        raise AssertionError("pallascheck ran despite --no-pallascheck")

    monkeypatch.setattr(pc_mod, "run_pallascheck", boom)
    rc = amain.main(
        ["--no-audit", "--no-cost", "--no-shardcheck", "--no-pallascheck"]
    )
    assert rc == 0
