"""jaxcost (ISSUE 3 tentpole): the static roofline interpreter, its
anti-pattern detectors (adversarial fixtures), and the budget gate —
including the full update-budgets workflow over a temp file and the
repo-level mirror of the CLI gate against the COMMITTED budgets.json."""

import jax
import jax.numpy as jnp

from tpu_pbrt.analysis import cost


def _findings(fn, args, wave=64, entry="fixture"):
    jx = jax.make_jaxpr(fn)(*args)
    roll, findings = cost.analyze_jaxpr(jx, entry, wave)
    return roll, [f for f in findings if f.waived is None]


# ---------------------------------------------------------------------------
# detector sanity: adversarial fixtures (ISSUE 3 satellite)
# ---------------------------------------------------------------------------


def test_injected_f32_f64_f32_round_trip_flagged():
    """The satellite's named fixture: an f32 -> f64 -> f32 round trip in
    a wave-sized array must produce a JC-CHURN finding."""
    from jax import enable_x64

    def f(x):
        return x.astype(jnp.float64).astype(jnp.float32) * 2.0

    with enable_x64():
        jx = jax.make_jaxpr(f)(jnp.ones((128,), jnp.float32))
    _, findings = cost.analyze_jaxpr(jx, "fixture", 64)
    churn = [f for f in findings if f.rule == "JC-CHURN"]
    assert churn, "f32->f64->f32 round trip not flagged"
    assert "float32->float64->float32" in churn[0].detail


def test_round_trip_through_arithmetic_flagged():
    """The film.add_samples shape: convert, arithmetic against a
    literal, convert back."""

    def f(x):
        i = jnp.ceil(x).astype(jnp.int32)
        return (i + 3).astype(jnp.float32)

    _, findings = _findings(f, (jnp.ones((256,), jnp.float32),))
    assert any(f.rule == "JC-CHURN" for f in findings)


def test_small_round_trip_not_flagged():
    def f(x):
        return x.astype(jnp.int32).astype(jnp.float32)

    _, findings = _findings(
        f, (jnp.ones((cost.CHURN_MIN_ELEMS - 1,), jnp.float32),)
    )
    assert not any(f.rule == "JC-CHURN" for f in findings)


def test_oversized_broadcast_flagged():
    """The satellite's second named fixture: a non-scalar broadcast
    materializing BCAST_MIN_RATIO x its input above BCAST_MIN_BYTES."""

    def f(x):
        return jnp.broadcast_to(x[:, None], (512, 4096)) * 1.5

    _, findings = _findings(f, (jnp.ones((512,), jnp.float32),))
    assert any(f.rule == "JC-BCAST" for f in findings)


def test_scalar_broadcast_not_flagged():
    """Scalar broadcasts fuse for free — never an anti-pattern."""

    def f(x):
        return x + jnp.float32(2.0)

    _, findings = _findings(f, (jnp.ones((512, 4096), jnp.float32),))
    assert not any(f.rule == "JC-BCAST" for f in findings)


def test_large_transpose_flagged_and_small_ignored():
    def big(x):
        return x.T

    _, findings = _findings(big, (jnp.ones((4096, 64), jnp.float32),))
    assert any(f.rule == "JC-RELAYOUT" for f in findings)

    _, findings = _findings(big, (jnp.ones((16, 8), jnp.float32),))
    assert not any(f.rule == "JC-RELAYOUT" for f in findings)


def test_narrow_gather_flagged_unless_sorted():
    """Random narrow gathers past wave width are flagged; the SAME
    gather at sort-derived indices is the sanctioned pattern (the
    stream tracer's whole design) and must pass."""
    tab = jnp.ones((65536,), jnp.float32)
    idx = jnp.zeros((32768,), jnp.int32)

    def unsorted(t, i):
        return t[jnp.clip(i, 0, 65535)]

    _, findings = _findings(unsorted, (tab, idx))
    assert any(f.rule == "JC-GATHER" for f in findings)

    def sorted_(t, i):
        (i_s,) = jax.lax.sort([i], num_keys=1)
        return t[jnp.clip(i_s, 0, 65535)]

    _, findings = _findings(sorted_, (tab, idx))
    assert not any(f.rule == "JC-GATHER" for f in findings)


def test_padding_waste_flagged():
    def f(x):
        return x * 2.0  # (1M, 3): minor dim 3 pads to 128 on TPU tiles

    _, findings = _findings(f, (jnp.ones((1 << 20, 3), jnp.float32),))
    assert any(f.rule == "JC-PAD" for f in findings)


# ---------------------------------------------------------------------------
# rollup model sanity
# ---------------------------------------------------------------------------


def test_dot_flops_model():
    def f(a, b):
        return a @ b

    roll, _ = _findings(
        f,
        (jnp.ones((128, 64), jnp.float32), jnp.ones((64, 32), jnp.float32)),
    )
    assert roll.flops == 2 * 64 * 128 * 32


def test_scan_multiplies_by_trip_count():
    def body(c, _):
        return c + 1.0, None

    def once(x):
        return x + 1.0

    def scanned(x):
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c

    r1, _ = _findings(once, (jnp.ones((256,), jnp.float32),))
    r10, _ = _findings(scanned, (jnp.ones((256,), jnp.float32),))
    assert r10.flops >= 10 * r1.flops


def test_while_body_charged_once():
    """A while body is one wave: the rollup must not multiply it."""

    def loop(x):
        return jax.lax.while_loop(
            lambda c: c[0] < 100, lambda c: (c[0] + 1, c[1] * 2.0), (0, x)
        )[1]

    def once(x):
        return x * 2.0

    r_loop, _ = _findings(loop, (jnp.ones((1024,), jnp.float32),))
    r_once, _ = _findings(once, (jnp.ones((1024,), jnp.float32),))
    assert r_loop.flops < 10 * r_once.flops
    assert r_loop.n_dynamic_loops == 1


def test_fingerprint_stable_and_change_sensitive():
    x = jnp.ones((64,), jnp.float32)
    r1, _ = _findings(lambda v: v * 2.0, (x,))
    r2, _ = _findings(lambda v: v * 2.0, (x,))
    r3, _ = _findings(lambda v: v * 2.0 + 1.0, (x,))
    assert r1.fingerprint == r2.fingerprint
    assert r1.fingerprint != r3.fingerprint


# ---------------------------------------------------------------------------
# budget gate: synthetic regression fails, --update-budgets clears it
# ---------------------------------------------------------------------------


def _toy_entries(scale: int):
    def build():
        x = jnp.ones((1024 * scale,), jnp.float32)
        return jax.make_jaxpr(lambda v: jnp.sum(v * 2.0 + 1.0))(x), 64

    return {"toy": build}


def test_budget_gate_regression_and_update(tmp_path):
    path = tmp_path / "budgets.json"
    # seed the budget from the baseline program
    errors, _, _, _ = cost.run_cost(
        update=True, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors == []
    # clean re-check against the committed file
    errors, warnings, _, _ = cost.run_cost(
        update=False, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors == [], errors
    # synthetic regression: the program got 4x bigger -> gate fails with
    # an entry-point diagnostic
    errors, _, _, _ = cost.run_cost(
        update=False, budgets_path=path, entries=_toy_entries(4)
    )
    assert errors and "toy" in errors[0] and "regressed" in errors[0]
    # --update-budgets clears it
    errors, _, _, _ = cost.run_cost(
        update=True, budgets_path=path, entries=_toy_entries(4)
    )
    assert errors == []
    errors, _, _, _ = cost.run_cost(
        update=False, budgets_path=path, entries=_toy_entries(4)
    )
    assert errors == []


def test_update_preserves_customized_tolerance(tmp_path):
    """--update-budgets refreshes the ROLLUPS only: a tolerance someone
    tightened in the committed file must survive the rewrite."""
    import json

    path = tmp_path / "budgets.json"
    cost.run_cost(update=True, budgets_path=path, entries=_toy_entries(1))
    data = json.loads(path.read_text())
    data["tolerance"] = 0.05
    path.write_text(json.dumps(data))
    cost.run_cost(update=True, budgets_path=path, entries=_toy_entries(2))
    assert json.loads(path.read_text())["tolerance"] == 0.05


def test_budget_gate_missing_entry_is_error(tmp_path):
    path = tmp_path / "budgets.json"
    errors, _, _, _ = cost.run_cost(
        update=False, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors and "no committed budget" in errors[0]


def test_budget_improvement_is_ratchet_warning(tmp_path):
    path = tmp_path / "budgets.json"
    cost.run_cost(update=True, budgets_path=path, entries=_toy_entries(4))
    errors, warnings, _, _ = cost.run_cost(
        update=False, budgets_path=path, entries=_toy_entries(1)
    )
    assert errors == []
    assert any("improved" in w for w in warnings)


def test_fingerprint_drift_is_warning_not_error(tmp_path):
    path = tmp_path / "budgets.json"
    cost.run_cost(update=True, budgets_path=path, entries=_toy_entries(1))

    def build():
        # same cost scale, different op mix -> fingerprint changes while
        # the metrics stay inside tolerance
        x = jnp.ones((1024,), jnp.float32)
        return jax.make_jaxpr(lambda v: jnp.sum((v - 1.0) * 2.0))(x), 64

    errors, warnings, _, _ = cost.run_cost(
        update=False, budgets_path=path, entries={"toy": build}
    )
    assert errors == []
    assert any("fingerprint changed" in w for w in warnings)


# ---------------------------------------------------------------------------
# the repo gate (tier-1 mirror of the CLI acceptance criterion)
# ---------------------------------------------------------------------------


def test_repo_entry_points_clean_against_committed_budgets():
    """ISSUE 3 acceptance: the shipped tree's entry points pass the
    committed budgets.json with zero cost errors and zero un-waived
    findings. A hot-path change that moves bytes/FLOPs past tolerance
    fails here (and in CI) even with no accelerator attached."""
    errors, warnings, rollups, findings = cost.run_cost(update=False)
    assert errors == [], "\n".join(errors)
    active = [f for f in findings if f.waived is None]
    assert active == [], "\n".join(str(f) for f in active)
    # every audited entry point must carry a budget row
    assert set(rollups) == set(cost.default_entry_points())


def test_bench_wave_rollup_shape():
    """The bench.py hook: a production-shaped pool wave traces without
    hardware and reports non-trivial static cost."""
    roll = cost.bench_wave_rollup(res=64, spp=4, chunk=1 << 12)
    assert roll.flops > 0 and roll.hbm_bytes > 0
    assert roll.n_dynamic_loops >= 1  # the drain loop is in the trace
