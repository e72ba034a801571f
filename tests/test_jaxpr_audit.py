"""jaxlint layer 2 (jaxpr/compile-time audit): the TPU hot-path
invariants asserted over the REAL render entry points (ISSUE 2
acceptance): no f64 in the path-integrator wave, film/pool donation
materialized as input->output aliasing in the executable, zero retraces
across two same-shape waves, and a clean smoke render under
jax.transfer_guard("disallow").

The golden-invariant matrix also covers volpath (homogeneous-medium
scene), bdpt and both SPPM passes — as of this PR all of them are clean,
so there are no xfail rows; a future violation fails loudly here and
must either be fixed or explicitly xfailed with a ROADMAP entry."""

import jax
import jax.numpy as jnp
import pytest

from tpu_pbrt.analysis import audit


# ---------------------------------------------------------------------------
# detector sanity: the checkers can actually see what they claim to
# ---------------------------------------------------------------------------


def test_find_f64_detects_wide_types():
    from jax import enable_x64

    with enable_x64():
        jx = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0
        )(jnp.ones((4,), jnp.float32))
    assert audit.find_f64(jx), "f64 jaxpr not detected"


def test_find_f64_clean_on_f32():
    jx = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4,), jnp.float32))
    assert audit.find_f64(jx) == []


def test_find_callbacks_detects_debug_print():
    def f(x):
        jax.debug.print("x={}", x)
        return x + 1

    jx = jax.make_jaxpr(f)(jnp.float32(1.0))
    assert audit.find_callbacks(jx), "debug callback not detected"


def test_callbacks_seen_inside_while_loop():
    def f(x):
        def body(c):
            jax.debug.print("c={}", c)
            return c - 1

        return jax.lax.while_loop(lambda c: c > 0, body, x)

    jx = jax.make_jaxpr(f)(jnp.int32(3))
    assert audit.find_callbacks(jx), "callback inside sub-jaxpr missed"


# ---------------------------------------------------------------------------
# golden jaxpr invariants over the real entry points
# ---------------------------------------------------------------------------


def _assert_clean(name, jx):
    f64 = audit.find_f64(jx)
    assert not f64, f"{name}: f64 leaked into the jaxpr: {f64[:5]}"
    cbs = audit.find_callbacks(jx)
    assert not cbs, f"{name}: callback primitives in the wave: {cbs}"


def test_path_wave_jaxpr_invariants():
    """ISSUE 2 acceptance: no f64 anywhere in the path-integrator wave."""
    _assert_clean("path.li", audit.integrator_li_jaxpr("path"))


def test_pool_drain_jaxpr_invariants():
    _assert_clean("pool_chunk", audit.pool_chunk_jaxpr())


def _eqns_with_scope(jaxpr, prefix=""):
    """Every equation of `jaxpr` and its sub-jaxprs with its whole scope
    path (a sub-jaxpr's name stack is relative to the equation that
    holds it)."""
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn, path
        for v in eqn.params.values():
            for sub in audit._sub_jaxprs(v):
                yield from _eqns_with_scope(sub, path)


def test_pool_drain_moves_no_lane():
    """ISSUE 26: a free slot finds its work item where it lies. Outside
    `_bounce_wave` the drain's body holds ONE pool-width sort (the
    deposit's) and gathers only the deposit's `[:seg]` window: a per-wave
    permutation of the lane arrays (a second sort, a take of a lane
    array by a pool-width index) cannot come back unnoticed."""
    pool = 256  # wide enough for the segmented deposit (seg = pool // 4)
    jx = audit.pool_chunk_jaxpr(n_work=1024, pool=pool)
    own = [(e, p) for e, p in _eqns_with_scope(jx.jaxpr)
           if "pool/loop" in p and "pool/bounce" not in p]
    sorts = [p for e, p in own if e.primitive.name == "sort"
             and e.invars[0].aval.shape[0] == pool]
    assert len(sorts) == 1 and "pool/deposit" in sorts[0], sorts
    takes = [(p, e.invars[1].aval.shape) for e, p in own
             if e.primitive.name == "gather"]
    assert takes, "the segmented deposit gathers its window"
    for p, idx_shape in takes:
        assert "pool/deposit" in p and idx_shape[0] == pool // 4, (p, idx_shape)
    ranks = [p for e, p in own if e.primitive.name == "cumsum"]
    assert len(ranks) == 1 and "pool/regen" in ranks[0], ranks


def test_stream_traversal_jaxpr_invariants():
    _assert_clean("stream_intersect", audit.stream_traversal_jaxpr())


def test_expand_sorts_the_packed_rows():
    """ISSUE 36: EXPAND sorts what it found, not what it tested. Its
    branch of the traversal's loop holds ONE sort, and the sort's two
    operands are `_PACK_ROWS` candidates a pair long: the 8-a-pair sort
    over every tested child cannot come back unnoticed."""
    from tpu_pbrt.accel.stream import _PACK_ROWS, _sizes

    jx = audit.stream_traversal_jaxpr()
    slab = _sizes(128)[0]  # the wave the audit traces
    sorts = [e for e, p in _eqns_with_scope(jx.jaxpr)
             if e.primitive.name == "sort" and "stream/expand" in p]
    assert len(sorts) == 1, sorts
    assert [v.aval.shape for v in sorts[0].invars] == [(_PACK_ROWS * slab,)] * 2


def test_film_deposit_jaxpr_invariants():
    _assert_clean("film.add_samples", audit.film_deposit_jaxpr())
    _assert_clean(
        "film.add_samples_pixel", audit.film_deposit_jaxpr(pixel_path=True)
    )


def test_mesh_step_jaxpr_invariants():
    _assert_clean("sharded_pool_renderer", audit.mesh_step_jaxpr())


def test_volpath_jaxpr_invariants():
    _assert_clean(
        "volpath.li", audit.integrator_li_jaxpr("volpath", "media")
    )


def test_bdpt_jaxpr_invariants():
    _assert_clean("bdpt.li", audit.integrator_li_jaxpr("bdpt", "cornell"))


def test_sppm_pass_jaxpr_invariants():
    cam, photon = audit.sppm_pass_jaxprs()
    _assert_clean("sppm camera pass", cam)
    _assert_clean("sppm photon pass", photon)


# ---------------------------------------------------------------------------
# compile-time invariants
# ---------------------------------------------------------------------------


def test_film_donation_materialized():
    """donate_argnums REQUESTS donation; the invariant is that the
    compiled executable actually aliases every film buffer input to an
    output (PR 1's donated-alias incident is the motivating example)."""
    assert audit.check_film_donation() == []


def test_zero_retraces_across_same_shape_waves():
    assert audit.check_recompile_guard() == []


def test_smoke_render_under_transfer_guard():
    assert audit.check_transfer_guard() == []


def test_donation_alias_counter_reads_hlo():
    txt = (
        "HloModule jit_f, is_scheduled=true, "
        "input_output_alias={ {0}: (0, {}, may-alias), "
        "{1}: (1, {}, may-alias) }, entry_computation_layout=..."
    )
    assert audit.donation_aliases(txt) == 2
    assert audit.donation_aliases("HloModule jit_f") == 0


def test_run_audit_aggregates_clean():
    """The CLI path: every audit passes on the shipped tree. Compile
    checks are exercised individually above; keep this to the pure-trace
    set so the aggregate stays cheap under pytest."""
    fails = audit.run_audit(include_compile=False)
    assert fails == [], "\n".join(fails)
