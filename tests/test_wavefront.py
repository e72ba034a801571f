"""Persistent wavefront: path regeneration in place (ISSUE 1 tentpole;
ISSUE 26 took the per-wave lane permutation out). Oracles:

- ESTIMATOR EQUIVALENCE: every sampler dimension is a pure function of
  (px, py, s, dimension salt), so a regenerated lane draws exactly the
  streams the fixed-batch loop would have — the two render paths must
  produce the same image on a real multi-bounce scene (bit-identical at
  spp=1 where each pixel sums a single sample; within float-accumulation
  order at higher spp).
- OCCUPANCY: on a depth-5 diffuse scene the pool's mean wave occupancy
  (live lanes / pool slots, averaged over trace waves) must be near 1,
  versus the ~0.3-0.4 a fixed batch decays to — the tentpole's whole
  point. The fixed-batch wave count per finished path must also shrink.
- FREE-SLOT RANK (ISSUE 26): the k-th free slot in lane order takes work
  item cursor + k, wherever it lies; the drain's waves, rays, occupancy
  and regenerated lanes are the ones the compacting parent gave.
"""

import functools
import os

import numpy as np
import pytest

from tpu_pbrt.scenes import compile_api, make_killeroo_like


def _render(spp, env, maxdepth=5):
    return _render_once(spp, tuple(sorted(env.items())), maxdepth)


@functools.lru_cache(maxsize=None)
def _render_once(spp, env, maxdepth):
    """One render per (spp, knobs): three of the file's seven renders are
    asked for twice, and a render is a whole chunk program built."""
    from tpu_pbrt import config

    env = dict(env)
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    config.reload()
    try:
        api = make_killeroo_like(
            res=32, spp=spp, maxdepth=maxdepth, n_theta=24, n_phi=48
        )
        scene, integ = compile_api(api)
        return integ.render(scene)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        config.reload()


def test_regen_image_bit_identical_at_spp1():
    """spp=1: each pixel holds exactly one sample, so there is no
    accumulation-order freedom — the pool render must reproduce the
    fixed-batch image to float precision."""
    r_fix = _render(1, {"TPU_PBRT_REGEN": "0"})
    r_reg = _render(1, {"TPU_PBRT_REGEN": "1", "TPU_PBRT_POOL": "256"})
    assert r_reg.stats.get("regen"), r_reg.stats
    assert r_reg.rays_traced == r_fix.rays_traced
    a = np.asarray(r_fix.image, np.float32)
    b = np.asarray(r_reg.image, np.float32)
    assert np.max(np.abs(a - b)) <= 1e-6, np.max(np.abs(a - b))


def test_regen_image_matches_fixed_batch_multisample():
    """spp=4 ((0,2)-sequence sampler): samples of a pixel deposit in
    termination order instead of work order, so the per-pixel sums may
    differ by float rounding only."""
    r_fix = _render(4, {"TPU_PBRT_REGEN": "0"})
    r_reg = _render(4, {"TPU_PBRT_REGEN": "1", "TPU_PBRT_POOL": "512"})
    assert r_reg.rays_traced == r_fix.rays_traced
    np.testing.assert_allclose(
        np.asarray(r_reg.image), np.asarray(r_fix.image),
        rtol=1e-4, atol=1e-5,
    )


def test_regen_occupancy_high_on_depth5_diffuse():
    """The judged occupancy metric: with regeneration the mean wave
    occupancy on a depth-5 diffuse scene must exceed 0.9 (the fixed
    batch decays to ~0.3-0.4 after the first bounces), and the pool must
    finish in fewer trace waves per path than the fixed-batch loop's
    full-width max_depth+2 sweeps."""
    r = _render(64, {"TPU_PBRT_REGEN": "1", "TPU_PBRT_POOL": "1024"})
    occ = r.stats["mean_wave_occupancy"]
    assert occ > 0.9, r.stats
    # wave-count evidence: lane-waves actually dispatched vs what the
    # fixed batch pays (every work item rides every one of the
    # max_depth+2 full-width waves)
    total_work = 32 * 32 * 64
    pool_lane_waves = r.stats["n_waves"] * r.stats["pool"]
    fixed_lane_waves = total_work * (5 + 2)
    assert pool_lane_waves * 2 <= fixed_lane_waves, (
        pool_lane_waves, fixed_lane_waves,
    )


def test_regen_respects_opt_out():
    r = _render(1, {"TPU_PBRT_REGEN": "0"})
    # no pool/regen stats on the fixed-batch path; the non-finite
    # firewall (ISSUE 5) is the one telemetry entry it does report —
    # a clean render counts zero scrubbed deposits
    assert "regen" not in r.stats
    assert "mean_wave_occupancy" not in r.stats
    assert r.stats.get("telemetry", {}).get("counters", {}) == {
        "nonfinite_deposits": 0
    }


# -- ISSUE 26: the free-slot rank alone, no scene ---------------------------


def _mask(kind, pool):
    lane = np.arange(pool)
    if kind == "all_free":
        return np.zeros(pool, bool)
    if kind == "all_live":
        return np.ones(pool, bool)
    if kind == "alternating":
        return lane % 2 == 0
    return np.random.default_rng(pool).random(pool) < 0.64  # a wave's occupancy


@pytest.mark.parametrize("pool", [256, 65536])
@pytest.mark.parametrize("kind", ["all_free", "all_live", "alternating", "random"])
@pytest.mark.parametrize("left", ["plenty", "runs_out", "none"])
def test_free_slot_work_hands_out_the_next_items_in_lane_order(kind, pool, left):
    import jax.numpy as jnp

    from tpu_pbrt.integrators.path import _free_slot_work

    has_work = _mask(kind, pool)
    n_free = int((~has_work).sum())
    cursor = 3 * pool + 7
    # work left past the cursor: more than the free slots can take, about
    # half of what they could take (it runs out mid-pool), none
    n_work = cursor + {"plenty": pool + 5, "runs_out": n_free // 2 + 1, "none": 0}[left]
    widx, can, consumed = _free_slot_work(
        jnp.asarray(has_work), jnp.int32(cursor), n_work
    )
    widx, can, consumed = np.asarray(widx), np.asarray(can), int(consumed)
    assert widx.dtype == np.int32 and can.dtype == bool
    # the parent's formula, n_live counted from the mask
    assert consumed == int(np.clip(n_work - cursor, 0, pool - int(has_work.sum())))
    assert not can[has_work].any()
    # exactly cursor .. cursor+consumed-1, each once, ascending in lane order
    assert int(can.sum()) == consumed
    assert widx[can].tolist() == list(range(cursor, cursor + consumed))
    # the free slots that got nothing are the LAST free slots in lane order
    free_lanes = np.flatnonzero(~has_work)
    assert np.flatnonzero(can).tolist() == free_lanes[:consumed].tolist()


def test_regen_in_place_drains_as_the_compacting_parent_did():
    """32x32, 4 spp, pool 512: the values the parent commit (per-wave
    compaction, PR 25) gives on this scene, read from it once. The same
    work items enter on the same wave, so the drain's shape is the
    parent's to the digit; the image is the fixed-batch loop's within
    the file's accumulation-order tolerance."""
    r = _render(4, {"TPU_PBRT_REGEN": "1", "TPU_PBRT_POOL": "512"})
    ctr = r.stats["telemetry"]["counters"]
    assert r.stats["n_waves"] == 21
    assert r.rays_traced == ctr["rays_traced"] == 11488
    assert r.stats["mean_wave_occupancy"] == pytest.approx(0.7970610119047619, abs=1e-12)
    assert ctr["lanes_regenerated"] == ctr["lanes_terminated"] == ctr["film_deposits"] == 4096
    assert ctr["occupancy_histogram"] == [3, 0, 1, 0, 1, 0, 0, 16]
    assert "lanes_compacted" not in ctr
    r_fix = _render(4, {"TPU_PBRT_REGEN": "0"})
    np.testing.assert_allclose(
        np.asarray(r.image), np.asarray(r_fix.image), rtol=1e-4, atol=1e-5
    )
