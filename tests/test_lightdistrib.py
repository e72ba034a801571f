"""SpatialLightDistribution tests (lightdistrib.cpp capability,
VERDICT r2 weak #9; ISSUE 37: any light count, O(log L) a pick).

(a) THE TABLE, at every border of the pick's plan (ISSUE 38: `TABLE_SIZES`,
    the dense select's side included) and with runs of equal entries:
    every voxel's pmf sums to 1 and is positive; the pdf of a pick IS the
    pick's pmf, bit for bit; the search returns the index and the pmf that
    the gather of a whole row and a count along it return (the program's
    former expression, kept HERE as the oracle and nowhere in the program),
    on random u and on u exactly on elements and on pivots; u = 0, u just
    under 1 and a point outside the grid clamp as before; a pick reads a
    take a level and a gather a tail step; the plan comes from the table.
(b) THE SCENE, with a table on each side of MAX_DENSE_ROWS: position-
    dependent selection must prefer nearby lights and leave the estimator
    unbiased (strategy choice changes variance, never the mean).
(c) THE ROW: one packed row a light against the column-by-column take, bit
    for bit, on a table holding every light type the compiler emits.
(d) THE FALLBACK: a table over its budget in bytes is replaced by power,
    LOUDLY, and the film is still the scene's.
"""

import functools
import re
from typing import NamedTuple
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_render import MATTE_DEPTH1, QUAD, render_scene
from tpu_pbrt.core import lights_dev as ld
from tpu_pbrt.core import smalltab as stab
from tpu_pbrt.core.smalltab import MAX_DENSE_ROWS

class Table(NamedTuple):
    """A test table: `n` light rows over `side`^3 voxels of the unit cube,
    `runs` of zero-importance lights (equal CDF entries) or none, built
    under a pivot budget of `budget` bytes (0: the program's)."""

    n: int
    side: int = 2
    runs: bool = False
    budget: int = 0


#: every border of the pick's plan (ISSUE 38): the dense select (3); one
#: level and a tail of 1 or 2 steps (17, 20, 33: no multiple of 16); two
#: levels and no tail (256) or a short one (257, 1,000); three levels and
#: none (4,096) or one (8,192); at the cell's 512 voxels three levels and a
#: tail of 1 (8,192) and of 3 (32,768, what the spatial table's 64 MiB
#: holds), and where a budget of 2 MiB stops the levels at two (the plan
#: the issue named), a tail of 5 and of 7; runs of equal entries
TABLE_SIZES = [
    Table(3), Table(17), Table(20), Table(33), Table(256), Table(257), Table(1000), Table(4096),
    Table(8192), Table(8192, 8), Table(8192, 8, budget=2 << 20), Table(8192, 8, True),
    Table(8192, 8, True, 2 << 20), Table(32768, 8), Table(32768, 8, budget=2 << 20),
]


def _case_id(t: Table) -> str:
    return f"{t.n}L_{t.side ** 3}V" + ("_runs" if t.runs else "") + (f"_{t.budget >> 20}MiB" if t.budget else "")


TABLES = pytest.mark.parametrize("case", TABLE_SIZES, ids=[_case_id(t) for t in TABLE_SIZES])


@functools.lru_cache(maxsize=None)
def _cdf(n_lights: int, side: int, runs: bool):
    """The host's (V, L) float32 CDF over n_lights in a unit cube of side^3
    voxels, built as the compiler builds it, and the mean pmf."""
    rng = np.random.default_rng(n_lights + side)
    imp = rng.uniform(0.0, 1.0, (side**3, n_lights)) ** 8 + 1e-6  # a few lights carry a voxel
    if runs:  # zero-importance lights: a run at the row's start, runs across pivots, its end
        for lo, hi in ((0, 40), (500, 1100), (4000, 4600), (n_lights - 300, n_lights - 1)):
            imp[:, lo:hi] = 0.0
        imp[rng.random(imp.shape) < 0.2] = 0.0
    imp /= imp.sum(-1, keepdims=True)
    cdf = np.cumsum(imp, -1).astype(np.float32)
    cdf[:, -1] = 1.0
    return cdf, imp.mean(0).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _table(case: Table):
    """The distribution of `_cdf` as `SpatialLightDistribution.build` makes
    it under the case's budget -> (distribution, the host's (V, L) CDF)."""
    cdf, mean_pmf = _cdf(case.n, case.side, case.runs)
    with mock.patch.object(stab, "PIVOT_TABLE_BUDGET_BYTES", case.budget or stab.PIVOT_TABLE_BUDGET_BYTES):
        sd = ld.SpatialLightDistribution.build(
            cdf, mean_pmf, np.zeros(3), np.full(3, float(case.side)), (case.side,) * 3
        )
    return sd, cdf


def _jit(sd, method):
    """sd.method under jit with the tables as ARGUMENTS, as the program is
    handed them (`bound`), not as constants."""
    f = jax.jit(lambda tables, *a: getattr(sd._replace(**tables), method)(*a))
    return lambda *a: f(sd.tables(), *a)


def _lanes(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    u = rng.uniform(0.0, 1.0, n).astype(np.float32)
    p = rng.uniform(0.0, 1.0, (n, 3)).astype(np.float32)
    return jnp.asarray(u), jnp.asarray(p)


def _voxel_of(p, side: int = 2):
    v = np.clip(np.floor(np.asarray(p) * side).astype(np.int32), 0, side - 1)
    return v[:, 0] + side * (v[:, 1] + side * v[:, 2])


def row_gather_and_count(cdf, voxel, u):
    """THE ORACLE: what `sample_discrete_at` was before ISSUE 37, in numpy:
    the voxel's whole row a lane, a count along it, two reads for the pmf."""
    row = cdf[voxel]  # (lanes, L)
    idx = np.minimum((np.asarray(u)[:, None] >= row).sum(-1), row.shape[-1] - 1)
    at = np.take_along_axis(row, idx[:, None], -1)[:, 0]
    prev = np.where(idx > 0, np.take_along_axis(row, np.maximum(idx - 1, 0)[:, None], -1)[:, 0], np.float32(0))
    return idx, np.maximum(at - prev, np.float32(1e-12))


@TABLES
def test_every_voxels_pmf_sums_to_one_and_is_positive(case):
    sd, cdf = _table(case)
    n_lights, n_vox = case.n, case.side**3
    assert (sd.cdf.ndim == 1) == (n_lights > MAX_DENSE_ROWS)  # stored flat where it is searched
    centre = (np.stack(np.unravel_index(np.arange(n_vox), (case.side,) * 3, order="F"), -1) + 0.5) / case.side

    def every_pdf(tables, centre):
        idx = jnp.broadcast_to(jnp.arange(n_lights), (n_vox, n_lights))
        p = jnp.broadcast_to(centre[:, None, :], (n_vox, n_lights, 3))
        return sd._replace(**tables).discrete_pdf_at(idx, p)

    pmf = np.asarray(jax.jit(every_pdf)(sd.tables(), jnp.asarray(centre, jnp.float32)))
    assert (pmf > 0).all()
    np.testing.assert_allclose(pmf.astype(np.float64).sum(-1), 1.0, atol=2e-5)
    np.testing.assert_array_equal(pmf, np.diff(cdf, axis=-1, prepend=np.float32(0)).clip(1e-12))


@TABLES
def test_pdf_of_a_pick_is_the_picks_pmf_bit_for_bit(case):
    sd, _ = _table(case)
    u, p = _lanes(4096, 1)
    idx, pmf = _jit(sd, "sample_discrete_at")(u, p)
    again = _jit(sd, "discrete_pdf_at")(idx, p)
    np.testing.assert_array_equal(np.asarray(pmf), np.asarray(again))
    assert len(np.unique(np.asarray(idx))) > min(case.n, 16) // 2


def _pivot_positions(n: int, levels: int):
    """Every element of a row that some level holds as a pivot."""
    bits = (n - 1).bit_length()
    pos = [np.arange(1, n >> (bits - 4 * (k + 1)) + 1) * (1 << (bits - 4 * (k + 1))) - 1 for k in range(levels)]
    return np.unique(np.concatenate(pos)) if pos else np.zeros(0, np.int64)


@TABLES
def test_the_search_returns_what_the_row_gather_and_count_returned(case):
    sd, cdf = _table(case)
    n_lights = case.n
    pick = _jit(sd, "sample_discrete_at")
    u, p = _lanes(2048, 2)
    voxel = _voxel_of(p, case.side)
    idx, pmf = pick(u, p)
    want_idx, want_pmf = row_gather_and_count(cdf, voxel, u)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(pmf), want_pmf)
    # and on u exactly ON elements of the table, where >= decides: any
    # element, and every element a level holds as a pivot
    rng = np.random.default_rng(case.n)
    on_any = np.arange(2048) % max(n_lights - 1, 1)
    pivots = _pivot_positions(n_lights, sd.plan[0])
    on_pivot = rng.choice(pivots[pivots < n_lights - 1], 2048) if len(pivots) > 1 else on_any
    for at in (on_any, on_pivot, np.minimum(on_pivot + 1, n_lights - 1)):
        on = jnp.asarray(cdf[voxel, at])
        idx, pmf = pick(on, p)
        want_idx, want_pmf = row_gather_and_count(cdf, voxel, on)
        np.testing.assert_array_equal(np.asarray(idx), want_idx)
        np.testing.assert_array_equal(np.asarray(pmf), want_pmf)


@TABLES
def test_u_at_its_ends_and_a_point_outside_the_grid_clamp(case):
    sd, cdf = _table(case)
    n_lights, side = case.n, case.side
    under_one = np.nextafter(np.float32(1.0), np.float32(0.0))
    u = jnp.asarray([0.0, under_one, 0.5, 0.5, 0.0, under_one], jnp.float32)
    p = jnp.asarray([[0.2 / side, 0.2 / side, 0.2 / side], [0.2 / side] * 3, [-5.0, -5.0, -5.0], [9.0, 9.0, 9.0],
                     [9.0, -5.0, 0.7 / side + 0.5], [-1.0, 0.3 / side, 40.0]], jnp.float32)
    top = side - 1
    voxel = np.asarray([0, 0, 0, top + side * (top + side * top), top + side * side * (side // 2), side * side * top])
    np.testing.assert_array_equal(np.asarray(sd._voxel(p)), voxel)
    idx, pmf = _jit(sd, "sample_discrete_at")(u, p)
    want_idx, want_pmf = row_gather_and_count(cdf, voxel, u)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(pmf), want_pmf)
    assert int(idx[0]) == 0 or cdf[0, 0] == 0.0
    assert 0 <= int(np.asarray(idx).min()) and int(np.asarray(idx).max()) <= n_lights - 1
    # an index outside the table is clamped for the pdf too, as the gather clamped it
    pdf = _jit(sd, "discrete_pdf_at")
    out = pdf(jnp.asarray([n_lights + 7, 0]), p[:2])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pdf(jnp.asarray([n_lights - 1, 0]), p[:2])))


def _reads(jaxpr) -> dict:
    """The lowered gathers of a jaxpr by what they fetch: a lane-major take
    of 15 pivots (`take`), one element of the flat table (`gather`), or a
    whole row (`row`)."""
    text = str(jaxpr)
    sizes = re.findall(r"gather\[[^\]]*?slice_sizes=\(([\d, ]*)\)", text, re.S)
    kinds = {"take": 0, "gather": 0, "row": 0}
    for size in sizes:
        dims = [int(d) for d in size.replace(" ", "").split(",") if d]
        kinds["take" if dims == [ld.PIVOTS, 1] else "gather" if dims == [1] else "row"] += 1
    return kinds


@pytest.mark.parametrize("case, levels, tail", [
    (Table(3), 0, 0), (Table(16), 0, 0), (Table(17, 8), 1, 1), (Table(256, 8), 2, 0),
    (Table(8192, 8), 3, 1), (Table(32768, 8), 3, 3), (Table(8192, 8, budget=2 << 20), 2, 5),
    (Table(32768, 8, budget=2 << 20), 2, 7), (Table(4096), 3, 0), (Table(1000), 2, 2),
], ids=lambda c: _case_id(c) if isinstance(c, Table) else None)
def test_the_search_reads_log2_elements_and_a_pdf_two(case, levels, tail):
    """A pick reads one take of 15 pivots a level and one element of the
    flat table a tail step, no row; the pmf comes with them. The pdf of a
    hit reads two elements. At or under 16 rows the voxel's row is read
    whole, once."""
    sd, _ = _table(case)
    assert sd.plan == (levels, tail) and len(sd.pivots) == levels
    searched = case.n > MAX_DENSE_ROWS
    assert sd.table_reads == (ld.PIVOTS * levels + tail if searched else case.n)
    u, p = _lanes(64)
    bound = lambda f: lambda t, *a: getattr(sd._replace(**t), f)(*a)  # noqa: E731
    picked = _reads(jax.make_jaxpr(bound("sample_discrete_at"))(sd.tables(), u, p))
    pdf = _reads(jax.make_jaxpr(bound("discrete_pdf_at"))(sd.tables(), jnp.zeros(64, jnp.int32), p))
    if searched:
        assert picked == {"take": levels, "gather": tail, "row": 0}
        assert pdf == {"take": 0, "gather": 2, "row": 0}
    else:
        assert picked["take"] == picked["gather"] == 0 and picked["row"] >= 1
    # the plan: the most 4-bit levels whose pivot table fits the budget
    with mock.patch.object(stab, "PIVOT_TABLE_BUDGET_BYTES", case.budget or stab.PIVOT_TABLE_BUDGET_BYTES):
        assert sd.plan == (ld.pick_plan(case.n, case.side**3) if searched else (0, 0))


@pytest.mark.parametrize("n_lights, n_vox, levels, tail", [
    (17, 512, 1, 1), (256, 512, 2, 0), (8192, 512, 3, 1), (32768, 512, 3, 3), (65536, 4096, 2, 8),
])
def test_the_plan_is_chosen_from_the_table(n_lights, n_vox, levels, tail):
    """At the compiler's 512 voxels (and at a 16^3 grid, where the budget
    binds): as many levels as the index has 4 bits for, while a level's
    pivot table fits PIVOT_TABLE_BUDGET_BYTES; one level more would not
    fit, or has no bits left."""
    assert ld.pick_plan(n_lights, n_vox) == (levels, tail)
    assert 4 * levels + tail == (n_lights - 1).bit_length()
    cdf = np.broadcast_to(np.linspace(1.0 / n_lights, 1.0, n_lights, dtype=np.float32), (n_vox, n_lights))
    tables = ld.pivot_tables(cdf, levels)
    assert [t.shape for t in tables] == [(ld.PIVOTS, n_vox * 16**k) for k in range(levels)]
    assert all(t.nbytes <= stab.PIVOT_TABLE_BUDGET_BYTES for t in tables)
    assert tail < 4 or ld.PIVOTS * 4 * n_vox * 16**levels > stab.PIVOT_TABLE_BUDGET_BYTES
    # a pivot is the element at the end of its sixteenth of the block, 1.0 past the row
    bits = (n_lights - 1).bit_length()
    width = 1 << (bits - 4)
    want = np.where(np.arange(1, 16) * width - 1 < n_lights, cdf[0, np.minimum(np.arange(1, 16) * width - 1, n_lights - 1)], 1.0)
    np.testing.assert_array_equal(tables[0][:, 0], want)


# -- (b): a scene on each side of MAX_DENSE_ROWS ------------------------------


def _two_cluster_scene(strategy, quads_a_side, spp=16):
    """`quads_a_side` light quads stacked at the left (red) and as many at
    the right (blue) of a floor: 4 light rows a pair of quads."""
    def cluster(x0, x1, rgb):
        out = []
        for k in range(quads_a_side):
            y0 = 0.4 + 0.5 * k / quads_a_side
            y1 = y0 + 0.4 / quads_a_side
            out.append(f'''AttributeBegin
AreaLightSource "diffuse" "rgb L" [{rgb}]
Shape "trianglemesh" {QUAD} "point P" [{x0} {y0} 0  {x1} {y0} 0  {x1} {y1} 0  {x0} {y1} 0]
AttributeEnd''')
        return "\n".join(out)

    return f'''
Integrator "directlighting" "string lightsamplestrategy" ["{strategy}"] {MATTE_DEPTH1}
Sampler "sobol" "integer pixelsamples" [{spp}]
PixelFilter "box"
Film "image" "integer xresolution" [24] "integer yresolution" [24] "string filename" [""]
LookAt 0 0 -4  0 0 0  0 1 0
Camera "perspective" "float fov" [70]
WorldBegin
{cluster(-2.2, -1.8, "20 4 4")}
{cluster(1.8, 2.2, "4 4 20")}
Material "matte" "rgb Kd" [0.7 0.7 0.7]
Shape "trianglemesh" {QUAD} "point P" [-3 -1 0.5   3 -1 0.5   3 -1 -3  -3 -1 -3]
WorldEnd
'''


SIDES = pytest.mark.parametrize("quads_a_side", [1, 5], ids=["4_rows_dense", "20_rows_searched"])


@SIDES
def test_spatial_distribution_built_and_prefers_near_light(quads_a_side):
    from tpu_pbrt.scene.api import Options, parse_string, pbrt_init

    api = pbrt_init(Options(quiet=True))
    parse_string(_two_cluster_scene("spatial", quads_a_side, spp=2), api, render=True)
    scene = api.scene
    sd = scene.spatial_distr
    assert sd is not None and scene.light_strategy_built == "spatial"
    L = sd.n
    assert L == scene.n_lights == 4 * quads_a_side  # two triangle rows a quad
    assert ("light_pick" in scene.dev) == ("rows" in scene.dev["light"]) == (L > MAX_DENSE_ROWS)
    sd = sd.bound(scene.dev)
    # a point right next to the left cluster mostly picks one of its rows
    p_left = jnp.asarray([[-2.0, 0.6, -0.2]], jnp.float32)
    p_right = jnp.asarray([[2.0, 0.6, -0.2]], jnp.float32)
    u = jnp.linspace(0.01, 0.99, 64)
    picks_l = np.asarray(sd.sample_discrete_at(u, jnp.broadcast_to(p_left, (64, 3)))[0])
    picks_r = np.asarray(sd.sample_discrete_at(u, jnp.broadcast_to(p_right, (64, 3)))[0])
    assert (picks_l < L // 2).mean() > 0.8, "near-left point should pick a left light"
    assert (picks_r >= L // 2).mean() > 0.8, "near-right point should pick a right light"
    # pmf consistency: discrete_pdf_at matches the sampled pick pmfs
    idx, pmf = sd.sample_discrete_at(u, jnp.broadcast_to(p_left, (64, 3)))
    pmf2 = sd.discrete_pdf_at(idx, jnp.broadcast_to(p_left, (64, 3)))
    np.testing.assert_array_equal(np.asarray(pmf), np.asarray(pmf2))


@SIDES
def test_the_scene_records_its_plan_and_carries_pivots_above_16_rows(quads_a_side):
    from tpu_pbrt.obs.trace import TRACE
    from tpu_pbrt.scene.api import Options, compile_string

    scene, _ = compile_string(_two_cluster_scene("spatial", quads_a_side, spp=2), Options(quiet=True))
    args = TRACE.spans("scene/light_distribution")[-1].args
    if scene.n_lights > MAX_DENSE_ROWS:  # 20 rows, 5 bits: one level over 512 voxels, one step
        assert (args["pick_levels"], args["pick_tail_steps"]) == ld.pick_plan(20, 512) == (1, 1)
        assert [t.shape for t in scene.dev["light_pick"]["pivots"]] == [(ld.PIVOTS, 512)]
        assert args["pivot_bytes"] == ld.PIVOTS * 512 * 4 <= stab.PIVOT_TABLE_BUDGET_BYTES
        assert ld.pick_reads(scene.dev, scene.spatial_distr) == ld.PIVOTS + 1 + ld.ROW_WIDTH
    else:  # the dense select: no table of the pick's in the program's arguments
        assert "light_pick" not in scene.dev and scene.spatial_distr.pivots == ()
        assert args["pick_levels"] == args["pick_tail_steps"] == args["pivot_bytes"] == 0


@SIDES
def test_spatial_strategy_unbiased(quads_a_side):
    img_s = render_scene(_two_cluster_scene("spatial", quads_a_side, spp=32)).image
    img_p = render_scene(_two_cluster_scene("power", quads_a_side, spp=32)).image
    rel = abs(img_s.mean() - img_p.mean()) / max(img_p.mean(), 1e-9)
    assert rel < 0.06, f"spatial {img_s.mean():.5f} vs power {img_p.mean():.5f}"
    assert np.isfinite(img_s).all()


# -- (c): the packed row -------------------------------------------------------

EVERY_LIGHT_TYPE = f'''
Integrator "path" "integer maxdepth" [2]
Sampler "random" "integer pixelsamples" [1]
Film "image" "integer xresolution" [8] "integer yresolution" [8] "string filename" [""]
LookAt 0 0 -4  0 0 0  0 1 0
Camera "perspective" "float fov" [70]
WorldBegin
LightSource "point" "rgb I" [3 2 1] "point from" [0.5 1.5 -0.5]
LightSource "spot" "rgb I" [5 5 4] "point from" [-1 2 0] "point to" [0 0 0.2] "float coneangle" [40] "float conedeltaangle" [8]
LightSource "distant" "rgb L" [0.5 0.6 0.7] "point from" [0 1 -1] "point to" [0 0 0]
LightSource "infinite" "rgb L" [0.1 0.1 0.15]
AttributeBegin
Rotate 30 0 1 0
LightSource "goniometric" "rgb I" [2 2 2]
AttributeEnd
AttributeBegin
Translate 0.3 1 0
LightSource "projection" "rgb I" [4 3 2] "float fov" [50]
AttributeEnd
AttributeBegin
AreaLightSource "diffuse" "rgb L" [7 6 5]
Shape "trianglemesh" {QUAD} "point P" [-0.5 1.9 -0.5  0.5 1.9 -0.5  0.5 1.9 0.5  -0.5 1.9 0.5]
AttributeEnd
AttributeBegin
AreaLightSource "diffuse" "rgb L" [1 2 3] "bool twosided" ["true"]
Shape "trianglemesh" "integer indices" [0 1 2 0 2 3 0 3 4 0 4 5 0 5 6 0 6 7 0 7 8 0 8 9 0 9 10]
  "point P" [1 0 0  1.2 0 0  1.2 0.1 0.1  1.1 0.2 0.1  1 0.3 0.2  0.9 0.3 0.3  0.8 0.2 0.3  0.8 0.1 0.2  0.9 0 0.1  1 -0.1 0.1  1.1 -0.1 0]
AttributeEnd
Material "matte" "rgb Kd" [0.7 0.7 0.7]
Shape "trianglemesh" {QUAD} "point P" [-3 -1 3   3 -1 3   3 -1 -3  -3 -1 -3]
WorldEnd
'''


def _same_bits(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_packed_row_against_the_column_by_column_take_bit_for_bit():
    from tpu_pbrt.scene import compiler as sc
    from tpu_pbrt.scene.api import Options, parse_string, pbrt_init

    api = pbrt_init(Options(quiet=True))
    parse_string(EVERY_LIGHT_TYPE, api, render=True)
    dev = api.scene.dev
    lt = dev["light"]
    types = set(np.asarray(lt["type"]).tolist())
    assert types == {sc.LIGHT_POINT, sc.LIGHT_SPOT, sc.LIGHT_DISTANT, sc.LIGHT_AREA, sc.LIGHT_INFINITE,
                     sc.LIGHT_GONIO, sc.LIGHT_PROJECTION}
    assert set(np.asarray(lt["twosided"]).tolist()) == {0, 1}
    n = lt["type"].shape[0]
    assert n == 2 + 9 + 6 > MAX_DENSE_ROWS and lt["rows"].shape == (ld.ROW_WIDTH, n)
    by_column = {**dev, "light": {k: v for k, v in lt.items() if k != "rows"}}
    rng = np.random.default_rng(3)
    lanes = 8 * n
    idx = jnp.asarray(np.arange(lanes) % n, jnp.int32)
    ref_p = jnp.asarray(rng.uniform(-1, 1, (lanes, 3)), jnp.float32)
    u = [jnp.asarray(rng.uniform(0, 1, lanes), jnp.float32) for _ in range(5)]
    # the fields themselves, as the table holds them
    row = ld._unpack_row(jnp.take(lt["rows"], idx, axis=1))
    for name in ("type", "p", "L", "dir", "cos0", "cos1", "twosided", "area", "tri_v"):
        np.testing.assert_array_equal(np.asarray(getattr(row, name)), np.asarray(lt[name])[np.asarray(idx)], err_msg=name)
    # and everything computed from them
    _same_bits(ld.sample_light_rows(dev, idx, ref_p, u[0], u[1]), ld.sample_light_rows(by_column, idx, ref_p, u[0], u[1]))
    _same_bits(ld.sample_le(dev, None, *u), ld.sample_le(by_column, None, *u))
    hit = jnp.where(idx % 3 == 0, -1, idx)  # lanes that hit no emitter too
    n_g = jnp.asarray(rng.normal(size=(lanes, 3)), jnp.float32)
    _same_bits(ld.emitted_radiance(dev, hit, -n_g, n_g), ld.emitted_radiance(by_column, hit, -n_g, n_g))
    _same_bits(ld.emitted_pdf(dev, None, ref_p, ref_p + 1.0, hit, n_g), ld.emitted_pdf(by_column, None, ref_p, ref_p + 1.0, hit, n_g))
    # the static read counts of a uniform pick: the packed row, and five of it a hit
    assert ld.pick_reads(dev, None) == ld.ROW_WIDTH and ld.emit_reads(dev, None) == 5


# -- (d): the loud fallback ----------------------------------------------------


def test_a_table_over_its_budget_falls_back_to_power_loudly(monkeypatch):
    from tpu_pbrt.obs.trace import TRACE
    from tpu_pbrt.scene import compiler as sc

    text = _two_cluster_scene("spatial", 5, spp=32)
    built = render_scene(text)
    said = []
    monkeypatch.setattr(sc, "Warning", said.append)
    monkeypatch.setattr(sc, "SPATIAL_TABLE_BUDGET_BYTES", 512 * 20 * 4 - 1)
    fell = render_scene(text)
    assert len(said) == 1 and '"spatial"' in said[0] and '"power"' in said[0] and "budget" in said[0]
    args = TRACE.spans("scene/light_distribution")[-1].args
    assert args["strategy_asked"] == "spatial" and args["strategy_built"] == "power"
    assert args["light_rows"] == 20 and args["table_bytes"] == 0 and args["voxels"] == 0
    # the film is still the scene's: power is another unbiased pick
    rel = abs(fell.image.mean() - built.image.mean()) / built.image.mean()
    assert rel < 0.06 and np.isfinite(fell.image).all()
    # one byte more and the table is built
    monkeypatch.setattr(sc, "SPATIAL_TABLE_BUDGET_BYTES", 512 * 20 * 4)
    render_scene(text)
    args = TRACE.spans("scene/light_distribution")[-1].args
    assert len(said) == 1 and args["strategy_built"] == "spatial" and args["table_bytes"] == 512 * 20 * 4
