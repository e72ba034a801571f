"""Sampling-layer tests (pbrt src/tests/sampling.cpp counterpart):
distribution correctness of the warps, CDF sampling, stratification, the
stateless RNG, and MIS heuristics."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_pbrt.core import sampling as sm
from tpu_pbrt.core import smalltab


def _u(n, salt):
    i = jnp.arange(n)
    return np.asarray(sm.uniform_float(i, salt))


class TestRNG:
    def test_uniformity(self):
        u = _u(100_000, 1)
        assert 0.0 <= u.min() and u.max() < 1.0
        # first three moments of U[0,1)
        assert abs(u.mean() - 0.5) < 3e-3
        assert abs((u**2).mean() - 1 / 3) < 3e-3
        hist, _ = np.histogram(u, bins=64, range=(0, 1))
        chi2 = ((hist - len(u) / 64) ** 2 / (len(u) / 64)).sum()
        assert chi2 < 64 * 2.0, f"chi2 {chi2}"

    def test_streams_uncorrelated(self):
        a = _u(50_000, 1)
        b = _u(50_000, 2)
        r = np.corrcoef(a, b)[0, 1]
        assert abs(r) < 0.02

    def test_deterministic(self):
        assert np.array_equal(_u(100, 7), _u(100, 7))


class TestWarps:
    def test_concentric_disk_in_unit_disk(self):
        n = 20_000
        u1, u2 = _u(n, 3), _u(n, 4)
        x, y = sm.concentric_sample_disk(jnp.asarray(u1), jnp.asarray(u2))
        r2 = np.asarray(x) ** 2 + np.asarray(y) ** 2
        assert r2.max() <= 1.0 + 1e-6
        # uniform density: mean radius^2 = 1/2
        assert abs(r2.mean() - 0.5) < 5e-3

    def test_cosine_hemisphere_mean_cos(self):
        n = 50_000
        d = np.asarray(sm.cosine_sample_hemisphere(jnp.asarray(_u(n, 5)), jnp.asarray(_u(n, 6))))
        assert (d[:, 2] >= 0).all()
        # E[cos theta] under p = cos/pi is 2/3
        assert abs(d[:, 2].mean() - 2 / 3) < 5e-3

    def test_uniform_sphere(self):
        n = 50_000
        d = np.asarray(sm.uniform_sample_sphere(jnp.asarray(_u(n, 8)), jnp.asarray(_u(n, 9))))
        assert np.allclose(np.linalg.norm(d, axis=-1), 1.0, atol=1e-5)
        assert np.abs(d.mean(axis=0)).max() < 0.02

    def test_uniform_triangle_barycentric(self):
        n = 50_000
        b0, b1 = sm.uniform_sample_triangle(jnp.asarray(_u(n, 10)), jnp.asarray(_u(n, 11)))
        b0, b1 = np.asarray(b0), np.asarray(b1)
        assert (b0 >= 0).all() and (b1 >= 0).all() and (b0 + b1 <= 1 + 1e-6).all()
        # uniform over the simplex: E[b0] = E[b1] = 1/3
        assert abs(b0.mean() - 1 / 3) < 5e-3
        assert abs(b1.mean() - 1 / 3) < 5e-3

    def test_cone_pdf_normalises(self):
        ct = 0.7
        n = 50_000
        d = np.asarray(sm.uniform_sample_cone(jnp.asarray(_u(n, 12)), jnp.asarray(_u(n, 13)), ct))
        assert (d[:, 2] >= ct - 1e-5).all()
        # solid angle of the cone = 2pi(1-ct); pdf = 1/that
        assert abs(float(sm.uniform_cone_pdf(jnp.float32(ct))) - 1 / (2 * np.pi * (1 - ct))) < 1e-6


class TestStratified:
    def test_stratified_1d_covers_strata(self):
        n_strata = 16
        s = jnp.arange(n_strata)
        vals = np.asarray(sm.stratified_1d(s, n_strata, 123, 7))
        cells = np.floor(vals * n_strata).astype(int)
        assert sorted(cells.tolist()) == list(range(n_strata)), cells

    def test_stratified_2d_covers_grid(self):
        sx = sy = 4
        s = jnp.arange(sx * sy)
        u, v = sm.stratified_2d(s, sx, sy, 55, 9)
        cx = np.floor(np.asarray(u) * sx).astype(int)
        cy = np.floor(np.asarray(v) * sy).astype(int)
        assert sorted((cy * sx + cx).tolist()) == list(range(sx * sy))

    def test_permutation_is_bijection(self):
        for n in (5, 8, 13, 100):
            p = np.asarray(sm.permutation_element(jnp.arange(n), n, jnp.uint32(17)))
            assert sorted(p.tolist()) == list(range(n)), (n, p)


class TestLowDiscrepancy:
    def test_radical_inverse_base2(self):
        got = np.asarray(sm.radical_inverse_base2(jnp.arange(8)))
        expect = [0, 0.5, 0.25, 0.75, 0.125, 0.625, 0.375, 0.875]
        assert np.allclose(got, expect, atol=1e-6)

    def test_sobol_2d_stratified(self):
        """(0,2)-sequence property: first 16 points stratify every 4x4
        elementary interval."""
        x, y = sm.sobol_2d(jnp.arange(16))
        cx = np.floor(np.asarray(x) * 4).astype(int)
        cy = np.floor(np.asarray(y) * 4).astype(int)
        assert sorted((cy * 4 + cx).tolist()) == list(range(16))


class TestDistribution1D:
    def test_discrete_pmf(self):
        d = sm.Distribution1D.build([1.0, 3.0, 0.0, 4.0])
        u = jnp.asarray(_u(100_000, 21))
        idx, pmf = d.sample_discrete(u)
        idx = np.asarray(idx)
        counts = np.bincount(idx, minlength=4) / len(idx)
        assert np.allclose(counts, [1 / 8, 3 / 8, 0, 4 / 8], atol=5e-3)
        assert np.allclose(np.asarray(pmf), counts[idx], atol=5e-3)

    def test_continuous_pdf_integrates(self):
        f = [0.2, 1.0, 2.0, 0.5, 0.3]
        d = sm.Distribution1D.build(f)
        u = jnp.asarray(_u(100_000, 22))
        x, pdf, _ = d.sample_continuous(u)
        x = np.asarray(x)
        # E[1/pdf] over samples = measure of domain = 1
        assert abs(np.mean(1.0 / np.asarray(pdf)) - 1.0) < 5e-3
        # histogram matches f (normalized)
        hist, _ = np.histogram(x, bins=5, range=(0, 1), density=True)
        fn = np.asarray(f) / np.mean(f)
        assert np.allclose(hist, fn, rtol=0.05)


class TestDistribution2D:
    def test_sample_matches_pdf(self):
        rng = np.random.default_rng(3)
        f = rng.uniform(0.1, 2.0, (8, 16))
        d = sm.Distribution2D.build(f)
        n = 200_000
        u1 = jnp.asarray(_u(n, 31))
        u2 = jnp.asarray(_u(n, 32))
        (u, v), pdf = d.sample_continuous(u1, u2)
        # cross-check pdf() against the sampling pdf
        pdf2 = d.pdf(u, v)
        assert np.allclose(np.asarray(pdf), np.asarray(pdf2), rtol=1e-4)
        # E[1/pdf] = domain measure = 1
        assert abs(np.mean(1.0 / np.asarray(pdf)) - 1.0) < 5e-3
        # cell frequencies proportional to f
        iu = np.clip((np.asarray(u) * 16).astype(int), 0, 15)
        iv = np.clip((np.asarray(v) * 8).astype(int), 0, 7)
        counts = np.zeros((8, 16))
        np.add.at(counts, (iv, iu), 1.0)
        counts /= counts.sum()
        expect = f / f.sum()
        assert np.abs(counts - expect).max() < 0.003


def _row_gather_sample(f, u1, u2):
    """The row-gather `Distribution2D.sample_continuous` the search
    replaced, kept here as the oracle: pbrt's (W + 1)-element CDF rows,
    the marginal by searchsorted, then the picked row gathered whole for
    every lane and counted, and its func row gathered again."""
    f = np.asarray(f, np.float64)
    h, w = f.shape
    cond_cdf = np.zeros((h, w + 1))
    cond_cdf[:, 1:] = np.cumsum(f, axis=1) / w
    cond_int = cond_cdf[:, -1].copy()
    safe = np.where(cond_int == 0, 1.0, cond_int)
    cond_cdf[:, 1:] = np.where(
        cond_int[:, None] == 0, np.arange(1, w + 1)[None, :] / w, cond_cdf[:, 1:] / safe[:, None])
    marg = sm.Distribution1D.build(cond_int)
    tables = (jnp.asarray(f, jnp.float32), jnp.asarray(cond_cdf, jnp.float32),
              jnp.asarray(cond_int, jnp.float32), marg.func, marg.cdf, marg.func_int)

    @jax.jit
    def sample(tables, u1, u2):  # the tables as arguments, as the program has them
        cond_func, cond_cdf, cond_int, marg_func, marg_cdf, marg_int = tables
        row = jnp.clip(jnp.searchsorted(marg_cdf, u2, side="right") - 1, 0, h - 1)
        mc0 = marg_cdf[row]
        mc1 = marg_cdf[row + 1]
        dv = jnp.where(mc1 > mc0, (u2 - mc0) / jnp.maximum(mc1 - mc0, 1e-20), 0.0)
        pdf_v = jnp.where(marg_int > 0, marg_func[row] / jnp.maximum(marg_int, 1e-20), 0.0)
        v = (row.astype(jnp.float32) + dv) / h
        cdf_row = cond_cdf[row]
        col = jnp.clip(jnp.sum(cdf_row <= u1[..., None], axis=-1) - 1, 0, w - 1)
        cc0 = jnp.take_along_axis(cdf_row, col[..., None], axis=-1)[..., 0]
        cc1 = jnp.take_along_axis(cdf_row, col[..., None] + 1, axis=-1)[..., 0]
        du = jnp.where(cc1 > cc0, (u1 - cc0) / jnp.maximum(cc1 - cc0, 1e-20), 0.0)
        ci = cond_int[row]
        fval = jnp.take_along_axis(cond_func[row], col[..., None], axis=-1)[..., 0]
        pdf_u = jnp.where(ci > 0, fval / jnp.maximum(ci, 1e-20), 0.0)
        return ((col.astype(jnp.float32) + du) / w, v), pdf_u * pdf_v

    return sample(tables, jnp.asarray(u1, jnp.float32), jnp.asarray(u2, jnp.float32))


def _map(h, w, seed):
    """A lumpy (h, w) importance map with two rows of zero mass and a run
    of zero columns in every row, as a map's black border gives them."""
    rng = np.random.default_rng(seed)
    f = rng.gamma(0.3, 1.0, (h, w)) * np.sin((np.arange(h) + 0.5) / h * np.pi)[:, None]
    f[:, w // 3: w // 3 + max(w // 8, 1)] = 0.0
    if h > 2:
        f[1] = 0.0
        f[h - 1] = 0.0
    return f


def _us(cdf_rows, n, seed):
    """u at 0, at 1 - 2^-24, on elements of the CDFs (the steps), on either
    side of them, and uniform."""
    rng = np.random.default_rng(seed)
    steps = np.unique(np.append(np.asarray(cdf_rows, np.float32).reshape(-1), np.float32(0.0)))
    steps = steps[steps < 1.0]
    edge = np.asarray([0.0, np.float32(1.0 - 2.0**-24), np.float32(0.5)], np.float32)
    on = rng.choice(steps, n)
    near = np.nextafter(on, np.float32(0.0)).astype(np.float32)
    up = np.nextafter(on, np.float32(1.0)).astype(np.float32)
    out = np.concatenate([edge, on, near, up, rng.random(n, np.float32)]).astype(np.float32)
    # no subnormal u: XLA flushes them to zero in some fusions and not in
    # others (and the TPU in all), so two programs may part on one
    tiny = np.finfo(np.float32).tiny
    return np.where((out > 0) & (out < tiny), tiny, out).astype(np.float32)


class TestDistribution2DSearch:
    """`sample_continuous` as two pivot searches (smalltab.pivot_search)
    returns the row gather's (u, v) and pdf bit for bit."""

    @pytest.mark.parametrize("h, w", [(1, 1), (3, 1), (4, 7), (6, 128), (5, 2049), (3, 4097), (64, 32)])
    def test_bit_identical_to_the_row_gather(self, h, w):
        f = _map(h, w, h * 1000 + w)
        d = sm.Distribution2D.build(f)
        cond = np.asarray(d.cond_cdf).reshape(h, w)
        u1 = _us(cond, 1500, 1)
        u2 = _us(np.asarray(d.marg_cdf), 1500, 2)
        rng = np.random.default_rng(3)
        u2 = rng.permutation(u2)
        (uo, vo), po = _row_gather_sample(f, u1, u2)
        (us_, vs), ps = jax.jit(lambda d, a, b: d.sample_continuous(a, b))(d, jnp.asarray(u1), jnp.asarray(u2))
        np.testing.assert_array_equal(np.asarray(us_), np.asarray(uo))
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(vo))
        np.testing.assert_array_equal(np.asarray(ps), np.asarray(po))

    @pytest.mark.parametrize("h, w", [(8, 2049), (5, 300)])
    def test_bit_identical_on_the_picked_rows_steps(self, h, w):
        """u1 on the elements of the very row its u2 picks: the count's
        boundary, where a search and a count could part."""
        f = _map(h, w, 7)
        d = sm.Distribution2D.build(f)
        cond = np.asarray(d.cond_cdf).reshape(h, w)
        rng = np.random.default_rng(5)
        u2 = rng.random(4000, np.float32)
        mc = np.concatenate([[0.0], np.asarray(d.marg_cdf)])
        row = np.clip(np.searchsorted(mc, u2, side="right") - 1, 0, h - 1)
        pick = cond[row, rng.integers(0, w, len(u2))]
        u1 = np.where(rng.random(len(u2)) < 0.5, pick, np.nextafter(pick, np.float32(0.0))).astype(np.float32)
        u1 = np.minimum(u1, np.float32(1.0 - 2.0**-24))
        (uo, vo), po = _row_gather_sample(f, u1, u2)
        (us_, vs), ps = jax.jit(lambda d, a, b: d.sample_continuous(a, b))(d, jnp.asarray(u1), jnp.asarray(u2))
        np.testing.assert_array_equal(np.asarray(us_), np.asarray(uo))
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(vo))
        np.testing.assert_array_equal(np.asarray(ps), np.asarray(po))

    @pytest.mark.parametrize("h, w", [(1024, 2048), (64, 128), (1, 1), (5, 2049)])
    def test_table_reads_are_the_plans(self, h, w):
        """`table_reads`: 15 pivots a level and one element a tail step of
        each search (`pick_plan`'s), and the pdf's four; the traced
        function reads exactly those: one take of 15 pivots a level, one
        element a step and four for the pdf, no row."""
        d = sm.Distribution2D.build(np.ones((h, w)))
        (lm, tm), (lc, tc) = smalltab.pick_plan(h, 1), smalltab.pick_plan(w, h)
        assert (len(d.marg_pivots), len(d.cond_pivots)) == (lm, lc)
        assert d.table_reads == smalltab.PIVOTS * (lm + lc) + tm + tc + 4
        jaxpr = str(jax.make_jaxpr(lambda d, a, b: d.sample_continuous(a, b))(
            d, jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.float32)))
        sizes = re.findall(r"gather\[[^\]]*?slice_sizes=\(([\d, ]*)\)", jaxpr, re.S)
        takes = scalars = 0
        for size in sizes:
            dims = [int(x) for x in size.replace(" ", "").split(",") if x]
            if dims == [smalltab.PIVOTS, 1]:
                takes += 1
            else:
                assert all(x == 1 for x in dims), dims  # one element, never a row
                scalars += 1
        assert takes == lm + lc and scalars == tm + tc + 3  # marg_int is a scalar, not gathered
        if (h, w) == (1024, 2048):
            assert (lm, tm, lc, tc) == (2, 2, 2, 3) and d.table_reads == 69 < 128

    def test_no_lanes_by_width_value_at_the_pools_lanes(self):
        """Compiled on the CPU at the pool's 2^18 lanes and a 2048x1024
        map: no buffer of lanes x (a row's width or more)."""
        d = sm.Distribution2D.build(np.ones((1024, 2048)))
        lanes = jax.ShapeDtypeStruct((1 << 18,), jnp.float32)
        text = jax.jit(lambda d, a, b: d.sample_continuous(a, b)).lower(d, lanes, lanes).compile().as_text()
        wide = [m for m in re.findall(r"f32\[262144,(\d+)\]", text) if int(m) >= 2048]
        assert "262144" in text and not wide, wide


class TestMIS:
    def test_power_heuristic_partition(self):
        """w_f(pf,pg) + w_g(pg,pf) = 1 — the MIS weights partition unity."""
        pf = jnp.asarray(_u(1000, 41)) * 5
        pg = jnp.asarray(_u(1000, 42)) * 5
        wf = np.asarray(sm.power_heuristic(1, pf, 1, pg))
        wg = np.asarray(sm.power_heuristic(1, pg, 1, pf))
        assert np.allclose(wf + wg, 1.0, atol=1e-5)
