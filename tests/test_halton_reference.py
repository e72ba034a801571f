"""The halton sampler under a per-lane salt (ISSUE 33), against references
that share no code with it.

(a) THE GENERATOR. A plain scrambled radical inverse, sample shuffle and
pair choice in NumPy on 64-bit integers masked to 32 bits (the program
works in wrapping uint32), against `sample_1d` / `sample_2d("halton")`
under ARRAY salts: every salt 0..95 (six depths of sixteen dimensions),
8 and 16 samples a pixel and 12, which is no power of two. Nothing is
imported from `tpu_pbrt.core.sampling` but the two functions under test;
the constants the two sides must agree on are copied here, each under its
name. Tolerances: the base-2 coordinates are a bit reversal, an xor and one
exact product, EQUAL to the last bit; a prime-base coordinate is a float32
sum of the digits an index below spp can have and of the offset inside the
last stratum, taken here in the program's order (lowest digit first), so it
is equal too unless the compiler contracts a product and a sum into one
rounding: 1 ulp of float32 (6e-8) is allowed for that, and the exact value
in float64 has to lie within 1e-6 (the float32 factor chain's own drift).
Array salt against scalar salt of the same value: EQUAL, every lane.

The scramble is held to what makes the estimator unbiased: over pixels,
every cell of a base's b x b grid is equally likely for a draw (until PR 33
one digit offset served every position and the cells were 0.12 to 1.97
times as likely as each other at 8 samples a pixel, and a base-2 second
coordinate kept its top bits unscrambled, mean 0.44; the chip read the
film's mean 1.6-2.0 % off the reference's).

(b) THE PATH. The film of the pool under halton against the film of the
fixed-batch loop under halton (`TPU_PBRT_REGEN=0`), 32x32, 8 samples a
pixel, the `test` preset's mesh of `killeroo-halton`, through a `.pbrt`
file as the benchmark writes it. The same samples of the same pixels, so
`rays_traced` is equal and the films differ by the order of float32 sums
alone: a pixel is the sum of 8 radiances of up to ~20 each, so 1e-5
relative (a hundred ulp of headroom over the few the order can move) with
1e-6 absolute for pixels near black. The render is cut into dispatches of
3,072 of its 8,192 samples: the last is ragged (2,048 samples and 1,024
work items past the film's end, twice the pool's 512 lanes), and every
pixel still has its 8.

The mutation "every lane takes lane 0's pair" (`_halton_which` answering
with its first lane's index) must fail both.
"""

import functools
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- the constants both sides must agree on, copied ------------------------
HASH_INIT = 0x2545F491  # hash_u32's start value
MIX_MUL_1, MIX_MUL_2 = 0x9E3779B1, 0x85EBCA6B  # _mix's two products
HASH_FINAL_MUL = 0xC2B2AE35  # hash_u32's last product
SALT_1D_ORDER, SALT_1D_SCRAMBLE = 0x6E5, 0x4A1  # sample_1d("halton")
SALT_2D_SCRAMBLE, SALT_2D_ORDER = 0x62B, 0xD47  # sample_2d("halton")
SECOND_SEED_SALT = 0x5EC  # the second coordinate's scramble is hash_u32(seed, SECOND_SEED_SALT)
TAIL_SALT = 0x7A11  # the offset inside the last stratum: uniform_float(seed, TAIL_SALT)
HALTON_PAIRS = [(2, 3), (5, 7), (3, 5), (7, 2), (2, 5), (3, 7)]
ONE_MINUS_EPSILON = np.float32(0.99999994)
#: Kensler's permutation: its six odd multipliers, in order of use
PERM_MULS = (0xE170893D, 0x0929EB3F, 0x6935FA69, 0x74DCCA23, 0x9E501CC3, 0xC860A3DF)
PERM_ROUNDS = 16  # the program's fixed number of cycle-walk rounds

M32 = np.uint64(0xFFFFFFFF)


def u64(x):
    return np.asarray(x).astype(np.int64).astype(np.uint64) & M32


def plain_hash(*parts):
    h = np.uint64(HASH_INIT)
    for p in parts:
        h = ((h ^ u64(p)) * np.uint64(MIX_MUL_1)) & M32
        h = h ^ (h >> np.uint64(16))
        h = (h * np.uint64(MIX_MUL_2)) & M32
        h = h ^ (h >> np.uint64(13))
    h = (h * np.uint64(HASH_FINAL_MUL)) & M32
    return h ^ (h >> np.uint64(16))


def plain_permutation(i, n, p):
    """Kensler's hash permutation of [0, n), cycle-walked on the next power
    of two for at most PERM_ROUNDS rounds."""
    i, p = u64(i), u64(p)
    w = n - 1
    for sh in (1, 2, 4, 8, 16):
        w |= w >> sh
    w = np.uint64(w)
    sh = lambda v, k: v >> np.uint64(k)  # noqa: E731
    mul = lambda v, m: (v * np.uint64(m)) & M32  # noqa: E731

    def mix(i):
        i = mul(i ^ p, PERM_MULS[0])
        i = i ^ sh(p, 16)
        i = i ^ sh(i & w, 4)
        i = mul(i ^ sh(p, 8), PERM_MULS[1])
        i = i ^ sh(p, 23)
        i = i ^ sh(i & w, 1)
        i = (i * (np.uint64(1) | sh(p, 27))) & M32
        i = mul(i, PERM_MULS[2])
        i = i ^ sh(i & w, 11)
        i = mul(i, PERM_MULS[3])
        i = mul(i ^ sh(p, 2), PERM_MULS[4])
        i = i ^ sh(i & w, 2)
        i = mul(i, PERM_MULS[5]) & w
        return i ^ sh(i, 5)

    y = mix(i)
    for _ in range(PERM_ROUNDS - 1):
        again = y >= np.uint64(n)
        if not again.any():
            break
        y = np.where(again, mix(y), y)
    y = np.minimum(y, np.uint64(n - 1))
    return ((y + p) & M32) % np.uint64(n)


def plain_base2(n, scramble):
    bits = np.zeros_like(n)
    for k in range(32):
        bits |= ((n >> np.uint64(k)) & np.uint64(1)) << np.uint64(31 - k)
    bits ^= scramble & M32
    u = bits.astype(np.uint32).astype(np.float32) * np.float32(2.0 ** -32)
    return np.minimum(u, ONE_MINUS_EPSILON), bits.astype(np.float64) * 2.0 ** -32


def plain_uniform(*parts):
    """24 bits of the hash as a float in [0, 1) -> (float32, float64)."""
    top = (plain_hash(*parts) >> np.uint64(8)).astype(np.float64)
    u32 = np.minimum((top.astype(np.float32) * np.float32(2.0 ** -24)), ONE_MINUS_EPSILON)
    return u32, top * 2.0 ** -24


def plain_scrambled_inverse(base, n, seed, n_bound):
    """-> (float32 in the program's order of summing, float64 exact).
    Digit k of n becomes (a d + c_k) mod base: a in 1..base-1 from the
    seed, c_k in 0..base-1 from the hash of (seed, k), for as many digits
    as n_bound - 1 has in the base; a uniform offset from the hash of
    (seed, TAIL_SALT) fills the last stratum."""
    if base == 2:
        return plain_base2(n, seed)
    digits = 0
    while base ** digits < n_bound:
        digits += 1
    b = np.uint64(base)
    a = seed % np.uint64(base - 1) + np.uint64(1)
    out32, factor32 = np.zeros(n.shape, np.float32), np.float32(1.0)
    exact = np.zeros(n.shape, np.float64)
    for k in range(digits):
        d = (a * (n % b) + plain_hash(seed, k) % b) % b
        factor32 = np.float32(factor32 * np.float32(1.0 / base))
        out32 = out32 + d.astype(np.float32) * factor32
        exact += d.astype(np.float64) * float(base) ** -(k + 1)
        n = n // b
    tail32, tail = plain_uniform(seed, TAIL_SALT)
    out32 = out32 + tail32 * factor32
    return np.minimum(out32, ONE_MINUS_EPSILON), exact + tail * float(base) ** -digits


def plain_halton_2d(spp, px, py, s, salt):
    seed = plain_hash(px, py, salt, SALT_2D_SCRAMBLE)
    sp = plain_permutation(s, spp, plain_hash(px, py, salt, SALT_2D_ORDER))
    u32 = np.zeros(px.shape, np.float32)
    v32, u64_, v64_ = u32.copy(), u32.astype(np.float64), u32.astype(np.float64)
    base_u, base_v = np.zeros(px.shape, int), np.zeros(px.shape, int)
    for k, (b1, b2) in enumerate(HALTON_PAIRS):
        m = (np.asarray(salt) % len(HALTON_PAIRS)) == k
        if not m.any():
            continue
        u32[m], u64_[m] = plain_scrambled_inverse(b1, sp[m], seed[m], spp)
        v32[m], v64_[m] = plain_scrambled_inverse(b2, sp[m], plain_hash(seed[m], SECOND_SEED_SALT), spp)
        base_u[m], base_v[m] = b1, b2
    return (u32, v32), (u64_, v64_), (base_u, base_v)


def plain_halton_1d(spp, px, py, s, salt):
    sp = plain_permutation(s, spp, plain_hash(px, py, salt, SALT_1D_ORDER))
    return plain_base2(sp, plain_hash(px, py, salt, SALT_1D_SCRAMBLE))[0]


ULP = float(np.finfo(np.float32).eps) / 2  # one ulp just under 1.0


@functools.lru_cache(maxsize=None)
def lanes(spp, n=3072):
    """A few thousand (px, py, s) over the cell's 700x700 film with every
    salt 0..95 among them, 32 lanes each."""
    rng = np.random.default_rng(spp)
    px, py = rng.integers(0, 700, n).astype(np.int32), rng.integers(0, 700, n).astype(np.int32)
    s = rng.integers(0, spp, n).astype(np.int32)
    salt = rng.permutation(np.arange(n) % 96).astype(np.int32)
    return px, py, s, salt


@functools.lru_cache(maxsize=None)
def draws(spp, mutated=False):
    """The program's draws under the array salt, one jit for the three."""
    import jax

    from tpu_pbrt.core import sampling

    def f(px, py, s, salt):
        return (*sampling.sample_2d("halton", spp, px, py, s, salt),
                sampling.sample_1d("halton", spp, px, py, s, salt))

    with pytest.MonkeyPatch.context() as mp:
        if mutated:
            mp.setattr(sampling, "_halton_which", lane_zeros_pair(sampling._halton_which))
        return [np.asarray(x) for x in jax.jit(f)(*lanes(spp))]


def lane_zeros_pair(which):
    """The mutation: every lane is given the pair of lane 0."""
    import jax.numpy as jnp

    def mutated(salt):
        w = which(salt)
        return jnp.broadcast_to(w.reshape(-1)[0], w.shape) if getattr(w, "ndim", 0) else w

    return mutated


def disagreements(spp, mutated=False):
    """Lanes of the 2D draw outside (a)'s tolerances against the plain
    generator -> (count, worst gap)."""
    px, py, s, salt = lanes(spp)
    (u32, v32), (ux, vx), (bu, bv) = plain_halton_2d(spp, px, py, s, salt)
    u, v, _ = draws(spp, mutated)
    bad, worst = 0, 0.0
    for got, ref32, exact, base in ((u, u32, ux, bu), (v, v32, vx, bv)):
        gap = np.abs(got.astype(np.float64) - ref32.astype(np.float64))
        tol = np.where(base == 2, 0.0, ULP)
        drift = np.abs(got.astype(np.float64) - np.minimum(exact, float(ONE_MINUS_EPSILON)))
        bad += int(np.count_nonzero((gap > tol) | (drift > 1e-6)))
        worst = max(worst, float(gap.max()))
    return bad, worst


@pytest.mark.parametrize("spp", [8, 16, 12])
def test_array_salt_draws_are_the_plain_generators(spp):
    bad, worst = disagreements(spp)
    assert bad == 0, (bad, worst)
    px, py, s, salt = lanes(spp)
    assert np.array_equal(draws(spp)[2], plain_halton_1d(spp, px, py, s, salt))
    u, v, _ = draws(spp)
    assert 0.0 <= min(u.min(), v.min()) and max(u.max(), v.max()) < 1.0


@pytest.mark.parametrize("spp", [8, 16, 12])
def test_array_salt_equals_scalar_salt_on_every_lane(spp):
    """Both ways a caller may hold a salt that is not a Python int: the
    traced scalar (the fixed-batch loop's `lax.switch`) and the constant."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.core import sampling

    px, py, s, salt = lanes(spp)
    u, v, w = draws(spp)
    traced = jax.jit(lambda px, py, s, k: (
        *sampling.sample_2d("halton", spp, px, py, s, k), sampling.sample_1d("halton", spp, px, py, s, k)))
    for k in range(96):
        m = salt == k
        assert m.sum() == 32
        args = (jnp.asarray(px[m]), jnp.asarray(py[m]), jnp.asarray(s[m]))
        us, vs, ws = traced(*args, jnp.int32(k))
        assert np.array_equal(us, u[m]) and np.array_equal(vs, v[m]) and np.array_equal(ws, w[m]), k
        if k < 12:  # each pair twice under a static salt
            uc, vc = sampling.sample_2d("halton", spp, *args, k)
            assert np.array_equal(uc, u[m]) and np.array_equal(vc, v[m]), k


def test_one_pair_for_all_lanes_is_not_the_plain_generator():
    bad, _ = disagreements(8, mutated=True)
    # lane 0's pair is right for a sixth of the lanes
    assert bad > lanes(8)[0].size // 2


def test_every_cell_of_a_bases_grid_is_equally_likely_over_pixels():
    """Unbiasedness: a draw's marginal over the seeds (the pixels) is
    uniform. 8 indices of a base-3, base-5 or base-7 net never fill the
    b x b grid of one pixel; over pixels every cell must still come up as
    often as every other: within 5 sigma of a count's own noise."""
    import jax
    import jax.numpy as jnp

    from tpu_pbrt.core import sampling

    n, spp = 1 << 18, 8
    rng = np.random.default_rng(7)
    px, py = (jnp.asarray(rng.integers(0, 700, n), jnp.int32) for _ in range(2))
    s = jnp.asarray(rng.integers(0, spp, n), jnp.int32)
    salt = jnp.asarray(rng.integers(0, 96, n), jnp.int32)
    u, v = (np.asarray(x) for x in jax.jit(lambda *a: sampling.sample_2d("halton", spp, *a))(px, py, s, salt))
    which = np.asarray(salt) % len(HALTON_PAIRS)
    for k, pair in enumerate(HALTON_PAIRS):
        for x, base in zip((u[which == k], v[which == k]), pair):
            cells = base * base if base > 2 else 16
            count = np.bincount(np.floor(x * cells).astype(int), minlength=cells)
            want = x.size / cells
            assert np.abs(count - want).max() < 5 * np.sqrt(want), (pair, base, count / want)
            assert abs(x.mean() - 0.5) < 5 * np.sqrt(1 / 12 / x.size)


# -- (b) the path -----------------------------------------------------------

CELL = "killeroo-halton-frames-1chip"
FILM_RTOL, FILM_ATOL = 1e-5, 1e-6


@pytest.fixture(scope="module")
def scene_file():
    """The `test` preset's scene of `killeroo-halton` at 8 samples a pixel,
    written as the benchmark writes it (a .pbrt file and a binary PLY)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import run as harness

    config = harness.load_json(ROOT, "benchmark", "configs", "killeroo-halton.json")
    config = harness.merge(config, config["presets"]["test"])
    config["pixelsamples"] = 8
    assert config["sampler"] == "halton" and config["xresolution"] == config["yresolution"] == 32
    work = os.path.join(ROOT, ".bench_work", "test_halton_reference")
    shutil.rmtree(work, ignore_errors=True)
    desc = harness.load_module("scenes", config["scene_writer"]).build(config, 5)
    yield harness.load_module("", "scenedesc").write_scene(desc, work, "scene")
    shutil.rmtree(work, ignore_errors=True)
    sys.path.remove(os.path.join(ROOT, "benchmark"))


def render(path, regen, mutated=False):
    from tpu_pbrt import config
    from tpu_pbrt.core import sampling
    from tpu_pbrt.scene.api import Options, compile_file

    with pytest.MonkeyPatch.context() as mp:
        for k, v in {"TPU_PBRT_REGEN": regen, "TPU_PBRT_CHUNK": "3072", "TPU_PBRT_POOL": "512"}.items():
            mp.setenv(k, v)
        if mutated:
            mp.setattr(sampling, "_halton_which", lane_zeros_pair(sampling._halton_which))
        config.reload()
        try:
            import jax

            scene, integ = compile_file(path, Options(quiet=True))
            r = integ.render(scene)
            plan = integ.prepare_chunks(scene)
            assert (plan.chunk, plan.n_chunks, plan.total) == (3072, 3, 8192)
            return r, np.asarray(jax.device_get(r.film_state.weight))
        finally:
            mp.undo()
            config.reload()


@pytest.fixture(scope="module")
def pool(scene_file):
    return render(scene_file, "1")


@pytest.fixture(scope="module")
def fixed(scene_file):
    return render(scene_file, "0")


def test_halton_renders_through_the_pool_and_counts_its_pairs(pool):
    r, _ = pool
    assert r.stats["regen"] and r.stats["pool"] == 512
    c = r.stats["telemetry"]["counters"]
    # two pairs a live lane a wave; a live lane traces one ray and, where
    # its last bounce sampled a light, that bounce's shadow ray
    assert c["halton_pairs"] % 2 == 0
    assert c["rays_traced"] / 2 < c["halton_pairs"] / 2 <= c["rays_traced"] == r.rays_traced
    # lanes at mixed depths did share waves: the pool was refilled in flight
    assert r.stats["mean_wave_occupancy"] > 0.5


def test_the_ragged_last_dispatch_leaves_every_pixel_its_samples(pool):
    """8,192 samples in dispatches of 3,072: the third holds 2,048 and
    1,024 work items past the film's end."""
    r, weight = pool
    assert r.completed_fraction == 1.0 and not r.stats.get("truncated_chunks")
    assert weight.shape == (32, 32) and np.array_equal(weight, np.full((32, 32), 8.0, weight.dtype))
    assert r.stats["telemetry"]["counters"]["film_deposits"] == 32 * 32 * 8


def test_the_pools_halton_film_is_the_fixed_batch_loops(pool, fixed):
    (rp, wp), (rf, wf) = pool, fixed
    assert "regen" not in rf.stats
    assert rp.rays_traced == rf.rays_traced > 32 * 32 * 8
    assert np.array_equal(wp, wf)
    a, b = np.asarray(rp.image, np.float64), np.asarray(rf.image, np.float64)
    assert b.max() > 1.0
    np.testing.assert_allclose(a, b, rtol=FILM_RTOL, atol=FILM_ATOL)


def test_one_pair_for_all_lanes_is_not_the_fixed_batch_loops_film(scene_file, fixed):
    rm, _ = render(scene_file, "1", mutated=True)
    rf, _ = fixed
    assert rm.stats["regen"]
    a, b = np.asarray(rm.image, np.float64), np.asarray(rf.image, np.float64)
    outside = np.abs(a - b) > FILM_ATOL + FILM_RTOL * np.abs(b)
    # other samples of the same pixels: most pixels move, and paths end elsewhere
    assert outside.any(axis=-1).mean() > 0.25, outside.mean()
    assert rm.rays_traced != rf.rays_traced
