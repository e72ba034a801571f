"""Device-side sampling: counter-based RNG, warps, distributions, MIS.

Capability match for pbrt-v3:
- src/core/rng.h RNG (PCG32): replaced TPU-first by a *stateless*
  counter-based generator — every random number is a pure hash of
  (pixel_index, sample_index, dimension) — so a wavefront of a million rays
  draws its samples with no per-lane mutable state, renders are bit-exact
  reproducible, and checkpoint/resume only needs the sample-range cursor
  (SURVEY.md §5.4).
- src/core/sampling.{h,cpp}: ConcentricSampleDisk, CosineSampleHemisphere,
  UniformSample{Sphere,Hemisphere,Triangle,Cone}, Distribution1D/2D,
  Balance/PowerHeuristic, StratifiedSample via index permutation.
- src/core/lowdiscrepancy.h RadicalInverse / scrambled variants (the
  Halton/(0,2)-sequence samplers in samplers/ build on these).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.obs import phases as ph

ONE_MINUS_EPSILON = np.float32(0.99999994)


# -------------------------------------------------------------------------
# Stateless RNG. pcg-style integer hash over a mixed 32-bit counter.
# -------------------------------------------------------------------------

def _mix(h, v):
    """One round of bob-jenkins-style avalanche combine (uint32)."""
    h = (h ^ v) * jnp.uint32(0x9E3779B1)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    return h ^ (h >> 13)


def hash_u32(*parts) -> jnp.ndarray:
    """Hash any number of integer parts to uint32 (broadcasts)."""
    h = jnp.uint32(0x2545F491)
    for p in parts:
        h = _mix(h, jnp.asarray(p).astype(jnp.uint32))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def uniform_float(*parts) -> jnp.ndarray:
    """U[0,1) from hashed parts; strictly < 1 (pbrt OneMinusEpsilon clamp)."""
    u = hash_u32(*parts)
    f = (u >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    return jnp.minimum(f, ONE_MINUS_EPSILON)


def uniform_2d(*parts):
    """Two independent U[0,1) streams distinguished by a trailing salt."""
    return uniform_float(*parts, 0x5B3C), uniform_float(*parts, 0xA7E9)


# -------------------------------------------------------------------------
# Warps (pbrt sampling.cpp)
# -------------------------------------------------------------------------

def concentric_sample_disk(u1, u2):
    """Shirley–Chiu concentric map; returns (x, y)."""
    ox = 2.0 * u1 - 1.0
    oy = 2.0 * u2 - 1.0
    degenerate = (ox == 0.0) & (oy == 0.0)
    use_x = jnp.abs(ox) > jnp.abs(oy)
    r = jnp.where(use_x, ox, oy)
    theta = jnp.where(
        use_x,
        (jnp.pi / 4.0) * (oy / jnp.where(ox == 0.0, 1.0, ox)),
        (jnp.pi / 2.0) - (jnp.pi / 4.0) * (ox / jnp.where(oy == 0.0, 1.0, oy)),
    )
    x = jnp.where(degenerate, 0.0, r * jnp.cos(theta))
    y = jnp.where(degenerate, 0.0, r * jnp.sin(theta))
    return x, y


def cosine_sample_hemisphere(u1, u2):
    """Malley's method; returns direction (...,3) in local frame, z up."""
    x, y = concentric_sample_disk(u1, u2)
    z = jnp.sqrt(jnp.maximum(0.0, 1.0 - x * x - y * y))
    return jnp.stack([x, y, z], axis=-1)


def cosine_hemisphere_pdf(cos_theta):
    return cos_theta * (1.0 / jnp.pi)


def uniform_sample_hemisphere(u1, u2):
    z = u1
    r = jnp.sqrt(jnp.maximum(0.0, 1.0 - z * z))
    phi = 2.0 * jnp.pi * u2
    return jnp.stack([r * jnp.cos(phi), r * jnp.sin(phi), z], axis=-1)


UNIFORM_HEMISPHERE_PDF = 1.0 / (2.0 * np.pi)
UNIFORM_SPHERE_PDF = 1.0 / (4.0 * np.pi)


def uniform_sample_sphere(u1, u2):
    z = 1.0 - 2.0 * u1
    r = jnp.sqrt(jnp.maximum(0.0, 1.0 - z * z))
    phi = 2.0 * jnp.pi * u2
    return jnp.stack([r * jnp.cos(phi), r * jnp.sin(phi), z], axis=-1)


def uniform_sample_triangle(u1, u2):
    """Returns barycentrics (b0, b1) (sqrt warp)."""
    su0 = jnp.sqrt(u1)
    return 1.0 - su0, u2 * su0


def uniform_sample_cone(u1, u2, cos_theta_max):
    cos_theta = (1.0 - u1) + u1 * cos_theta_max
    sin_theta = jnp.sqrt(jnp.maximum(0.0, 1.0 - cos_theta * cos_theta))
    phi = 2.0 * jnp.pi * u2
    return jnp.stack(
        [sin_theta * jnp.cos(phi), sin_theta * jnp.sin(phi), cos_theta], axis=-1
    )


def uniform_cone_pdf(cos_theta_max):
    return 1.0 / (2.0 * jnp.pi * jnp.maximum(1.0 - cos_theta_max, 1e-9))


# -------------------------------------------------------------------------
# MIS heuristics (pbrt sampling.h)
# -------------------------------------------------------------------------

def balance_heuristic(nf, f_pdf, ng, g_pdf):
    return (nf * f_pdf) / jnp.maximum(nf * f_pdf + ng * g_pdf, 1e-20)


def power_heuristic(nf, f_pdf, ng, g_pdf):
    f = nf * f_pdf
    g = ng * g_pdf
    return (f * f) / jnp.maximum(f * f + g * g, 1e-20)


# -------------------------------------------------------------------------
# Stratification on a counter-based stream. A wavefront renderer cannot
# carry pbrt's per-pixel sample arrays, so stratified dimensions are formed
# directly from the sample index: for spp = sx*sy, sample s of pixel p gets
# cell perm_p(s) of an sx×sy grid, jittered. perm_p is a per-pixel
# Feistel-style permutation so cross-dimension correlation is broken
# (pbrt's Shuffle equivalent, but stateless).
# -------------------------------------------------------------------------

def permutation_element(i, n, seed):
    """Stateless random permutation of [0,n): Kensler's hash permutation
    (Correlated Multi-Jittered Sampling, also pbrt-v4 PermutationElement) —
    an invertible mix cycle-walked on the next power of two. The unbounded
    do-while becomes 16 fixed masked rounds (miss probability < 2^-16 per
    element; each round rejects with p < 1/2)."""
    n = jnp.asarray(n, jnp.uint32)
    i = jnp.asarray(i, jnp.uint32)
    p = jnp.asarray(seed, jnp.uint32)
    w = n - 1
    w = w | (w >> 1)
    w = w | (w >> 2)
    w = w | (w >> 4)
    w = w | (w >> 8)
    w = w | (w >> 16)

    def mix(i):
        i = i ^ p
        i = i * jnp.uint32(0xE170893D)
        i = i ^ (p >> 16)
        i = i ^ ((i & w) >> 4)
        i = i ^ (p >> 8)
        i = i * jnp.uint32(0x0929EB3F)
        i = i ^ (p >> 23)
        i = i ^ ((i & w) >> 1)
        i = i * (jnp.uint32(1) | (p >> 27))
        i = i * jnp.uint32(0x6935FA69)
        i = i ^ ((i & w) >> 11)
        i = i * jnp.uint32(0x74DCCA23)
        i = i ^ (p >> 2)
        i = i * jnp.uint32(0x9E501CC3)
        i = i ^ ((i & w) >> 2)
        i = i * jnp.uint32(0xC860A3DF)
        i = i & w
        return i ^ (i >> 5)

    y = mix(i)
    for _ in range(15):
        y = jnp.where(y >= n, mix(y), y)
    return (jnp.minimum(y, n - 1) + p) % n


def stratified_1d(sample_index, n_strata, *key_parts):
    """Jittered stratified sample: cell = perm(sample_index), jitter inside."""
    seed = hash_u32(*key_parts, 0x517A)
    cell = permutation_element(sample_index, n_strata, seed).astype(jnp.float32)
    u = uniform_float(*key_parts, 0x11D7)
    return jnp.minimum((cell + u) / n_strata, ONE_MINUS_EPSILON)


def stratified_2d(sample_index, sx, sy, *key_parts):
    """Jittered 2D stratification over an sx×sy grid."""
    seed = hash_u32(*key_parts, 0x2F83)
    cell = permutation_element(sample_index, sx * sy, seed)
    cx = (cell % jnp.uint32(sx)).astype(jnp.float32)
    cy = (cell // jnp.uint32(sx)).astype(jnp.float32)
    u1 = uniform_float(*key_parts, 0x9E01)
    u2 = uniform_float(*key_parts, 0xC6A3)
    return (
        jnp.minimum((cx + u1) / sx, ONE_MINUS_EPSILON),
        jnp.minimum((cy + u2) / sy, ONE_MINUS_EPSILON),
    )


# -------------------------------------------------------------------------
# Radical inverse / scrambling (pbrt lowdiscrepancy.h) — bases 2 and 3
# device-side; arbitrary-base host-side for Halton tables.
# -------------------------------------------------------------------------

def reverse_bits_32(n):
    n = jnp.asarray(n, jnp.uint32)
    n = (n << 16) | (n >> 16)
    n = ((n & jnp.uint32(0x00FF00FF)) << 8) | ((n & jnp.uint32(0xFF00FF00)) >> 8)
    n = ((n & jnp.uint32(0x0F0F0F0F)) << 4) | ((n & jnp.uint32(0xF0F0F0F0)) >> 4)
    n = ((n & jnp.uint32(0x33333333)) << 2) | ((n & jnp.uint32(0xCCCCCCCC)) >> 2)
    n = ((n & jnp.uint32(0x55555555)) << 1) | ((n & jnp.uint32(0xAAAAAAAA)) >> 1)
    return n


def radical_inverse_base2(n, scramble=0):
    """Van der Corput, with optional XOR scramble (uint32)."""
    bits = reverse_bits_32(n) ^ jnp.asarray(scramble, jnp.uint32)
    return jnp.minimum(
        bits.astype(jnp.float32) * jnp.float32(2.3283064365386963e-10), ONE_MINUS_EPSILON
    )


def sobol_2d(n, scramble_x=0, scramble_y=0):
    """First two dimensions of the Sobol' sequence ((0,2)-sequence), as used
    by pbrt's ZeroTwoSequenceSampler (gray-code matrices for dim 2)."""
    x = reverse_bits_32(n) ^ jnp.asarray(scramble_x, jnp.uint32)

    # dimension 2: Sobol' direction numbers for the second dimension
    v = jnp.uint32(1 << 31)
    n = jnp.asarray(n, jnp.uint32)
    y = jnp.zeros_like(n)
    for i in range(32):
        y = jnp.where((n >> i) & 1, y ^ v, y)
        v = v ^ (v >> 1)
    y = y ^ jnp.asarray(scramble_y, jnp.uint32)
    to_f = jnp.float32(2.3283064365386963e-10)
    return (
        jnp.minimum(x.astype(jnp.float32) * to_f, ONE_MINUS_EPSILON),
        jnp.minimum(y.astype(jnp.float32) * to_f, ONE_MINUS_EPSILON),
    )


def _primes(n):
    out, c = [], 2
    while len(out) < n:
        if all(c % p for p in out):
            out.append(c)
        c += 1
    return out


#: prime bases for the Halton sampler's dimensions (primes.cpp equivalent,
#: generated instead of tabulated)
PRIMES = _primes(64)


def radical_inverse_prime(base: int, n, scramble_seed=None, n_bound=None):
    """ScrambledRadicalInverse (lowdiscrepancy.h) for a STATIC prime base:
    digit reversal in the given base, with an optional seeded scramble.

    The scramble maps digit k of n to (a * digit + c_k) mod b: one
    multiplier a in 1..b-1 for the stream (a bijection on the digits, so
    the first b^m indices still fill the b^m strata one each) and an
    offset c_k of ITS OWN for every digit position, then adds a uniform
    offset inside the last stratum. Each point is then uniform on [0, 1)
    over the seeds, which is what makes an estimate averaged over seeded
    streams unbiased: with one offset for every position a point's digits
    are tied to each other, the cells of the b x b grid are not equally
    likely, and 8 indices of a base-3 net err the same way in every pixel
    (tests/test_halton_reference.py holds the cells equal).

    `n_bound` (static) promises n < n_bound: only the digits n_bound - 1
    can have are taken (a TPU has no integer divide: the remainders and
    quotients of all 12 to 21 positions are long sequences of vector
    operations each); the uniform offset stands for the rest, as it does
    past the last position without a bound."""
    if base == 2:
        scr = 0 if scramble_seed is None else scramble_seed
        return radical_inverse_base2(n, scr)
    n = jnp.asarray(n, jnp.uint32)
    digits = int(np.ceil(32 / np.log2(base)))
    if n_bound is not None:
        live = 0
        while base ** live < int(n_bound):
            live += 1
        digits = min(live, digits)
    inv_base = np.float32(1.0 / base)
    if scramble_seed is not None:
        seed = jnp.asarray(scramble_seed, jnp.uint32)
        a = (seed % jnp.uint32(base - 1)) + jnp.uint32(1)  # coprime to prime b
    out = jnp.zeros(jnp.shape(n), jnp.float32)
    factor = np.float32(1.0)
    for k in range(digits):
        d = n % jnp.uint32(base)
        if scramble_seed is not None:
            d = (a * d + hash_u32(seed, k) % jnp.uint32(base)) % jnp.uint32(base)
        factor = factor * inv_base
        out = out + d.astype(jnp.float32) * factor
        n = n // jnp.uint32(base)
    if scramble_seed is not None:
        out = out + uniform_float(seed, _TAIL_SALT) * factor
    return jnp.minimum(out, ONE_MINUS_EPSILON)


#: hash salt of the scrambled radical inverse's offset inside its last stratum
_TAIL_SALT = 0x7A11


# -------------------------------------------------------------------------
# True Sobol' sampler (samplers/sobol.cpp + core/sobolmatrices.cpp
# capability; VERDICT r4 #7). pbrt ships Joe-Kuo generator matrices as a
# 1024-dim table; this build GENERATES its own direction numbers at
# import (first-primitive-polynomial-per-degree over GF(2), hash-seeded
# odd initial m values) and compensates the unoptimized initialization
# with per-dimension fast-Owen scrambling (Laine-Karras) — randomized
# QMC keeps every dimension a base-2 (0,1)-sequence regardless of the
# m choice, which is what the stratification tests pin. The SobolSampler
# global index remap (SobolIntervalToIndex) is reproduced exactly, with
# the van-der-Corput inverse matrices computed from THESE matrices so
# the remap is self-consistent: sample `frame` of pixel (px, py) gets
# the unique global index whose first two dimensions land in that pixel.
# -------------------------------------------------------------------------

N_SOBOL_DIMS = 64
_SOBOL_BITS = 32


def _pascal_matrix():
    """MSB-aligned direction numbers of the Pascal (binomial mod 2)
    matrix — the classical Sobol dimension 2, whose pairing with the
    van der Corput identity is an exact (0,2)-sequence."""
    v = np.zeros(_SOBOL_BITS, np.uint64)
    m = 1
    ms = [1]
    for i in range(1, _SOBOL_BITS):
        m = ms[-1] ^ (ms[-1] << 1)  # x+1 recurrence => Pascal columns
        ms.append(m & ((1 << (i + 1)) - 1))
    for k in range(_SOBOL_BITS):
        v[k] = np.uint64(ms[k]) << np.uint64(31 - k)
    return v


def _lower_tri_scramble(v_cols, seed):
    """Apply a hash-seeded unit-lower-triangular (MSB-first) linear
    scramble L to a 32-column direction matrix: a LINEAR Owen scramble,
    which preserves every (t,m,s)-net property of the sequence while
    decorrelating it from other scrambled copies."""
    rows = np.zeros(_SOBOL_BITS, np.uint64)
    state = np.uint64(seed * 2654435761 % (1 << 32))
    for p in range(_SOBOL_BITS):
        state = np.uint64((int(state) * 6364136223846793005 + 1442695040888963407) % (1 << 64))
        rand_low = int(state >> np.uint64(33)) & ((1 << (31 - p)) - 1)
        rows[p] = (np.uint64(1) << np.uint64(31 - p)) | np.uint64(rand_low)
    out = np.zeros_like(v_cols)
    for k in range(_SOBOL_BITS):
        acc = np.uint64(0)
        col = int(v_cols[k])
        for p in range(_SOBOL_BITS):
            if (col >> (31 - p)) & 1:
                acc ^= rows[p]
        out[k] = acc
    return out


def _build_sobol_matrices():
    """(N_SOBOL_DIMS, 32) uint32 direction-number table, MSB-aligned.

    dims 0/1: van der Corput + Pascal (the exact (0,2) pair the global
    pixel remap inverts). Every later CONSUMED-TOGETHER pair
    (2k, 2k+1) is an independently linear-Owen-scrambled copy of that
    same pair, so each 2D decision drawn through sample_2d keeps the
    exact (0,2)-sequence property while distinct decisions decorrelate
    (pbrt's Joe-Kuo table achieves pairwise quality by optimized
    initialization; the scrambled-copy construction achieves it by
    inheritance)."""
    v = np.zeros((N_SOBOL_DIMS, _SOBOL_BITS), np.uint64)
    for k in range(_SOBOL_BITS):
        v[0, k] = np.uint64(1) << np.uint64(31 - k)
    v[1] = _pascal_matrix()
    for pair in range(1, N_SOBOL_DIMS // 2):
        v[2 * pair] = _lower_tri_scramble(v[0], 2 * pair + 17)
        v[2 * pair + 1] = _lower_tri_scramble(v[1], 2 * pair + 18)
    return v.astype(np.uint32)


_SOBOL_V = _build_sobol_matrices()
_SOBOL_V_I32 = _SOBOL_V.view(np.int32)


def _sobol_dev():
    # numpy -> fresh constant per trace (a cached device array would
    # leak across jit traces)
    return jnp.asarray(_SOBOL_V_I32)


def _gf2_inv(mat):
    """Invert a binary matrix (lists of row bitmasks) over GF(2)."""
    n = len(mat)
    a = list(mat)
    inv = [1 << i for i in range(n)]
    for col in range(n):
        piv = next(r for r in range(col, n) if (a[r] >> col) & 1)
        a[col], a[piv] = a[piv], a[col]
        inv[col], inv[piv] = inv[piv], inv[col]
        for r in range(n):
            if r != col and ((a[r] >> col) & 1):
                a[r] ^= a[col]
                inv[r] ^= inv[col]
    return inv


class _RemapTables:
    """Per-resolution (m = log2) tables for SobolIntervalToIndex."""

    cache: dict = {}

    @classmethod
    def get(cls, m):  # jaxlint: disable=JL-SYNC,JL-MUT — host table bake
        if m in cls.cache:
            return cls.cache[m]
        # rows: for each low index bit c < 2m, the (x|y) bits it produces
        # through dims 0/1 (x from dim 0, y from dim 1), packed y-low.
        # Output bit layout: b = (px << m) | py.
        fwd = []
        for c in range(2 * m):
            xv = int(_SOBOL_V[0, c]) >> (32 - m)  # top m bits
            yv = int(_SOBOL_V[1, c]) >> (32 - m)
            fwd.append((xv << m) | yv)
        inv = _gf2_inv(fwd)  # maps target (x|y) bits -> low index bits
        # delta rows: contribution of frame bit c (index bits >= 2m)
        # to the pixel bits
        hi = []
        for c in range(_SOBOL_BITS - 2 * m):
            xv = int(_SOBOL_V[0, c + 2 * m]) >> (32 - m)
            yv = int(_SOBOL_V[1, c + 2 * m]) >> (32 - m)
            hi.append((xv << m) | yv)
        # cache NUMPY tables: device arrays created inside a jit trace
        # would leak tracers into later traces
        tabs = (
            np.asarray(hi, np.int64).astype(np.int32),
            np.asarray(inv, np.int64).astype(np.int32),
        )
        cls.cache[m] = tabs
        return tabs


def sobol_interval_to_index(m: int, frame, px, py):
    """SobolSampler's global index remap (sobolmatrices' VdCSobolMatrices
    path, rebuilt from this module's matrices): the index whose dims 0/1
    land sample `frame` in pixel (px, py) of the 2^m x 2^m grid."""
    if m == 0:
        return frame
    hi, inv = _RemapTables.get(m)
    m2 = 2 * m
    index = frame << m2
    delta = jnp.zeros_like(px)
    for c in range(hi.shape[0]):
        delta = delta ^ jnp.where((frame >> c) & 1 != 0, int(hi[c]), 0)
    b = ((px << m) | py) ^ delta
    for c in range(m2):
        index = index ^ jnp.where((b >> c) & 1 != 0, int(inv[c]), 0)
    return index


def _sobol_raw_bits(index, dim):
    """32-bit Sobol value of `index` (i32, global) in dimension `dim`,
    before scrambling. `dim` may be a static int, a traced scalar, or a
    PER-LANE array (the persistent-wavefront pool mixes path depths in
    one wave, so each lane salts its own dimension)."""
    dim = jnp.asarray(dim, jnp.int32) % N_SOBOL_DIMS
    if dim.ndim == 0:
        row = jax.lax.dynamic_slice(
            _sobol_dev(), (dim, 0), (1, _SOBOL_BITS)
        )[0]
        cols = [row[k] for k in range(_SOBOL_BITS)]
    else:
        rows = jnp.take(_sobol_dev(), dim, axis=0)  # (..., 32)
        cols = [rows[..., k] for k in range(_SOBOL_BITS)]
    out = jnp.zeros_like(index)
    for k in range(_SOBOL_BITS):
        out = out ^ jnp.where((index >> k) & 1 != 0, cols[k], 0)
    return out


def _fast_owen(bits, seed):
    """Laine-Karras hash-based nested scramble on MSB-aligned bits."""
    v = reverse_bits_32(bits)
    v = v + seed.astype(jnp.uint32)
    v = v ^ (v * jnp.uint32(0x6C50B47C))
    v = v ^ (v * jnp.uint32(0xB82F1E52))
    v = v ^ (v * jnp.uint32(0xC7AFE638))
    v = v ^ (v * jnp.uint32(0x8D22F6E6))
    return reverse_bits_32(v)


def sobol_sample(index, dim, scramble_seed=None):
    """U[0,1) Sobol' sample of global `index` in dimension `dim`, with
    per-dimension fast-Owen scrambling when a seed is given."""
    bits = _sobol_raw_bits(index, dim).astype(jnp.uint32)
    if scramble_seed is not None:
        bits = _fast_owen(bits, scramble_seed)
    return jnp.minimum(
        bits.astype(jnp.float32) * jnp.float32(2.3283064365386963e-10),
        jnp.float32(1.0 - 1e-7),
    )


# -------------------------------------------------------------------------
# Sampler plugin dispatch (samplers/{random,stratified,zerotwosequence,
# sobol,halton,maxmin}.cpp; VERDICT r3 #7). The wavefront redesign keeps
# every draw a pure function of (px, py, sample index, dimension salt);
# what the plugin selects is the STRUCTURE of each dimension's stream:
#
# - random:      the counter-hash (rng.h equivalent)
# - stratified:  jittered strata over the spp range, shuffled per
#                (pixel, dimension) so dimensions pair independently
# - 02sequence/lowdiscrepancy/sobol/maxmindist: xor-scrambled (0,2)
#   Sobol' pairs, sample order shuffled per (pixel, dimension) — pbrt's
#   ZeroTwoSequenceSampler decorrelates dimensions exactly this way
#   (shuffled independently per dimension request). maxmindist's bespoke
#   generator matrix is approximated by the (0,2) sequence (documented).
# - halton:      per-pixel scrambled Halton — dimension pairs use the
#   prime bases of _HALTON_PAIRS at the SAME index (jointly LD), with
#   per-pixel digit scrambles replacing pbrt's global pixel stride walk
#   (lowdiscrepancy.cpp: equivalent stratification, no 2^k image tiling).
# -------------------------------------------------------------------------

#: joint 2D bases for halton pair-dimensions — LOW primes only (base-b
#: stratification is only perfect at b^k samples, so large bases stratify
#: poorly at render spp; pair reuse is decorrelated by the per-dimension
#: sample-order shuffle)
_HALTON_PAIRS = [(2, 3), (5, 7), (3, 5), (7, 2), (2, 5), (3, 7)]


def _halton_which(salt):
    """Index into _HALTON_PAIRS of dimension pair `salt` (an int, a traced
    scalar or a per-lane array)."""
    return salt % len(_HALTON_PAIRS)


def _halton_pair(spp: int, px, py, s, salt):
    """The halton sampler's 2D draw for dimension pair `salt`: the joint
    (b1, b2) = _HALTON_PAIRS[salt % 6] pair at a SHARED shuffled index.
    The pair keeps its joint 2D low discrepancy (same point set,
    reordered) and different pair-dimensions decorrelate through the
    shuffle.

    `salt` is a Python int, a traced scalar (the fixed-batch loop's
    bounce * DIMS_PER_BOUNCE: a `lax.switch` runs the one pair) or a
    PER-LANE array (the pool's depth * DIMS_PER_BOUNCE: lanes at mixed
    depths share a wave, so each lane picks its own pair). Under an
    array the inverse of every base a first or a second coordinate can
    have is computed once for the whole wave (six prime-base inverses and
    the two bit reversals, where the six pairs one after the other would
    be nine and three) and each lane SELECTS its pair's two: a select
    copies bits, so a lane draws exactly what the scalar salt of the same
    value draws."""
    seed = hash_u32(px, py, salt, 0x62B)
    # the second coordinate's scramble is a whole word of its own: a
    # shifted copy of the first's would leave a base-2 coordinate's top
    # bits unscrambled, the same strata corners in every pixel
    seed2 = hash_u32(seed, 0x5EC)
    sp = permutation_element(s, spp, hash_u32(px, py, salt, 0xD47))

    def pair(b1, b2):
        return (
            radical_inverse_prime(b1, sp, seed, n_bound=spp),
            radical_inverse_prime(b2, sp, seed2, n_bound=spp),
        )

    if isinstance(salt, (int, np.integer)):
        return pair(*_HALTON_PAIRS[_halton_which(salt)])
    which = jnp.asarray(_halton_which(salt), jnp.int32)
    if which.ndim == 0:
        uv = jax.lax.switch(
            which, [lambda b=b: jnp.stack(pair(*b)) for b in _HALTON_PAIRS]
        )
        return uv[0], uv[1]
    out = []
    for coord, scramble in ((0, seed), (1, seed2)):
        inverse = {
            b: radical_inverse_prime(b, sp, scramble, n_bound=spp)
            for b in sorted({p[coord] for p in _HALTON_PAIRS})
        }
        u = inverse[_HALTON_PAIRS[0][coord]]
        for k in range(1, len(_HALTON_PAIRS)):
            u = jnp.where(which == k, inverse[_HALTON_PAIRS[k][coord]], u)
        out.append(u)
    return out[0], out[1]


def sobol_resolution_log2(res_xy) -> int:
    """The SobolSampler's pixel grid: the smallest 2^m x 2^m grid
    covering the film (sobol.cpp's resolution rounding). Returns m —
    callers hold it (it is static per scene) and pass it into the traced
    film-dimension remap explicitly; module-global trace-time state here
    would silently bake a stale grid into any new jit closure (ADVICE
    r4)."""
    m = 0
    while (1 << m) < max(int(res_xy[0]), int(res_xy[1])):
        m += 1
    return m


def _sobol_dim_draw(px, py, s, salt, which, spp):
    """Decision-dimension Sobol draw: the consumed-together pair
    (2k, 2k+1) for dimension-salt k — an exact (0,2)-sequence by
    construction — indexed by the PER-PIXEL sample rank (shuffled per
    pixel+salt) with per-pixel fast-Owen scrambles. This is the padded
    construction (pbrt-v4's PaddedSobolSampler): a pixel's spp draws
    stratify perfectly in every 2D decision, and pixels decorrelate.
    pbrt-v3's global-index consumption of Joe-Kuo dims needs table
    quality this build's generated matrices cannot promise jointly
    with the pixel dims; only the FILM dims ride the global remap
    (sobol_interval_to_index), which is where the global sequence has
    provable structure here."""
    n_pairs = N_SOBOL_DIMS // 2 - 1
    sp = permutation_element(s, spp, hash_u32(px, py, salt, 0x5A11))
    if isinstance(salt, (int, np.integer)):
        dim = 2 + 2 * (int(salt) % n_pairs) + which
    else:
        dim = 2 + 2 * (jnp.asarray(salt, jnp.int32) % n_pairs) + which
    seed = hash_u32(px, py, salt, 0x193 + 0x7FEB * which).astype(jnp.uint32)
    return sobol_sample(sp, dim, seed)


def sample_1d(kind: str, spp: int, px, py, s, salt):
    """One U[0,1) draw for dimension `salt` under sampler `kind`."""
    if kind == "random" or spp <= 1:
        return uniform_float(px, py, s, salt)
    if kind == "sobol":
        return _sobol_dim_draw(px, py, s, salt, 0, spp)
    if kind == "stratified":
        return stratified_1d(s, spp, px, py, salt)
    if kind == "halton":
        # 1D dimensions use the base-2 sequence with a per-dimension
        # sample-order shuffle + XOR scramble: base 2 stratifies perfectly
        # at the power-of-two spp renders use (a base-b sequence only
        # stratifies at b^k samples, and a digit scramble turns a partial
        # prefix into a random stratum subset), while the shuffle
        # decorrelates dimensions (the padded-sampler construction).
        # Halton's distinguishing JOINT low-discrepancy lives in the
        # prime-base pairs of sample_2d.
        with jax.named_scope(ph.SAMPLER_HALTON):
            sp = permutation_element(s, spp, hash_u32(px, py, salt, 0x6E5))
            return radical_inverse_base2(sp, hash_u32(px, py, salt, 0x4A1))
    # (0,2)-family: shuffled + scrambled van der Corput
    sp = permutation_element(s, spp, hash_u32(px, py, salt, 0x7F2))
    return radical_inverse_base2(sp, hash_u32(px, py, salt, 0x9D3))


def sample_2d(kind: str, spp: int, px, py, s, salt):
    """A consumed-together 2D pair for dimension pair `salt`."""
    if kind == "random" or spp <= 1:
        return (
            uniform_float(px, py, s, salt),
            uniform_float(px, py, s, salt + 0x151),
        )
    if kind == "sobol":
        return (
            _sobol_dim_draw(px, py, s, salt, 0, spp),
            _sobol_dim_draw(px, py, s, salt, 1, spp),
        )
    if kind == "stratified":
        sx = max(int(np.sqrt(spp)), 1)
        sy = (spp + sx - 1) // sx  # sx*sy >= spp: permutation stays a bijection
        return stratified_2d(s, sx, sy, px, py, salt)
    if kind == "halton":
        with jax.named_scope(ph.SAMPLER_HALTON):
            return _halton_pair(spp, px, py, s, salt)
    sp = permutation_element(s, spp, hash_u32(px, py, salt, 0x3C5))
    return sobol_2d(
        sp, hash_u32(px, py, salt, 0x8E7), hash_u32(px, py, salt, 0xB19)
    )


def normalize_sampler_name(name: str) -> str:
    """Scene-file sampler name -> dispatch kind (api.cpp MakeSampler)."""
    n = (name or "").lower()
    if n in ("random",):
        return "random"
    if n in ("stratified",):
        return "stratified"
    if n in ("halton",):
        return "halton"
    if n in ("sobol",):
        return "sobol"
    if n in ("lowdiscrepancy", "02sequence", "zerotwosequence"):
        return "02"
    from tpu_pbrt.utils.error import Warning as _W

    if n == "maxmindist":
        _W(
            'sampler "maxmindist" has no bespoke generator matrix in this '
            "build; SUBSTITUTING the (0,2)-sequence sampler"
        )
        return "02"
    _W(f'sampler "{name}" unknown; using the (0,2)-sequence sampler')
    return "02"


# -------------------------------------------------------------------------
# Distribution1D / Distribution2D (pbrt sampling.h) — piecewise-constant
# CDF importance sampling. Build host-side (numpy), sample device-side.
# -------------------------------------------------------------------------

class Distribution1D(NamedTuple):
    """func: (N,), cdf: (N+1,), integral: scalar — all device arrays."""

    func: jnp.ndarray
    cdf: jnp.ndarray
    func_int: jnp.ndarray

    @staticmethod
    def build(f) -> "Distribution1D":
        f = np.asarray(f, dtype=np.float64)
        n = len(f)
        cdf = np.zeros(n + 1)
        cdf[1:] = np.cumsum(f) / n
        func_int = cdf[-1]
        if func_int == 0:
            cdf[1:] = np.arange(1, n + 1) / n
        else:
            cdf[1:] /= func_int
        return Distribution1D(
            jnp.asarray(f, jnp.float32), jnp.asarray(cdf, jnp.float32), jnp.float32(func_int)
        )

    @property
    def count(self):
        return self.func.shape[0]

    def sample_continuous(self, u):
        """Returns (x in [0,1), pdf, offset)."""
        offset = jnp.clip(
            jnp.searchsorted(self.cdf, u, side="right") - 1, 0, self.count - 1
        )
        c0 = self.cdf[offset]
        c1 = self.cdf[offset + 1]
        du = jnp.where(c1 > c0, (u - c0) / jnp.maximum(c1 - c0, 1e-20), 0.0)
        pdf = jnp.where(
            self.func_int > 0, self.func[offset] / jnp.maximum(self.func_int, 1e-20), 0.0
        )
        x = (offset.astype(jnp.float32) + du) / self.count
        return x, pdf, offset

    def sample_discrete(self, u):
        """Returns (offset, pmf)."""
        offset = jnp.clip(
            jnp.searchsorted(self.cdf, u, side="right") - 1, 0, self.count - 1
        )
        pmf = jnp.where(
            self.func_int > 0,
            self.func[offset] / jnp.maximum(self.func_int * self.count, 1e-20),
            0.0,
        )
        return offset, pmf

    def discrete_pdf(self, index):
        return self.func[index] / jnp.maximum(self.func_int * self.count, 1e-20)


class Distribution2D(NamedTuple):
    """Conditional rows + marginal over rows, flattened to fixed arrays.

    cond_func/cond_cdf: (H, W)/(H, W+1); marg over row integrals."""

    cond_func: jnp.ndarray
    cond_cdf: jnp.ndarray
    cond_int: jnp.ndarray  # (H,)
    marg_func: jnp.ndarray  # (H,)
    marg_cdf: jnp.ndarray  # (H+1,)
    marg_int: jnp.ndarray  # scalar

    @staticmethod
    def build(f) -> "Distribution2D":
        f = np.asarray(f, dtype=np.float64)
        h, w = f.shape
        cond_cdf = np.zeros((h, w + 1))
        cond_cdf[:, 1:] = np.cumsum(f, axis=1) / w
        cond_int = cond_cdf[:, -1].copy()
        safe = np.where(cond_int == 0, 1.0, cond_int)
        cond_cdf[:, 1:] = np.where(
            cond_int[:, None] == 0,
            np.arange(1, w + 1)[None, :] / w,
            cond_cdf[:, 1:] / safe[:, None],
        )
        marg = Distribution1D.build(cond_int)
        return Distribution2D(
            jnp.asarray(f, jnp.float32),
            jnp.asarray(cond_cdf, jnp.float32),
            jnp.asarray(cond_int, jnp.float32),
            marg.func,
            marg.cdf,
            marg.func_int,
        )

    def sample_continuous(self, u1, u2):
        """Returns ((u, v), pdf)."""
        h, w = self.cond_func.shape
        # marginal (rows)
        row = jnp.clip(jnp.searchsorted(self.marg_cdf, u2, side="right") - 1, 0, h - 1)
        mc0 = self.marg_cdf[row]
        mc1 = self.marg_cdf[row + 1]
        dv = jnp.where(mc1 > mc0, (u2 - mc0) / jnp.maximum(mc1 - mc0, 1e-20), 0.0)
        pdf_v = jnp.where(
            self.marg_int > 0, self.marg_func[row] / jnp.maximum(self.marg_int, 1e-20), 0.0
        )
        v = (row.astype(jnp.float32) + dv) / h
        # conditional (cols within row) — count-based search so it batches
        cdf_row = self.cond_cdf[row]  # (..., W+1)
        u1e = jnp.asarray(u1)[..., None]
        col = jnp.clip(jnp.sum(cdf_row <= u1e, axis=-1) - 1, 0, w - 1)
        cc0 = jnp.take_along_axis(cdf_row, col[..., None], axis=-1)[..., 0]
        cc1 = jnp.take_along_axis(cdf_row, col[..., None] + 1, axis=-1)[..., 0]
        du = jnp.where(cc1 > cc0, (u1 - cc0) / jnp.maximum(cc1 - cc0, 1e-20), 0.0)
        ci = self.cond_int[row]
        fval = jnp.take_along_axis(self.cond_func[row], col[..., None], axis=-1)[..., 0]
        pdf_u = jnp.where(ci > 0, fval / jnp.maximum(ci, 1e-20), 0.0)
        uu = (col.astype(jnp.float32) + du) / w
        return (uu, v), pdf_u * pdf_v

    def pdf(self, u, v):
        """Pdf of (u,v) in [0,1)^2 (pbrt Distribution2D::Pdf)."""
        h, w = self.cond_func.shape
        iu = jnp.clip((u * w).astype(jnp.int32), 0, w - 1)
        iv = jnp.clip((v * h).astype(jnp.int32), 0, h - 1)
        return self.cond_func[iv, iu] / jnp.maximum(self.marg_int, 1e-20)
