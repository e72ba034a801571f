"""Film: filter-weighted sample accumulation into a framebuffer.

Capability match for pbrt-v3 src/core/film.{h,cpp}: Film (full-res pixel
array with crop window, filter-weighted xyz + filterWeightSum + splat
planes, scale / maxsampleluminance, diagonal), FilmTile/MergeFilmTile and
AddSplat.

TPU-first redesign: there are no tiles-as-objects and no mutexes/atomics.
The film is a functional pytree (rgb, weight, splat arrays); a batch of
samples lands via a statically-unrolled footprint of masked scatter-adds
(XLA lowers `at[].add` to deterministic scatter), and "merge" is just `+`
(or a psum across devices) because accumulation is associative. FilmTile
semantics (crop-window restriction) fall out of rendering only a tile's
pixel batch. This replaces the mutex-guarded Film::MergeFilmTile and the
AtomicFloat splats (SURVEY.md §5.2).
"""

from __future__ import annotations

import functools
import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpu_pbrt.core.filters import FilterSpec, make_filter
from tpu_pbrt.core.spectrum import luminance
from tpu_pbrt.obs import phases as ph
from tpu_pbrt.utils.error import Error, Warning


class FilmState(NamedTuple):
    """The accumulation buffers — a pure pytree; merging two states is
    elementwise addition (associative, so psum-able across a mesh)."""

    rgb: jnp.ndarray  # (H, W, 3) filter-weighted radiance sums
    weight: jnp.ndarray  # (H, W) filter weight sums
    splat: jnp.ndarray  # (H, W, 3) unweighted splats (BDPT/MLT/SPPM)


def merge_film(a: FilmState, b: FilmState) -> FilmState:
    """Film::MergeFilmTile, functional form."""
    with jax.named_scope(ph.FILM_MERGE):
        return FilmState(
            a.rgb + b.rgb, a.weight + b.weight, a.splat + b.splat
        )


def _deposit_scope(fn):
    """Trace a deposit method under the `film/deposit` phase scope."""

    @functools.wraps(fn)
    def scoped(*args, **kw):
        with jax.named_scope(ph.FILM_DEPOSIT):
            return fn(*args, **kw)

    return scoped


def nonfinite_mask(L) -> jnp.ndarray:
    """Rows of a (..., 3) radiance batch carrying any NaN/Inf component.

    The non-finite FIREWALL's shared predicate (ISSUE 5): every deposit
    path zeroes these rows before accumulation (the scrub half — pbrt's
    AddSample NaN drop, extended to Inf), and callers that carry a
    telemetry block count the same mask into the `nonfinite_deposits`
    counter — one predicate, so the scrub and the count can never
    disagree. One contaminated wave therefore cannot poison the film
    (NaN + x = NaN would otherwise spread to every later checkpoint),
    and the contamination is visible instead of silent."""
    return jnp.any(~jnp.isfinite(jnp.asarray(L, jnp.float32)), axis=-1)


@partial(jax.jit, static_argnums=(0, 1))
def _init_state_jit(ry: int, rx: int) -> FilmState:
    return FilmState(
        rgb=jnp.zeros((ry, rx, 3), jnp.float32),
        weight=jnp.zeros((ry, rx), jnp.float32),
        splat=jnp.zeros((ry, rx, 3), jnp.float32),
    )


class Film:
    """Host-side film configuration + the jit-traceable accumulation ops."""

    def __init__(
        self,
        resolution=(1280, 720),
        crop_window=(0.0, 1.0, 0.0, 1.0),
        filt: Optional[FilterSpec] = None,
        diagonal_mm: float = 35.0,
        filename: str = "pbrt.exr",
        scale: float = 1.0,
        max_sample_luminance: float = float("inf"),
    ):
        self.full_resolution = (int(resolution[0]), int(resolution[1]))
        self.filter = filt or FilterSpec("box", 0.5, 0.5, 0.0, 0.0)
        self.diagonal = diagonal_mm * 0.001
        self.filename = filename
        self.scale = scale
        self.max_sample_luminance = max_sample_luminance
        x0, x1, y0, y1 = crop_window
        rx, ry = self.full_resolution
        # pbrt Film ctor: croppedPixelBounds from the crop window
        self.cropped_pixel_bounds = (
            int(math.ceil(rx * x0)),
            int(math.ceil(rx * x1)),
            int(math.ceil(ry * y0)),
            int(math.ceil(ry * y1)),
        )
        if (
            self.cropped_pixel_bounds[1] <= self.cropped_pixel_bounds[0]
            or self.cropped_pixel_bounds[3] <= self.cropped_pixel_bounds[2]
        ):
            Error("Degenerate crop window")

    # -- sample bounds (Film::GetSampleBounds) ----------------------------
    def sample_bounds(self):
        """Pixel-area bounds that samples must cover so the filter is fed
        at the crop edges."""
        fx, fy = self.filter.xwidth, self.filter.ywidth
        x0, x1, y0, y1 = self.cropped_pixel_bounds
        return (
            int(math.floor(x0 + 0.5 - fx)),
            int(math.ceil(x1 - 0.5 + fx)),
            int(math.floor(y0 + 0.5 - fy)),
            int(math.ceil(y1 - 0.5 + fy)),
        )

    def physical_extent(self):
        """Film::GetPhysicalExtent (meters), for RealisticCamera/light We."""
        rx, ry = self.full_resolution
        aspect = ry / rx
        x = math.sqrt(self.diagonal * self.diagonal / (1 + aspect * aspect))
        y = aspect * x
        return (-x / 2, x / 2, -y / 2, y / 2)

    # -- device ops -------------------------------------------------------
    def init_state(self) -> FilmState:
        rx, ry = self.full_resolution
        # jitted creation: eager jnp.zeros stages an implicit
        # host->device scalar transfer, which the jaxpr audit's
        # transfer_guard("disallow") smoke render treats as an error;
        # inside jit the zeros are compile-time constants
        return _init_state_jit(ry, rx)

    @_deposit_scope
    def add_samples(self, state: FilmState, p_film, L, ray_weight=None) -> FilmState:
        """FilmTile::AddSample over a batch. p_film: (R,2) raster coords,
        L: (R,3). Static filter footprint of masked scatter-adds."""
        f = self.filter
        L = jnp.asarray(L, jnp.float32)
        # pbrt: drop NaNs, clamp to maxSampleLuminance
        bad = nonfinite_mask(L)
        L = jnp.where(bad[..., None], 0.0, L)
        if np.isfinite(self.max_sample_luminance):
            y = luminance(L)
            s = jnp.where(
                y > self.max_sample_luminance, self.max_sample_luminance / jnp.maximum(y, 1e-20), 1.0
            )
            L = L * s[..., None]
        if ray_weight is not None:
            L = L * jnp.asarray(ray_weight, jnp.float32)[..., None]

        # discrete coords: pixel (x,y) has its sample center at x+0.5.
        # x0f/y0f stay f32 next to their int32 twins: ceil() is exact on
        # integer-valued f32, so feeding the filter from the float copy
        # is bit-identical to re-converting the ints — and deletes the
        # f32->i32->f32 round trip the cost pass flagged
        # (JC-CHURN:film.add_samples: two convert passes per footprint tap)
        dx = p_film[..., 0] - 0.5
        dy = p_film[..., 1] - 0.5
        x0f = jnp.ceil(dx - f.xwidth)
        y0f = jnp.ceil(dy - f.ywidth)
        x0 = x0f.astype(jnp.int32)
        y0 = y0f.astype(jnp.int32)
        nx = int(math.floor(2 * f.xwidth)) + 1
        ny = int(math.floor(2 * f.ywidth)) + 1
        rx, ryres = self.full_resolution
        cx0, cx1, cy0, cy1 = self.cropped_pixel_bounds

        rgb, wsum = state.rgb, state.weight
        for oy in range(ny):
            for ox in range(nx):
                px = x0 + ox
                py = y0 + oy
                fw = f.evaluate((x0f + ox) - dx, (y0f + oy) - dy)
                inb = (px >= cx0) & (px < cx1) & (py >= cy0) & (py < cy1)
                fw = jnp.where(inb, fw, 0.0)
                pxc = jnp.clip(px, 0, rx - 1)
                pyc = jnp.clip(py, 0, ryres - 1)
                rgb = rgb.at[pyc, pxc].add(fw[..., None] * L)
                wsum = wsum.at[pyc, pxc].add(fw)
        return FilmState(rgb, wsum, state.splat)

    def aligned_chunk_pixels(self, chunk: int, spp: int) -> int:
        """Static gate for add_samples_aligned: returns the pixels per
        chunk when the fast path applies (the default box(0.5) filter —
        a one-pixel deposit — full-frame crop, whole-pixel chunks tiling
        the frame exactly), else 0."""
        rx, ry = self.full_resolution
        if not self.pixel_deposit_ok() or spp <= 0 or chunk % spp:
            return 0
        npc = chunk // spp
        return npc if (rx * ry) % npc == 0 else 0

    @_deposit_scope
    def add_samples_aligned(
        self, state: FilmState, start_pix, spp: int, p_film, L,
        ray_weight=None,
    ) -> FilmState:
        """add_samples for a chunk of `chunk//spp` CONSECUTIVE pixels
        with spp consecutive samples each (the render loop's layout):
        the per-pixel filter sums become one reshape + axis-sum and the
        film update two contiguous slice-adds — no scatter. Scatter-adds
        of the general path cost ~90 ms per 1M-sample chunk on this
        v5e; this is ~2 ms. Caller must have checked
        aligned_chunk_pixels() != 0 (box(0.5) only).

        Documented deviation: a jitter of EXACTLY 0.0 lands on a pixel
        boundary, where the general path's box filter deposits the
        sample into BOTH adjacent pixels with weight 1; this path
        deposits into the sample's own pixel only. The double deposit
        raises rgb and weight together, so the developed (weighted-mean)
        image is unchanged up to rounding — and the event has ~2^-23
        probability per sample."""
        f = self.filter
        L = jnp.asarray(L, jnp.float32)
        bad = nonfinite_mask(L)
        L = jnp.where(bad[..., None], 0.0, L)
        if np.isfinite(self.max_sample_luminance):
            y = luminance(L)
            s = jnp.where(
                y > self.max_sample_luminance,
                self.max_sample_luminance / jnp.maximum(y, 1e-20), 1.0,
            )
            L = L * s[..., None]
        if ray_weight is not None:
            L = L * jnp.asarray(ray_weight, jnp.float32)[..., None]
        del f  # box(0.5): in-pixel weight is identically 1
        n = L.shape[0]
        npc = n // spp
        contrib = L.reshape(npc, spp, 3).sum(axis=1)
        wadd = jnp.full((npc,), spp, dtype=jnp.float32)
        rx, ry = self.full_resolution
        rgb_flat = state.rgb.reshape(rx * ry, 3)
        w_flat = state.weight.reshape(rx * ry)
        cur = jax.lax.dynamic_slice(rgb_flat, (start_pix, 0), (npc, 3))
        rgb_flat = jax.lax.dynamic_update_slice(
            rgb_flat, cur + contrib, (start_pix, 0)
        )
        curw = jax.lax.dynamic_slice(w_flat, (start_pix,), (npc,))
        w_flat = jax.lax.dynamic_update_slice(
            w_flat, curw + wadd, (start_pix,)
        )
        return FilmState(
            rgb_flat.reshape(ry, rx, 3), w_flat.reshape(ry, rx), state.splat
        )

    def pixel_deposit_ok(self) -> bool:
        """Static gate for add_samples_pixel: box(0.5) filter (one-pixel
        deposit) over the full frame."""
        f = self.filter
        rx, ry = self.full_resolution
        return (
            f.name == "box" and f.xwidth == 0.5 and f.ywidth == 0.5
            and self.cropped_pixel_bounds == (0, rx, 0, ry)
        )

    @_deposit_scope
    def add_samples_pixel(
        self, state: FilmState, px, py, L, mask, ray_weight=None
    ) -> FilmState:
        """add_samples for the box(0.5)/full-frame case with KNOWN integer
        pixel coordinates: each masked sample deposits into its own pixel
        with filter weight 1 — two masked scatter-adds instead of the
        general path's filter footprint. Used by the persistent-wavefront
        pool, whose terminated lanes deposit mid-loop and already carry
        (px, py). Shares add_samples_aligned's documented deviation: a
        jitter of exactly 0.0 deposits into the sample's own pixel only,
        where the general footprint path would also hit the boundary
        neighbor (the fixed-batch single-device render takes the aligned
        path, so pool and fixed-batch images stay identical).
        Caller must have checked pixel_deposit_ok()."""
        L = jnp.asarray(L, jnp.float32)
        bad = nonfinite_mask(L)
        L = jnp.where(bad[..., None], 0.0, L)
        if np.isfinite(self.max_sample_luminance):
            y = luminance(L)
            s = jnp.where(
                y > self.max_sample_luminance,
                self.max_sample_luminance / jnp.maximum(y, 1e-20), 1.0,
            )
            L = L * s[..., None]
        if ray_weight is not None:
            L = L * jnp.asarray(ray_weight, jnp.float32)[..., None]
        rx, ryres = self.full_resolution
        pxc = jnp.clip(px, 0, rx - 1)
        pyc = jnp.clip(py, 0, ryres - 1)
        rgb = state.rgb.at[pyc, pxc].add(
            jnp.where(mask[..., None], L, 0.0)
        )
        wsum = state.weight.at[pyc, pxc].add(
            jnp.where(mask, 1.0, 0.0)
        )
        return FilmState(rgb, wsum, state.splat)

    @_deposit_scope
    def add_splats(self, state: FilmState, p_film, v) -> FilmState:
        """Film::AddSplat over a batch (no filtering; box deposit)."""
        v = jnp.asarray(v, jnp.float32)
        bad = nonfinite_mask(v)
        v = jnp.where(bad[..., None], 0.0, v)
        if np.isfinite(self.max_sample_luminance):
            y = luminance(v)
            s = jnp.where(
                y > self.max_sample_luminance, self.max_sample_luminance / jnp.maximum(y, 1e-20), 1.0
            )
            v = v * s[..., None]
        px = jnp.floor(p_film[..., 0]).astype(jnp.int32)
        py = jnp.floor(p_film[..., 1]).astype(jnp.int32)
        cx0, cx1, cy0, cy1 = self.cropped_pixel_bounds
        inb = (px >= cx0) & (px < cx1) & (py >= cy0) & (py < cy1)
        v = jnp.where(inb[..., None], v, 0.0)
        rx, ryres = self.full_resolution
        pxc = jnp.clip(px, 0, rx - 1)
        pyc = jnp.clip(py, 0, ryres - 1)
        return FilmState(state.rgb, state.weight, state.splat.at[pyc, pxc].add(v))

    def develop(self, state: FilmState, splat_scale: float = 1.0) -> np.ndarray:
        """Film::WriteImage math: rgb/filterWeightSum + splatScale*splat,
        then `scale`. Returns the cropped (h, w, 3) float32 image."""
        # explicit device_get: develop() runs inside the render loop's
        # jax.transfer_guard("disallow") audit, where an implicit D2H
        # (np.asarray on a device buffer) is a hard error. Host numpy
        # from here on: no device op stands under the scope today
        with jax.named_scope(ph.FILM_DEVELOP):
            rgb = np.asarray(jax.device_get(state.rgb), np.float64)
            w = np.asarray(jax.device_get(state.weight), np.float64)
            splat = np.asarray(jax.device_get(state.splat), np.float64)
        img = rgb / np.maximum(w, 1e-20)[..., None]
        img = np.where(w[..., None] > 0, img, 0.0)
        img = img + splat_scale * splat
        img = img * self.scale
        x0, x1, y0, y1 = self.cropped_pixel_bounds
        return img[y0:y1, x0:x1].astype(np.float32)

    def write_image(self, state: FilmState, splat_scale: float = 1.0, filename: str = ""):
        from tpu_pbrt.utils import imageio

        img = self.develop(state, splat_scale)
        imageio.write_image(filename or self.filename, img)
        return img


def make_film(name: str, params, filt: FilterSpec, options=None) -> Film:
    """api.cpp MakeFilm -> CreateFilm."""
    if name != "image":
        Warning(f'Film "{name}" unknown; using "image".')
    xres = params.find_one_int("xresolution", 1280)
    yres = params.find_one_int("yresolution", 720)
    if options is not None and getattr(options, "quick_render", False):
        xres = max(1, xres // 4)
        yres = max(1, yres // 4)
    crop = (0.0, 1.0, 0.0, 1.0)
    cr = params.find_float("cropwindow")
    if cr is not None and len(cr) == 4:
        crop = (
            min(cr[0], cr[1]), max(cr[0], cr[1]),
            min(cr[2], cr[3]), max(cr[2], cr[3]),
        )
    elif cr is not None:
        Error(f"{len(cr)} values supplied for \"cropwindow\". Expected 4.")
    if options is not None and getattr(options, "crop_window", None):
        c = options.crop_window
        crop = (c[0], c[1], c[2], c[3])
    filename = params.find_one_string("filename", "")
    if options is not None and getattr(options, "image_file", ""):
        if filename:
            Warning(
                f'Output filename supplied on command line, "{options.image_file}" '
                f'is overriding filename provided in scene description file, "{filename}".'
            )
        filename = options.image_file
    if not filename:
        filename = "pbrt.exr"
    return Film(
        resolution=(xres, yres),
        crop_window=crop,
        filt=filt,
        diagonal_mm=params.find_one_float("diagonal", 35.0),
        filename=filename,
        scale=params.find_one_float("scale", 1.0),
        max_sample_luminance=params.find_one_float("maxsampleluminance", float("inf")),
    )
