"""Native C++ BVH builder tests: the ctypes bridge must produce the SAME
tree as the pure-numpy reference implementation (both implement pbrt's
binned SAH with identical f64 math and stable tie-breaking), and must be
substantially faster."""

import shutil
import time

import numpy as np
import pytest

from tpu_pbrt.accel.build import _build_recursive, triangle_bounds
from tpu_pbrt.accel import native
from tpu_pbrt.accel.native import native_build_sah


def _random_tris(n, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.uniform(-10, 10, (n, 1, 3))
    tri = base + rng.normal(0, 0.3, (n, 3, 3))
    return tri


needs_native = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++ on this machine"
)


@needs_native
@pytest.mark.parametrize("n", [1, 2, 7, 100, 5000])
def test_native_matches_numpy(n):
    bmin, bmax = triangle_bounds(_random_tris(n))
    a = native_build_sah(bmin.astype(np.float64), bmax.astype(np.float64), 4)
    b = _build_recursive(bmin.astype(np.float64), bmax.astype(np.float64), 4, "sah")
    assert a.n_nodes == b.n_nodes
    np.testing.assert_array_equal(a.prim_order, b.prim_order)
    np.testing.assert_array_equal(a.n_prims, b.n_prims)
    np.testing.assert_array_equal(a.prim_offset, b.prim_offset)
    np.testing.assert_array_equal(a.second_child, b.second_child)
    np.testing.assert_array_equal(a.axis, b.axis)
    np.testing.assert_allclose(a.bounds_min, b.bounds_min, rtol=1e-6)
    np.testing.assert_allclose(a.bounds_max, b.bounds_max, rtol=1e-6)


@needs_native
def test_native_covers_all_prims():
    """Every primitive appears exactly once in leaf order, and leaf
    metadata tiles the order array."""
    n = 20000
    bmin, bmax = triangle_bounds(_random_tris(n, seed=3))
    a = native_build_sah(bmin.astype(np.float64), bmax.astype(np.float64), 4)
    assert sorted(a.prim_order.tolist()) == list(range(n))
    leaves = a.n_prims > 0
    assert a.n_prims[leaves].sum() == n
    assert (a.n_prims <= 4).all()


@needs_native
def test_native_speedup():
    n = 100_000
    bmin, bmax = triangle_bounds(_random_tris(n, seed=1))
    b64min, b64max = bmin.astype(np.float64), bmax.astype(np.float64)
    t0 = time.time()
    native_build_sah(b64min, b64max, 4)
    t_native = time.time() - t0
    t0 = time.time()
    _build_recursive(b64min, b64max, 4, "sah")
    t_numpy = time.time() - t0
    assert t_native < t_numpy / 5, f"native {t_native:.2f}s vs numpy {t_numpy:.2f}s"


def test_failed_build_is_reported_not_replaced(monkeypatch, tmp_path):
    """A build that fails raises with the compiler's say-so; only
    TPU_PBRT_NATIVE=0 selects the numpy builders."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_CXX", ["false"])
    with pytest.raises(native.NativeBuildError, match="native build failed"):
        native.get_lib()
    assert native.builder_name() == "native"


@needs_native
def test_binary_is_keyed_on_source_content(monkeypatch, tmp_path):
    """A stale binary under the old fixed name is never loaded: the
    library's name carries the hash of the source it was built from."""
    stale = tmp_path / "libtpupbrt.so"
    stale.write_bytes(b"not a shared object")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_OUT_DIR", str(tmp_path))
    assert native.get_lib() is not None
    built = [p.name for p in tmp_path.iterdir() if p != stale]
    assert len(built) == 1 and built[0].startswith("libtpupbrt-")
