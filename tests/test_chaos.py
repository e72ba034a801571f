"""Chaos fault-injection subsystem (ISSUE 5): plan grammar + registry
determinism, each injection point firing exactly once, recovery
bit-identity, checkpoint v4 (checksum, fsync+rotate, corrupt-current ->
.prev fallback, v2/v3 compat), the non-finite film firewall
(scrub/count/raise/retry), retry backoff shape, and the bench probe's
chaos-hang + backoff satellite."""

import os

import numpy as np
import pytest

from tpu_pbrt import config
from tpu_pbrt.chaos import CHAOS, Fault, parse_plan


@pytest.fixture(autouse=True)
def _clear_chaos():
    """The registry is process-global state like the config snapshot —
    never let one test's plan leak into the next."""
    CHAOS.clear()
    yield
    CHAOS.clear()


def _render(res=12, spp=2, maxdepth=2, chunk=96, **render_kw):
    """Small multi-chunk pool render (res*res*spp=288 work items / 96 =
    3 chunks) shared by the recovery tests."""
    os.environ["TPU_PBRT_CHUNK"] = str(chunk)
    os.environ.setdefault("TPU_PBRT_RETRY_BACKOFF", "0.01")
    config.reload()
    try:
        from tpu_pbrt.scenes import compile_api, make_cornell

        api = make_cornell(
            res=res, spp=spp, integrator="path", maxdepth=maxdepth
        )
        scene, integ = compile_api(api)
        return integ.render(scene, **render_kw)
    finally:
        del os.environ["TPU_PBRT_CHUNK"]
        os.environ.pop("TPU_PBRT_RETRY_BACKOFF", None)
        config.reload()


# ---------------------------------------------------------------------------
# plan grammar
# ---------------------------------------------------------------------------


class TestPlanParsing:
    def test_full_grammar(self):
        plan = parse_plan(
            "dispatch:poison@chunk=3,ckpt:torn@write=2,"
            "nan:wave@5&chunk=1,probe:hang@attempt=1"
        )
        assert [(f.site, f.kind) for f in plan] == [
            ("dispatch", "poison"), ("ckpt", "torn"),
            ("nan", "wave"), ("probe", "hang"),
        ]
        assert plan[0].params == {"chunk": 3}
        # bare @value binds to the site's default key
        assert plan[2].params == {"wave": 5, "chunk": 1}
        assert plan[3].params == {"attempt": 1}

    def test_times_and_defaults(self):
        (f,) = parse_plan("dispatch:fail@chunk=2&times=99")
        assert f.times == 99 and f.params == {"chunk": 2}
        (g,) = parse_plan("mesh:lost")
        assert g.site == "mesh" and g.params == {} and g.times == 1

    def test_empty_plan(self):
        assert parse_plan("") == []
        assert parse_plan("  ,  ") == []

    @pytest.mark.parametrize(
        "bad",
        ["bogus:fail@chunk=1", "dispatch:explode", "nan:wave@x=y",
         "dispatch", "ckpt:torn@write=banana"],
    )
    def test_invalid_plans_fail_loudly(self, bad):
        """A typo'd plan must not silently inject nothing — that would
        certify recovery that was never exercised."""
        with pytest.raises(ValueError):
            parse_plan(bad)

    @pytest.mark.parametrize(
        "bad",
        ["dispatch:fail@chunck=3", "nan:wave@5&chnk=2", "ckpt:torn@chunk=1"],
    )
    def test_unknown_param_keys_fail_loudly(self, bad):
        """A typo'd KEY must not fall through to the seams' .get()
        defaults and fire the fault somewhere other than where the plan
        claimed."""
        with pytest.raises(ValueError, match="unknown param"):
            parse_plan(bad)

    def test_spec_roundtrip(self):
        for spec in ("dispatch:poison@chunk=3", "ckpt:torn@write=2"):
            (f,) = parse_plan(spec)
            assert parse_plan(f.spec())[0] == f


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_fires_exactly_once_and_exhausts(self):
        from tpu_pbrt.integrators.common import ChunkDispatchError

        CHAOS.install("dispatch:fail@chunk=1")
        with pytest.raises(ChunkDispatchError) as ei:
            CHAOS.dispatch(1, 0)
        assert not ei.value.poisons_state
        # exhausted: the re-dispatch of the same chunk runs clean
        CHAOS.dispatch(1, 1)
        CHAOS.dispatch(1, 0)
        assert CHAOS.report() == [
            {"fault": "dispatch:fail@chunk=1", "fired": 1, "times": 1}
        ]

    def test_attempt_matching(self):
        from tpu_pbrt.integrators.common import ChunkDispatchError

        CHAOS.install("dispatch:fail@chunk=0&attempt=1")
        CHAOS.dispatch(0, 0)  # wrong attempt: clean
        with pytest.raises(ChunkDispatchError):
            CHAOS.dispatch(0, 1)

    def test_poison_and_mesh_kinds(self):
        from tpu_pbrt.integrators.common import ChunkDispatchError

        CHAOS.install("dispatch:poison@chunk=2")
        with pytest.raises(ChunkDispatchError) as ei:
            CHAOS.dispatch(2, 0)
        assert ei.value.poisons_state
        CHAOS.install("mesh:lost@chunk=1")
        CHAOS.dispatch(1, 0, mesh=False)  # mesh faults need a mesh
        with pytest.raises(ChunkDispatchError) as ei:
            CHAOS.dispatch(1, 0, mesh=True)
        assert ei.value.poisons_state

    def test_registered_hook_is_called(self):
        """The promoted first-class form of the old test-only
        `integ._fault_hook` monkeypatch."""
        seen = []
        CHAOS.register_hook(lambda c, a: seen.append((c, a)))
        CHAOS.dispatch(4, 2)
        assert seen == [(4, 2)]
        CHAOS.clear()
        CHAOS.dispatch(4, 2)
        assert seen == [(4, 2)]

    def test_determinism_same_seed_same_bitflip(self):
        CHAOS.install("ckpt:bitflip@write=1", seed=7)
        a = CHAOS.bitflip_offset(10_000)
        CHAOS.install("ckpt:bitflip@write=1", seed=7)
        assert CHAOS.bitflip_offset(10_000) == a
        CHAOS.install("ckpt:bitflip@write=1", seed=8)
        assert CHAOS.bitflip_offset(10_000) != a

    def test_nan_wave_host_decision(self):
        CHAOS.install("nan:wave@3&chunk=2")
        assert CHAOS.has_nan() and CHAOS.trace_key() == (True,)
        assert CHAOS.nan_wave_for(0) == -1
        assert CHAOS.nan_wave_for(2) == 3
        # fired: the retry of chunk 2 is clean
        assert CHAOS.nan_wave_for(2) == -1
        CHAOS.clear()
        assert CHAOS.trace_key() == (False,)

    def test_probe_hang_parity_with_bench_parser(self):
        """The import-free parser in bench.py and the registry agree on
        the probe:hang grammar."""
        import bench

        CHAOS.install("probe:hang@attempt=2")
        assert not CHAOS.probe_hang(1) and CHAOS.probe_hang(2)
        os.environ["TPU_PBRT_FAULTS"] = "probe:hang@attempt=2,probe:hang@3"
        try:
            assert bench._probe_hang_attempts() == {2, 3}
        finally:
            del os.environ["TPU_PBRT_FAULTS"]


# ---------------------------------------------------------------------------
# retry backoff
# ---------------------------------------------------------------------------


class TestBackoff:
    def test_capped_exponential_with_deterministic_jitter(self, monkeypatch):
        from tpu_pbrt.integrators.common import redispatch_backoff

        monkeypatch.setenv("TPU_PBRT_RETRY_BACKOFF", "1.0")
        monkeypatch.setenv("TPU_PBRT_RETRY_BACKOFF_CAP", "8.0")
        config.reload()
        b = [redispatch_backoff(3, k) for k in range(1, 8)]
        # deterministic
        assert b == [redispatch_backoff(3, k) for k in range(1, 8)]
        # jitter keeps each sleep within [0.5, 1.0] * min(2^(k-1), cap)
        for k, v in enumerate(b, start=1):
            ceil = min(2.0 ** (k - 1), 8.0)
            assert 0.5 * ceil <= v <= ceil
        # capped: the tail stops growing past the cap
        assert max(b) <= 8.0
        # different chunks decorrelate
        assert redispatch_backoff(4, 1) != redispatch_backoff(3, 1)

    def test_zero_base_disables_sleeping(self, monkeypatch):
        from tpu_pbrt.integrators.common import redispatch_backoff

        monkeypatch.setenv("TPU_PBRT_RETRY_BACKOFF", "0")
        config.reload()
        assert redispatch_backoff(0, 5) == 0.0


# ---------------------------------------------------------------------------
# checkpoint v4
# ---------------------------------------------------------------------------


class TestCheckpointV4:
    def _state(self, fill=1.0):
        import jax.numpy as jnp

        from tpu_pbrt.core.film import FilmState

        return FilmState(
            rgb=jnp.full((4, 4, 3), fill), weight=jnp.full((4, 4), fill),
            splat=jnp.zeros((4, 4, 3)),
        )

    def test_v4_writes_checksum_and_rotates_prev(self, tmp_path):
        from tpu_pbrt.parallel.checkpoint import (
            _FORMAT_VERSION,
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._state(1.0), 1, 10, fingerprint="fp")
        with np.load(p) as z:
            assert int(z["version"]) == _FORMAT_VERSION == 4
            assert "checksum" in z
        assert not os.path.exists(p + ".prev")
        save_checkpoint(p, self._state(2.0), 2, 20, fingerprint="fp")
        # the previous good write is kept as the corruption fallback
        _, nxt, _, _ = load_checkpoint(p + ".prev", "fp")
        assert nxt == 1
        _, nxt, _, _ = load_checkpoint(p, "fp")
        assert nxt == 2

    def test_corrupt_current_falls_back_to_prev(self, tmp_path):
        from tpu_pbrt.parallel.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._state(1.0), 1, 10, fingerprint="fp")
        save_checkpoint(p, self._state(2.0), 2, 20, fingerprint="fp")
        size = os.path.getsize(p)
        with open(p, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0xFF]))
        st, nxt, rays, _ = load_checkpoint(p, "fp")
        assert (nxt, rays) == (1, 10)
        assert float(np.asarray(st.rgb)[0, 0, 0]) == 1.0

    def test_truncated_current_falls_back(self, tmp_path):
        from tpu_pbrt.parallel.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._state(1.0), 1, 10)
        save_checkpoint(p, self._state(2.0), 2, 20)
        with open(p, "rb") as f:
            data = f.read()
        with open(p, "wb") as f:
            f.write(data[: len(data) // 3])
        _, nxt, _, _ = load_checkpoint(p)
        assert nxt == 1

    def test_missing_current_falls_back_to_prev(self, tmp_path):
        """Only .prev on disk (a crash in a hardlink-less rotation, or a
        deleted current): checkpoint_exists sees it and load falls
        back — resume must not silently restart from chunk 0."""
        from tpu_pbrt.parallel.checkpoint import (
            checkpoint_exists,
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        assert not checkpoint_exists(p)
        save_checkpoint(p, self._state(1.0), 1, 10)
        save_checkpoint(p, self._state(2.0), 2, 20)
        os.remove(p)
        assert checkpoint_exists(p)
        _, nxt, _, _ = load_checkpoint(p)
        assert nxt == 1

    def test_rotation_never_unpublishes_current(self, tmp_path):
        """The .prev rotation hardlinks the old current in place: at
        every instant a complete file exists at `path` (a rename-based
        rotate has a crash window with NO current checkpoint)."""
        from tpu_pbrt.parallel.checkpoint import save_checkpoint

        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._state(1.0), 1, 10)
        ino = os.stat(p).st_ino
        save_checkpoint(p, self._state(2.0), 2, 20)
        # .prev is the OLD current's inode: the rotation was a link, not
        # a rename that momentarily removed `path`
        assert os.stat(p + ".prev").st_ino == ino
        assert os.stat(p).st_ino != ino

    def test_corrupt_without_prev_raises(self, tmp_path):
        from tpu_pbrt.parallel.checkpoint import (
            CorruptCheckpointError,
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._state(), 1, 10)
        with open(p, "wb") as f:
            f.write(b"garbage")
        with pytest.raises(CorruptCheckpointError):
            load_checkpoint(p)

    def test_fingerprint_mismatch_never_falls_back(self, tmp_path):
        """Misconfiguration is not corruption: resuming under the wrong
        settings must refuse even though a .prev exists."""
        from tpu_pbrt.parallel.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        save_checkpoint(p, self._state(), 1, 10, fingerprint="a")
        save_checkpoint(p, self._state(), 2, 20, fingerprint="a")
        with pytest.raises(ValueError, match="different render configuration"):
            load_checkpoint(p, "b")

    def test_v2_and_v3_files_still_load(self, tmp_path):
        from tpu_pbrt.parallel.checkpoint import load_checkpoint

        st = self._state()
        for version, extra in ((2, {}), (
            3, {"counters": np.array('{"rays_traced": 9}')}
        )):
            p = str(tmp_path / f"v{version}.npz")
            np.savez_compressed(
                p, version=version, rgb=np.asarray(st.rgb),
                weight=np.asarray(st.weight), splat=np.asarray(st.splat),
                next_chunk=5, rays=77, fingerprint=np.array(""), **extra,
            )
            _, nxt, rays, ctr = load_checkpoint(p)
            assert (nxt, rays) == (5, 77)
            assert ctr == ({} if version == 2 else {"rays_traced": 9})

    def test_chaos_ckpt_faults(self, tmp_path):
        """torn/crash/bitflip injection through save_checkpoint leaves
        exactly the on-disk shapes load_checkpoint must survive."""
        from tpu_pbrt.parallel.checkpoint import (
            load_checkpoint,
            save_checkpoint,
        )

        p = str(tmp_path / "ck.npz")
        CHAOS.install("ckpt:crash@write=2")
        save_checkpoint(p, self._state(1.0), 1, 10)
        save_checkpoint(p, self._state(2.0), 2, 20)  # crashes pre-rename
        _, nxt, _, _ = load_checkpoint(p)
        assert nxt == 1, "crash between tmp write and rename lost the old file"

        CHAOS.install("ckpt:torn@write=2")
        save_checkpoint(p, self._state(3.0), 3, 30)
        save_checkpoint(p, self._state(4.0), 4, 40)  # torn current
        _, nxt, _, _ = load_checkpoint(p)
        assert nxt == 3, "torn current did not fall back to .prev"

        CHAOS.install("ckpt:bitflip@write=2")
        save_checkpoint(p, self._state(5.0), 5, 50)
        save_checkpoint(p, self._state(6.0), 6, 60)  # flipped current
        _, nxt, _, _ = load_checkpoint(p)
        assert nxt == 5, "bit-flipped current did not fall back to .prev"


# ---------------------------------------------------------------------------
# recovery bit-identity (render-level)
# ---------------------------------------------------------------------------


class TestRecoveryBitIdentity:
    def test_nan_scrub_counts_and_stays_finite(self):
        """Acceptance: an injected NaN wave leaves the final image fully
        finite with nonfinite_deposits > 0 in telemetry."""
        ref = _render()
        assert ref.stats["telemetry"]["counters"]["nonfinite_deposits"] == 0
        CHAOS.install("nan:wave@1&chunk=1")
        r = _render()
        assert CHAOS.fired_total() == 1
        img = np.asarray(r.image)
        assert np.isfinite(img).all()
        assert r.stats["telemetry"]["counters"]["nonfinite_deposits"] > 0

    def test_nan_retry_mode_recovers_bit_identical(self, tmp_path, monkeypatch):
        ref = _render()
        monkeypatch.setenv("TPU_PBRT_NONFINITE", "retry")
        CHAOS.install("nan:wave@1&chunk=1")
        r = _render(
            checkpoint_path=str(tmp_path / "f.ckpt"), checkpoint_every=1
        )
        assert r.stats["recovery"]["nonfinite_retries"] == 1
        np.testing.assert_array_equal(
            np.asarray(r.image), np.asarray(ref.image)
        )
        assert r.stats["telemetry"]["counters"]["nonfinite_deposits"] == 0

    def test_nan_raise_mode_aborts(self, monkeypatch):
        from tpu_pbrt.integrators.common import NonFiniteRadianceError

        monkeypatch.setenv("TPU_PBRT_NONFINITE", "raise")
        CHAOS.install("nan:wave@1&chunk=1")
        with pytest.raises(NonFiniteRadianceError):
            _render()

    def test_nan_strict_modes_require_telemetry(self, monkeypatch):
        """raise/retry read the scrub count off the telemetry counters;
        with them killed the modes must refuse loudly up front, not
        silently degrade to scrub."""
        monkeypatch.setenv("TPU_PBRT_TELEMETRY", "0")
        monkeypatch.setenv("TPU_PBRT_NONFINITE", "raise")
        with pytest.raises(ValueError, match="TPU_PBRT_NONFINITE"):
            _render()

    def test_rollback_does_not_double_count_retry_extras(self, tmp_path):
        """A clean redispatch BEFORE a checkpointed rollback: the
        reloaded snapshot already bakes in that redispatch, and
        ctr_snapshot must add only the unbaked delta — not re-add the
        whole process total on every rollback."""
        CHAOS.install("dispatch:fail@chunk=0,dispatch:poison@chunk=2")
        r = _render(
            checkpoint_path=str(tmp_path / "f.ckpt"), checkpoint_every=1
        )
        assert r.stats["recovery"]["redispatches"] == 2
        assert r.stats["telemetry"]["counters"]["chunks_redispatched"] == 2

    def test_exhaustion_writes_emergency_checkpoint_then_resume(
        self, tmp_path, monkeypatch
    ):
        """Retry-budget exhaustion raises AFTER persisting completed
        work; a later resume finishes bit-identically."""
        from tpu_pbrt.parallel.checkpoint import load_checkpoint

        ref = _render()
        ck = str(tmp_path / "f.ckpt")
        monkeypatch.setenv("TPU_PBRT_RETRY_MAX", "2")
        CHAOS.install("dispatch:fail@chunk=2&times=99")
        with pytest.raises(RuntimeError, match="chunk 2 failed"):
            _render(checkpoint_path=ck, checkpoint_every=1)
        CHAOS.clear()
        _, cursor, _, _ = load_checkpoint(ck)
        assert cursor == 2, "emergency checkpoint lost completed chunks"
        monkeypatch.delenv("TPU_PBRT_RETRY_MAX")
        r = _render(checkpoint_path=ck, checkpoint_every=1)
        np.testing.assert_array_equal(
            np.asarray(r.image), np.asarray(ref.image)
        )

    @pytest.mark.parametrize("row", ["clean-redispatch", "stream-tracer"])
    def test_matrix_scenario_entry_point(self, tmp_path, row):
        """The `python -m tpu_pbrt.chaos` machinery itself (one cheap
        scenario end-to-end through its helpers), and the one row whose
        recovery ladder runs over a stream-traced scene (every other
        case here renders the 36-triangle cornell, the brute tracer);
        the full matrix runs in tools/ci.sh."""
        from tpu_pbrt.chaos import __main__ as matrix

        ok, detail = matrix.SCENARIOS[row](str(tmp_path))
        assert ok, detail


# ---------------------------------------------------------------------------
# bench probe (satellite: backoff + chaos hang)
# ---------------------------------------------------------------------------


class TestBenchProbe:
    def test_probe_recovers_from_simulated_hang(self, tmp_path, monkeypatch):
        """probe:hang@attempt=1 makes attempt 1 time out like a hung
        accelerator runtime; the capped-backoff retry then
        succeeds — with per-attempt accounting in the returned tuple."""
        import bench

        import time

        monkeypatch.setenv("TPU_PBRT_FAULTS", "probe:hang@attempt=1")
        monkeypatch.setattr(bench, "_FLIGHT_PATH", str(tmp_path / "f.jsonl"))
        # rebase the budget clock: bench.T_START is import-time and the
        # probe's budget guard would otherwise see a half-spent budget
        # deep into a long suite run
        monkeypatch.setattr(bench, "T_START", time.time())
        # attempt 1 really runs (a child that sleeps out the 3 s); attempt
        # 2 is answered here: a real `import jax; jax.devices()` child has
        # to beat the same 3 s clock, and on a machine full of compiles it
        # does not (ISSUE 28)
        real_run = bench.subprocess.run

        def run(argv, **kw):
            if "jax.devices()" not in argv[-1]:
                return real_run(argv, **kw)
            return bench.subprocess.CompletedProcess(argv, 0, "cpu 8\n", "")

        monkeypatch.setattr(bench.subprocess, "run", run)
        ok, detail, retries, wait_s = bench.probe_backend(
            timeout_s=3.0, max_attempts=2, backoff_base_s=0.05,
        )
        assert ok and retries == 1 and detail == "cpu 8"
        assert wait_s >= 3.0  # the hung attempt burned its full timeout
        import json

        lines = [
            json.loads(ln)
            for ln in open(tmp_path / "f.jsonl").read().splitlines()
        ]
        phases = [ln["phase"] for ln in lines]
        assert "probe_backoff" in phases
        assert any(ln.get("chaos_hang") for ln in lines)
        assert any(ln.get("ok") for ln in lines)
