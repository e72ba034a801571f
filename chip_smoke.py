#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu-pbrt still starts on the chip.

Drives the system's main path once, through the entry points a user
calls, at the full width of the killeroo-class configuration
(BASELINE.json's first: path integrator, maxdepth 5, one 128,880-triangle
matte mesh + ground + area light + point light, 512x512):

  cli       python -m tpu_pbrt.main <scene>.pbrt -o <image>.pfm, 64 spp
  accuracy  the same scene at 128x128x256 spp against the committed CPU
            reference (refimg/), per-pixel MSE <= 1e-4
  serve     python -m tpu_pbrt.main --serve, over stdin/stdout: a cold
            512x512x16 spp job, scenes/cornell-path.pbrt, the first scene
            again (warm: no scene compile, no program built), results,
            health, shutdown
  mesh4     with four devices: cli and serve again with --mesh 4

This process only orchestrates: it never imports jax or tpu_pbrt, because
a chip belongs to one process at a time. Every leg is one child process,
one after another, and each reports the device it ran on. A leg that
fails, or that ran on the CPU, fails the smoke.

The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}},
printed only when every leg passed on an accelerator; otherwise the exit
code is non-zero and no result is printed.

CHIP_SMOKE_DEBUG=1 is for debugging this script where there is no chip:
toy sizes, the CPU allowed, the accuracy comparison skipped. A debug run
cannot pass: it prints no result and exits 3.
"""

from __future__ import annotations

import array
import json
import math
import os
import queue
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEBUG = os.environ.get("CHIP_SMOKE_DEBUG") == "1"
#: the contract's limit is 1200 s, compilation included
DEADLINE_S = 1150.0
T0 = time.monotonic()

#: the configuration. Depth (spp) is cut where a leg says so; the
#: resolution and the geometry are not.
FULL = dict(res=512, n_theta=180, n_phi=360, cli_spp=64, serve_spp=16,
            acc_res=128, acc_spp=256, cornell_res=256, cornell_req={})
TOY = dict(res=32, n_theta=24, n_phi=48, cli_spp=4, serve_spp=2,
           acc_res=16, acc_spp=4, cornell_res=64,
           cornell_req={"quick": True})
CFG = TOY if DEBUG else FULL
MSE_BOUND = 1e-4  # the repo's own (bench.py, BASELINE.json)


class LegFailed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - T0)


def child_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("CHIP_SMOKE_DEBUG", None)
    env.update(extra)
    return env


def run_child(argv, log_path, env=None, timeout=None):
    """Run one child to its end (killed at the timeout); its stderr goes
    to `log_path`, its stdout comes back."""
    timeout = min(timeout or 1e9, max(remaining(), 1.0))
    with open(log_path, "w") as log:
        try:
            r = subprocess.run(
                argv, cwd=HERE, env=env or child_env(), stdout=subprocess.PIPE,
                stderr=log, text=True, timeout=timeout,
            )
        except subprocess.TimeoutExpired as e:
            raise LegFailed(
                f"{' '.join(argv[:4])}… did not finish in {timeout:.0f}s"
            ) from e
    return r.returncode, r.stdout


def tail(path: str, n: int = 12) -> str:
    try:
        with open(path, errors="replace") as fh:
            return "".join(fh.readlines()[-n:]).rstrip()
    except OSError:
        return ""


def last_json(stdout: str) -> dict:
    for line in reversed(stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise LegFailed("the child printed no JSON summary line")


def read_pfm(path: str):
    """(width, height, float32 samples as written: rows bottom-up)."""
    with open(path, "rb") as fh:
        kind = fh.readline().strip()
        w, h = (int(t) for t in fh.readline().split())
        scale = float(fh.readline())
        data = array.array("f")
        data.frombytes(fh.read())
    if kind != b"PF" or len(data) != w * h * 3:
        raise LegFailed(f"{path}: not a {w}x{h} colour PFM")
    if (scale > 0) == (sys.byteorder == "little"):
        data.byteswap()
    return w, h, data


def check_image(path: str, res: int) -> float:
    """The image exists, has the expected shape, is finite and not
    black; returns its mean."""
    if not os.path.exists(path):
        raise LegFailed(f"no image at {path}")
    w, h, data = read_pfm(path)
    if (w, h) != (res, res):
        raise LegFailed(f"{path} is {w}x{h}, expected {res}x{res}")
    total = math.fsum(data)  # finite iff every sample is
    if not math.isfinite(total):
        raise LegFailed(f"{path} holds non-finite pixels")
    mean = total / len(data)
    if not mean > 0.0:
        raise LegFailed(f"{path} is black (mean {mean})")
    return mean


def check_device(rep: dict, probe: dict) -> None:
    """A leg's own account of where it ran."""
    for key in ("platform", "device_kind", "devices", "jax"):
        if not rep.get(key):
            raise LegFailed(f"the leg reports no {key}: {rep}")
    if rep["platform"] == "cpu" and not DEBUG:
        raise LegFailed("the leg ran on the CPU")
    if (rep["platform"], rep["device_kind"]) != (
        probe["platform"], probe["kind"]
    ):
        raise LegFailed(f"the leg ran on {rep['device_kind']}, not {probe}")


def leg_line(name: str, rep: dict, **more) -> None:
    fields = dict(
        platform=rep.get("platform"), device_kind=rep.get("device_kind"),
        devices=rep.get("devices"), jax=rep.get("jax"),
        bvh_builder=rep.get("bvh_builder"),
        compile_s=rep.get("compile_seconds"),
        render_s=rep.get("render_seconds"), **more,
    )
    say(f"leg {name}: PASS " + " ".join(
        f"{k}={json.dumps(v)}" for k, v in fields.items()
    ))


# --------------------------------------------------------------------------
# set-up: the device, the scene files
# --------------------------------------------------------------------------


def probe_device(out: str) -> dict:
    """Ask a child what jax sees (the parent must not ask itself)."""
    code = (
        "import json, jax; d = jax.devices(); print(json.dumps({"
        "'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d), 'jax': jax.__version__}))"
    )
    rc, stdout = run_child(
        [sys.executable, "-c", code], os.path.join(out, "probe.log"),
        timeout=300,
    )
    if rc != 0:
        raise LegFailed(
            f"jax found no device (rc={rc}): "
            f"{tail(os.path.join(out, 'probe.log'), 3)}"
        )
    return last_json(stdout)


def write_scenes(out: str) -> dict:
    """The killeroo-like scene as .pbrt files + one shared PLY, written
    by tpu_pbrt.scenes (in git) in a child that is kept off the chip."""
    c = CFG
    variants = {
        "cli": (c["res"], c["cli_spp"]),
        "serve": (c["res"], c["serve_spp"]),
        "accuracy": (c["acc_res"], c["acc_spp"]),
    }
    paths = {
        k: os.path.join(out, f"killeroo-like-{r}x{r}-{s}spp.pbrt")
        for k, (r, s) in variants.items()
    }
    code = (
        "import json, sys\n"
        "from tpu_pbrt.scenes import write_killeroo_like\n"
        "for path, res, spp in json.loads(sys.argv[1]):\n"
        "    write_killeroo_like(path, res=res, spp=spp, "
        f"n_theta={c['n_theta']}, n_phi={c['n_phi']}, "
        "ply=sys.argv[2])\n"
    )
    jobs = [[paths[k], r, s] for k, (r, s) in variants.items()]
    rc, _ = run_child(
        [sys.executable, "-c", code, json.dumps(jobs),
         os.path.join(out, "killeroo-like.ply")],
        os.path.join(out, "scenes.log"),
        env=child_env(JAX_PLATFORMS="cpu"), timeout=300,
    )
    if rc != 0:
        raise LegFailed(
            f"writing the scene files failed (rc={rc}):\n"
            f"{tail(os.path.join(out, 'scenes.log'))}"
        )
    return paths


# --------------------------------------------------------------------------
# legs
# --------------------------------------------------------------------------


def render_cli(name, scene, image, out, probe, extra_args=()):
    """One `python -m tpu_pbrt.main` render; returns its summary line
    after the checks every render leg shares."""
    log = os.path.join(out, f"{name}.log")
    rc, stdout = run_child(
        [sys.executable, "-m", "tpu_pbrt.main", scene, "-o", image,
         *extra_args], log,
    )
    if rc != 0:
        raise LegFailed(f"tpu_pbrt.main exited {rc}:\n{tail(log)}")
    rep = last_json(stdout)
    with open(os.path.join(out, f"{name}.json"), "w") as fh:
        json.dump(rep, fh, indent=1)  # the whole summary, for PERF.md
    check_device(rep, probe)
    if rep.get("completed_fraction") != 1.0:
        raise LegFailed(f"completed_fraction={rep.get('completed_fraction')}")
    if rep.get("programs_after_first_chunk") != 0:
        raise LegFailed(
            f"{rep.get('programs_after_first_chunk')} program(s) were "
            "built after the first chunk"
        )
    if rep.get("redispatches") != 0:
        raise LegFailed(f"{rep.get('redispatches')} chunk re-dispatch(es)")
    return rep


def leg_cli(scenes, out, probe) -> str:
    image = os.path.join(out, "killeroo.pfm")
    rep = render_cli("cli", scenes["cli"], image, out, probe)
    mean = check_image(image, CFG["res"])
    leg_line(
        "cli", rep, image_mean=round(mean, 5),
        first_dispatch_s=(rep.get("phase_seconds") or {}).get(
            "dispatch_compile"),
        cache_hits=rep.get("cache_hits"), cache_misses=rep.get("cache_misses"),
    )
    return image


def leg_accuracy(scenes, out, probe) -> None:
    image = os.path.join(out, "killeroo-accuracy.pfm")
    rep = render_cli("accuracy", scenes["accuracy"], image, out, probe)
    res = CFG["acc_res"]
    check_image(image, res)
    if DEBUG:
        leg_line("accuracy", rep, mse="skipped in a debug run")
        return
    import numpy as np  # not jax: the reference is an .npz

    ref_path = os.path.join(
        HERE, "refimg", f"killeroo_cpu_{res}x{res}_{CFG['acc_spp']}spp.npz"
    )
    with np.load(ref_path) as z:
        ref = np.asarray(z["image"], np.float32)
    _, _, data = read_pfm(image)
    img = np.asarray(data, np.float32).reshape(res, res, 3)[::-1]
    mse = float(np.mean((img - ref) ** 2))
    if not mse <= MSE_BOUND:
        raise LegFailed(
            f"MSE {mse:.3e} against {os.path.basename(ref_path)} exceeds "
            f"{MSE_BOUND:.0e}"
        )
    leg_line("accuracy", rep, mse=mse, bound=MSE_BOUND)


class Daemon:
    """`python -m tpu_pbrt.main --serve --quiet` as a child, spoken to
    in its JSONL protocol; a reader thread keeps the pipe drained."""

    def __init__(self, log_path: str, extra_args=()):
        self.log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tpu_pbrt.main", "--serve", "--quiet",
             *extra_args],
            cwd=HERE, env=child_env(), stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self.log, text=True, bufsize=1,
        )
        self.lines: "queue.Queue[str]" = queue.Queue()
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put("")  # EOF

    def rpc(self, req: dict, timeout: float = 120.0) -> dict:
        self.proc.stdin.write(json.dumps(req) + "\n")
        self.proc.stdin.flush()
        end = time.monotonic() + min(timeout, max(remaining(), 1.0))
        while True:
            try:
                line = self.lines.get(timeout=max(end - time.monotonic(), 0.01))
            except queue.Empty:
                raise LegFailed(f"no answer to {req} in {timeout:.0f}s") from None
            if not line:
                raise LegFailed(f"the daemon closed its pipe on {req}")
            msg = json.loads(line)
            if "event" in msg:  # asynchronous done/failed notices
                continue
            if not msg.get("ok"):
                raise LegFailed(f"{req} answered {msg}")
            return msg

    def wait_done(self, job: str) -> dict:
        while True:
            p = self.rpc({"op": "poll", "job": job})
            if p["status"] == "done":
                return p
            if p["status"] in ("failed", "cancelled") or remaining() <= 0:
                raise LegFailed(f"job {job} ended {p['status']}: {p}")
            time.sleep(0.25)

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.log.close()


def leg_serve(name, scenes, out, probe, extra_args=(), want_devices=1):
    log = os.path.join(out, f"{name}.log")
    d = Daemon(log, extra_args)
    try:
        images, request_s = {}, {}

        def run_job(job, scene, **req):
            t = time.monotonic()
            d.rpc({"op": "submit", "scene": scene, "job": job, **req})
            d.wait_done(job)
            # submit to done on this host's clock: what a client waits,
            # scene compile and program build included
            request_s[job] = round(time.monotonic() - t, 3)
            images[job] = os.path.join(out, f"{name}-{job}.pfm")
            return d.rpc({"op": "result", "job": job, "out": images[job]})

        cold = run_job("cold", scenes["serve"])
        run_job("cornell", os.path.join(HERE, "scenes", "cornell-path.pbrt"),
                **CFG["cornell_req"])
        before = d.rpc({"op": "stats"})
        warm = run_job("warm", scenes["serve"])
        after = d.rpc({"op": "stats"})
        health = d.rpc({"op": "health"})
        d.proc.stdin.write(json.dumps({"op": "shutdown", "drain": True}) + "\n")
        d.proc.stdin.flush()
        try:
            rc = d.proc.wait(timeout=min(120.0, max(remaining(), 1.0)))
        except subprocess.TimeoutExpired:
            raise LegFailed("the daemon did not exit after shutdown") from None
        if rc != 0:
            raise LegFailed(f"the daemon exited {rc}:\n{tail(log)}")
    except LegFailed as e:
        raise LegFailed(f"{e}\n{tail(log)}") from None
    finally:
        d.close()

    rep = dict(after["process"])
    check_device(rep, probe)
    scene_compiles = (
        after["residency"]["scene_compiles"]
        - before["residency"]["scene_compiles"]
    )
    programs = after["process"]["programs"] - before["process"]["programs"]
    if scene_compiles or programs:
        raise LegFailed(
            f"the warm job paid {scene_compiles} scene compile(s) and "
            f"{programs} built program(s)"
        )
    if health.get("firing"):  # (a firing report also answers ok=false)
        raise LegFailed(f"health fired: {health}")
    check_image(images["cold"], CFG["res"])
    check_image(images["cornell"], CFG["cornell_res"])
    with open(images["cold"], "rb") as a, open(images["warm"], "rb") as b:
        if a.read() != b.read():
            raise LegFailed("the warm job's film differs from the cold job's")
    stats = warm["stats"]
    rep["render_seconds"] = cold["seconds"]
    waves = ((stats.get("telemetry") or {}).get("wave_spread") or {}).get(
        "per_device_waves", [])
    if len(waves) != want_devices or not all(w > 0 for w in waves):
        raise LegFailed(
            f"wave_spread {waves}: expected {want_devices} device(s), each "
            "with waves"
        )
    leg_line(name, rep, request_s=request_s, per_device_waves=waves,
             warm_scene_compiles=scene_compiles, warm_programs=programs)


def leg_mesh4(scenes, out, probe, single_image) -> None:
    image = os.path.join(out, "killeroo-mesh4.pfm")
    rep = render_cli(
        "mesh4-cli", scenes["cli"], image, out, probe,
        extra_args=("--mesh", "4"),
    )
    check_image(image, CFG["res"])
    waves = (rep.get("wave_spread") or {}).get("per_device_waves", [])
    if len(waves) != 4 or not all(w > 0 for w in waves):
        raise LegFailed(f"wave_spread {waves}: expected four busy devices")
    # the same samples, partitioned over the devices: the films agree to
    # f32 accumulation order (README "Distributed rendering";
    # tests/test_distributed.py's bound)
    a, b = read_pfm(single_image)[2], read_pfm(image)[2]
    worst = max(abs(x - y) - 1e-4 * abs(x) for x, y in zip(a, b))
    if worst > 1e-5:
        raise LegFailed(
            f"the four-device film is off the one-device film by {worst:.3e} "
            "beyond rtol 1e-4"
        )
    leg_line("mesh4-cli", rep, per_device_waves=waves)
    leg_serve("mesh4-serve", scenes, out, probe,
              extra_args=("--mesh", "4"), want_devices=4)


def main() -> int:
    out = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out, exist_ok=True)
    try:
        probe = probe_device(out)
        say(f"device: {json.dumps(probe)}" + (" [DEBUG RUN]" if DEBUG else ""))
        if probe["platform"] == "cpu" and not DEBUG:
            raise LegFailed("jax found no accelerator (platform=cpu)")
        if not probe.get("kind"):
            raise LegFailed(f"jax reports no device_kind: {probe}")
        scenes = write_scenes(out)
        legs = [
            ("cli", lambda: leg_cli(scenes, out, probe)),
            ("accuracy", lambda: leg_accuracy(scenes, out, probe)),
            ("serve", lambda: leg_serve("serve", scenes, out, probe)),
        ]
        single_image = None
        for name, leg in legs:
            t = time.monotonic()
            try:
                result = leg()
            except LegFailed as e:
                raise LegFailed(f"leg {name}: FAIL {e}") from None
            if name == "cli":
                single_image = result
            say(f"leg {name}: {time.monotonic() - t:.1f}s")
        if probe["count"] >= 4:
            t = time.monotonic()
            try:
                leg_mesh4(scenes, out, probe, single_image)
            except LegFailed as e:
                raise LegFailed(f"leg mesh4: FAIL {e}") from None
            say(f"leg mesh4: {time.monotonic() - t:.1f}s")
        else:
            say(f"leg mesh4: did not run ({probe['count']} device visible)")
    except LegFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"total: {time.monotonic() - T0:.1f}s")
    if DEBUG:
        print("chip_smoke: debug run — no result", file=sys.stderr)
        return 3
    print(json.dumps({
        "ok": True,
        "device": {"platform": probe["platform"], "kind": probe["kind"],
                   "count": probe["count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
