"""hbmcheck — analysis layer 6: static HBM residency, liveness &
capacity verification across the serve stack (ISSUE 18).

The serve knobs' hand-set caps become checked consequences of a
committed model: an aval-level static model of DEVICE memory
across the full serve lifecycle — resident compiled scenes
(`residency.scene_hbm_bytes`), per-job film/counter carries, the
pipeline window's un-donated depth-N slices, the `_prefetch_next`
activation, and develop/preview staging — gated by four rule families:

- **HC-CAP** — the worst-case simultaneous footprint under
  `TPU_PBRT_SERVE_RESIDENT_MB` x `max_active` x `TPU_PBRT_PIPELINE` x
  prefetch must fit a per-platform HBM capacity table with headroom,
  committed to `analysis/hbm_budgets.json` via the shared
  `--update-budgets` workflow. `--derive-hbm-caps` inverts the
  model: per HBM size it emits the
  largest safe (resident MB, max_active, pipeline depth) triple, and
  the committed serve knob defaults are validated against it.
- **HC-LEAK** — an abstract refcount over the serve code paths: every
  function that drives a job to a terminal status must provably drop
  EVERY device reference that job holds (film carry, in-flight window,
  per-slice counter scalars) AND unpin its resident scene, on every
  exit path — park, cancel, fail, finalize. Residency eviction must
  consult pin counts before dropping an entry.
- **HC-ACCT** — residency's ESTIMATED footprints (what the LRU evicts
  on) must match aval-derived exact bytes within tolerance, checked
  against a deterministic reference scene and the live FilmState
  layout.
- **HC-ALIAS** — donation-aliased carries counted ONCE: the symbolic
  window buffer graph (depth-1 donated in/out alias, the deferred
  checkpoint snapshot reference) deduped over alias edges must
  reproduce the closed-form per-job footprint exactly.

The static pass is cross-validated dynamically by protocheck's
PROTO-HBM invariant (layer 5): the same model evaluated on the LIVE
service after every explorer decision must stay under this module's
static worst case and return to baseline at drain.

Shares the `# jaxlint: disable=HC-*` pragma grammar with the other
layers. Runs without any accelerator; only HC-ACCT touches jax (a
tree-leaves walk over numpy arrays).
"""

from __future__ import annotations

import ast
import hashlib
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from tpu_pbrt.analysis.lint import Violation
from tpu_pbrt.analysis.protocheck import _pragma_lines, _shallow_walk, repo_root

BUDGETS_PATH = Path(__file__).resolve().parent / "hbm_budgets.json"
DEFAULT_TOLERANCE = 0.10

GiB = 1024 ** 3
#: per-chip HBM by platform — the capacity table HC-CAP gates against
#: (worst case = smallest platform)
HBM_BYTES = {"v4": 32 * GiB, "v5e": 16 * GiB, "v5p": 95 * GiB}
#: fraction of HBM the serve model may plan for — the rest is XLA
#: scratch, fragmentation slack, and compiled-program temporaries the
#: static model cannot see
HBM_HEADROOM = 0.80

#: the four per-slice counter scalars a dispatch appends (ray/occ/ctr/
#: nf device int64s on RenderJob's counter lists), 8 B each
COUNTER_BYTES_PER_SLICE = 4 * 8
#: reference film for the worst-case model and the budget entries
REF_FILM = (512, 512)
#: reference concurrent-job load (the serve selftest runs 2; 4 is the
#: planning headroom the derive output is inverted against)
REF_MAX_ACTIVE = 4

HC_RULES = {
    "HC-CAP": "worst-case serve HBM footprint exceeds platform capacity "
              "with headroom, or a configured knob exceeds its derived cap",
    "HC-LEAK": "a serve path drives a job terminal without releasing its "
               "device buffers, or eviction ignores pin counts",
    "HC-ACCT": "residency's estimated footprint drifts from aval-exact "
               "bytes beyond tolerance",
    "HC-ALIAS": "a donation-aliased carry is double counted in the "
                "window model",
    "HC-PARSE": "file does not parse",
}


# --------------------------------------------------------------------------
# the memory model
# --------------------------------------------------------------------------


def film_state_bytes(rx: int, ry: int) -> int:
    """Device bytes of ONE film accumulator carry at rx x ry, derived
    from the LIVE FilmState layout (a 2x2 numpy probe, scaled) — not a
    hardcoded per-pixel constant, so a new film plane shows up here and
    HC-ACCT catches residency drifting from it."""
    import numpy as np

    from tpu_pbrt.core.film import FilmState

    probe = FilmState(
        rgb=np.zeros((2, 2, 3), np.float32),
        weight=np.zeros((2, 2), np.float32),
        splat=np.zeros((2, 2, 3), np.float32),
    )
    per_pixel = sum(int(leaf.nbytes) for leaf in probe) // 4
    return int(rx) * int(ry) * per_pixel


def develop_staging_bytes(rx: int, ry: int) -> int:
    """The develop/preview staging buffer: one RGB f32 image the film
    resolve materializes before the D2H copy."""
    return int(rx) * int(ry) * 3 * 4


def job_hbm_bytes(film_bytes: int, depth: int) -> int:
    """Closed-form worst-case device bytes ONE mid-dispatch job holds:
    live film carries (donation collapses depth 1 to a single buffer;
    depth > 1 keeps every un-donated in-flight input plus the newest
    output — see integrators.common.live_film_carries) plus the
    per-slice counter scalars for a full window."""
    from tpu_pbrt.integrators.common import live_film_carries

    d = max(1, int(depth))
    return live_film_carries(d) * int(film_bytes) + d * COUNTER_BYTES_PER_SLICE


def serve_model(
    rx: Optional[int] = None, ry: Optional[int] = None,
    depth: Optional[int] = None, max_active: Optional[int] = None,
    prefetch: Optional[bool] = None,
    resident_bytes: Optional[int] = None,
) -> Dict[str, Any]:
    """The worst-case simultaneous serve footprint, knobs defaulting
    from the live config: resident scenes at the full LRU budget +
    max_active mid-dispatch jobs + the prefetched next activation (one
    freshly-initialized film carry; its first dispatch has not pushed a
    slice yet) + develop staging."""
    from tpu_pbrt.config import cfg

    if rx is None or ry is None:
        rx, ry = REF_FILM
    if depth is None:
        depth = int(cfg.pipeline)
    if max_active is None:
        max_active = REF_MAX_ACTIVE
    if prefetch is None:
        prefetch = bool(cfg.serve_prefetch)
    if resident_bytes is None:
        resident_bytes = (
            int(cfg.serve_resident_mb * 1e6) if cfg.serve_resident_mb else 0
        )
    fb = film_state_bytes(rx, ry)
    jb = job_hbm_bytes(fb, depth)
    pf = fb if prefetch else 0
    st = develop_staging_bytes(rx, ry)
    total = int(resident_bytes) + max_active * jb + pf + st
    return {
        "film": [int(rx), int(ry)],
        "depth": int(depth),
        "max_active": int(max_active),
        "prefetch": bool(prefetch),
        "film_state_bytes": fb,
        "resident_bytes": int(resident_bytes),
        "job_bytes": jb,
        "jobs_bytes": max_active * jb,
        "prefetch_bytes": pf,
        "staging_bytes": st,
        "total_bytes": total,
    }


def check_capacity(
    model: Optional[Dict[str, Any]] = None, headroom: float = HBM_HEADROOM,
) -> List[str]:
    """HC-CAP: the worst-case simultaneous footprint must fit the
    smallest platform's HBM with headroom — statically, before any
    serve process sees a chip."""
    m = model if model is not None else serve_model()
    platform, cap = min(HBM_BYTES.items(), key=lambda kv: kv[1])
    budget = int(cap * headroom)
    if m["total_bytes"] <= budget:
        return []
    return [
        f"HC-CAP: worst-case serve footprint {m['total_bytes']} B "
        f"(resident {m['resident_bytes']} + {m['max_active']} jobs x "
        f"{m['job_bytes']} + prefetch {m['prefetch_bytes']} + staging "
        f"{m['staging_bytes']}) exceeds {budget} B ({headroom:.0%} of "
        f"{platform} HBM {cap} B) — lower TPU_PBRT_SERVE_RESIDENT_MB, "
        "max_active or TPU_PBRT_PIPELINE"
    ]


# --------------------------------------------------------------------------
# HC-ACCT: residency estimates vs aval-exact bytes
# --------------------------------------------------------------------------


class _RefFilm:
    full_resolution = REF_FILM


class _RefScene:
    """A deterministic synthetic compiled-scene stand-in: a mixed-dtype
    nested dev pytree shaped like the real upload (tri soup, stream
    slabs, texture atlas, light CDF, material table) — enough leaf
    variety that an estimator taking dtype or nesting shortcuts drifts
    measurably from the exact walk."""

    def __init__(self):
        import numpy as np

        self.film = _RefFilm()
        self.dev = {
            "tri_verts9T": np.zeros((9, 4096), np.float32),
            "tstream": {
                "slabs48": np.zeros((48, 2048), np.float32),
                "child_idx": np.zeros((8, 2048), np.int32),
            },
            "tex_atlas_u8": np.zeros((256, 256, 3), np.uint8),
            "light_cdf": np.zeros((129,), np.float32),
            "mat_table": np.zeros((64, 16), np.float32),
        }


def reference_scene():
    return _RefScene()


def exact_scene_bytes(scene) -> int:
    """Aval-derived exact device bytes: shape x itemsize per dev leaf —
    deliberately independent of any `nbytes` attribute the estimator
    shortcuts through — plus the film term from the live FilmState
    layout."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree.leaves(scene.dev):
        shape = getattr(leaf, "shape", None)
        dims = tuple(shape) if shape is not None else (int(np.size(leaf)),)
        n = 1
        for d in dims:
            n *= int(d)
        total += n * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
    rx, ry = scene.film.full_resolution
    return total + film_state_bytes(rx, ry)


def acct_check(
    scene=None, tolerance: float = DEFAULT_TOLERANCE,
) -> List[str]:
    """HC-ACCT: the LRU evicts on `scene_hbm_bytes` estimates — they
    must track aval-exact bytes within tolerance, and residency's
    per-pixel film constant must match the live FilmState layout."""
    from tpu_pbrt.serve import residency

    errors: List[str] = []
    live_px = film_state_bytes(1, 1)
    if residency.FILM_BYTES_PER_PIXEL != live_px:
        errors.append(
            f"HC-ACCT: residency charges {residency.FILM_BYTES_PER_PIXEL} "
            f"B/pixel of film but the live FilmState layout is {live_px} "
            "B/pixel — the LRU would evict on wrong numbers; update "
            "residency.FILM_BYTES_PER_PIXEL"
        )
    sc = scene if scene is not None else reference_scene()
    est = residency.scene_hbm_bytes(sc)
    exact = exact_scene_bytes(sc)
    if exact > 0:
        ratio = est / exact
        if not (1.0 - tolerance <= ratio <= 1.0 + tolerance):
            errors.append(
                f"HC-ACCT: residency estimates {est} B for the reference "
                f"scene but the aval-exact footprint is {exact} B "
                f"({ratio:.2f}x, tolerance {tolerance:.0%}) — the LRU "
                "evicts on wrong numbers"
            )
    return errors


# --------------------------------------------------------------------------
# HC-ALIAS: donation-aliased carries counted once
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Buf:
    """A symbolic device buffer in the window model. `alias_of` names
    another Buf this one shares storage with (donation in/out, the
    deferred checkpoint snapshot); `donated` marks a dispatch output
    that MUST alias its input carry."""

    name: str
    nbytes: int
    alias_of: Optional[str] = None
    donated: bool = False


def job_buffers(
    film_bytes: int, depth: int, cadence: bool = True,
) -> List[Buf]:
    """The symbolic live-buffer set of one job mid-dispatch at `depth`.
    Depth 1 compiles donation into the chunk closure — the dispatch
    output ALIASES the input accumulator, one buffer. Depth > 1
    compiles donation out (deferred checkpoint writes may still read
    superseded carries), so each in-flight slice pins its un-donated
    input carry plus the newest output. The checkpoint cadence snapshot
    is a REFERENCE to an existing carry, never an allocation."""
    d = max(1, int(depth))
    bufs: List[Buf] = [Buf("carry0", int(film_bytes))]
    if d == 1:
        bufs.append(
            Buf("carry_out", int(film_bytes), alias_of="carry0", donated=True)
        )
    else:
        bufs.extend(
            Buf(f"carry{i}", int(film_bytes)) for i in range(1, d + 1)
        )
    if cadence:
        bufs.append(Buf("ckpt_snap", int(film_bytes), alias_of="carry0"))
    bufs.extend(
        Buf(f"counters{i}", COUNTER_BYTES_PER_SLICE) for i in range(d)
    )
    return bufs


def _alias_root(buf: Buf, by_name: Dict[str, Buf]) -> Optional[str]:
    seen = set()
    while buf.alias_of is not None:
        if buf.alias_of in seen or buf.alias_of not in by_name:
            return None
        seen.add(buf.name)
        buf = by_name[buf.alias_of]
    return buf.name


def dedup_bytes(bufs: List[Buf]) -> int:
    """Total bytes counting each alias class ONCE (by its root)."""
    by_name = {b.name: b for b in bufs}
    roots, total = set(), 0
    for b in bufs:
        r = _alias_root(b, by_name)
        if r is None or r in roots:
            continue
        roots.add(r)
        total += by_name[r].nbytes
    return total


def check_alias(bufs: List[Buf]) -> List[str]:
    """HC-ALIAS structural checks on a buffer graph: donated outputs
    must carry an alias edge (else the model double-counts the carry)
    and every alias edge must resolve."""
    errors: List[str] = []
    by_name: Dict[str, Buf] = {}
    for b in bufs:
        if b.name in by_name:
            errors.append(
                f"HC-ALIAS: duplicate buffer name {b.name!r} in the "
                "window model"
            )
        by_name[b.name] = b
    for b in bufs:
        if b.donated and b.alias_of is None:
            errors.append(
                f"HC-ALIAS: {b.name!r} is donation-aliased but carries "
                "no alias edge — the model would double-count the carry"
            )
        if b.alias_of is not None and b.alias_of not in by_name:
            errors.append(
                f"HC-ALIAS: {b.name!r} aliases unknown buffer "
                f"{b.alias_of!r}"
            )
    return errors


def alias_audit(depths: Tuple[int, ...] = (1, 2, 3)) -> List[str]:
    """HC-ALIAS self-consistency: at every depth the symbolic buffer
    graph, deduped over alias edges, must reproduce `job_hbm_bytes`
    exactly — the closed form HC-CAP plans with and the graph HC-ALIAS
    audits are the SAME model."""
    errors: List[str] = []
    fb = film_state_bytes(*REF_FILM)
    for d in depths:
        bufs = job_buffers(fb, d)
        errors.extend(check_alias(bufs))
        got, want = dedup_bytes(bufs), job_hbm_bytes(fb, d)
        if got != want:
            errors.append(
                f"HC-ALIAS: window model at depth {d} counts {got} B "
                f"after alias dedup but the closed-form job footprint is "
                f"{want} B — a donated or snapshot carry is double counted"
            )
    return errors


# --------------------------------------------------------------------------
# HC-LEAK: abstract refcount over the serve code paths
# --------------------------------------------------------------------------

_SERVICE_MOD = "tpu_pbrt/serve/service.py"
_RESIDENCY_MOD = "tpu_pbrt/serve/residency.py"
_TERMINAL_NAMES = frozenset({"FAILED", "CANCELLED", "DONE"})
_COUNTER_LISTS = frozenset(
    {"ray_counts", "occ_counts", "ctr_counts", "nf_counts"}
)


def _leak_service(tree: ast.AST, rel: str) -> List[Violation]:
    """Every function in service.py that assigns a terminal status must
    release the job's device buffers on that path — either by calling
    `_release_device` or by nulling `.state` AND clearing all four
    counter lists inline — and must `unpin` the resident scene."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        terminal_line = None
        has_release = has_unpin = has_state_none = False
        cleared: set = set()
        for n in _shallow_walk(node):
            if isinstance(n, ast.Assign):
                if (
                    isinstance(n.value, ast.Name)
                    and n.value.id in _TERMINAL_NAMES
                    and any(
                        isinstance(t, ast.Attribute) and t.attr == "status"
                        for t in n.targets
                    )
                ):
                    terminal_line = terminal_line or n.lineno
                if (
                    isinstance(n.value, ast.Constant)
                    and n.value.value is None
                    and any(
                        isinstance(t, ast.Attribute) and t.attr == "state"
                        for t in n.targets
                    )
                ):
                    has_state_none = True
            elif isinstance(n, ast.Call) and isinstance(
                n.func, ast.Attribute
            ):
                if n.func.attr == "_release_device":
                    has_release = True
                elif n.func.attr == "unpin":
                    has_unpin = True
                elif n.func.attr == "clear" and isinstance(
                    n.func.value, ast.Attribute
                ) and n.func.value.attr in _COUNTER_LISTS:
                    cleared.add(n.func.value.attr)
        if terminal_line is None:
            continue
        inline_release = has_state_none and cleared == set(_COUNTER_LISTS)
        if not (has_release or inline_release):
            out.append(Violation(
                "HC-LEAK", rel, terminal_line,
                f"{node.name}() drives a job to a terminal status but "
                "releases no device buffers on that path — call "
                "_release_device(job) (or null .state and clear all four "
                "counter lists) so the film carry, in-flight window and "
                "per-slice counters drop with the job", "error",
            ))
        if not has_unpin:
            out.append(Violation(
                "HC-LEAK", rel, terminal_line,
                f"{node.name}() drives a job to a terminal status without "
                "releasing its residency pin — the scene can never be "
                "evicted and the LRU budget silently shrinks", "error",
            ))
    return out


def _leak_residency(tree: ast.AST, rel: str) -> List[Violation]:
    """Any function that drops a resident entry (`del ..._entries[...]`)
    must consult pin counts in the same function — otherwise a pinned
    scene under a live job could be evicted out from under it."""
    out: List[Violation] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        del_line = None
        sees_pins = False
        for n in _shallow_walk(node):
            if isinstance(n, ast.Delete):
                for t in n.targets:
                    if (
                        isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and t.value.attr == "_entries"
                    ):
                        del_line = del_line or n.lineno
            if isinstance(n, ast.Attribute) and n.attr == "pins":
                sees_pins = True
        if del_line is not None and not sees_pins:
            out.append(Violation(
                "HC-LEAK", rel, del_line,
                f"{node.name}() drops a resident entry without consulting "
                "pin counts — a pinned scene under a live job could be "
                "evicted out from under it", "error",
            ))
    return out


def hc_leak_source(src: str, rel: str) -> List[Violation]:
    """HC-LEAK over one source blob. Module scoping is by `rel` (the
    repo-relative path), like the SV-* rules; the shared
    `# jaxlint: disable=HC-LEAK` pragma grammar applies (a pragma on
    the def line covers the whole function)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Violation(
            "HC-PARSE", rel, e.lineno or 0,
            f"does not parse: {e.msg}", "error",
        )]
    found: List[Violation] = []
    if rel.endswith(_SERVICE_MOD.rsplit("/", 1)[-1]) and "serve" in rel:
        found.extend(_leak_service(tree, rel))
    if rel.endswith(_RESIDENCY_MOD.rsplit("/", 1)[-1]) and "serve" in rel:
        found.extend(_leak_residency(tree, rel))
    per_line, file_wide = _pragma_lines(src)
    def_lines = {
        n.lineno: n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    kept = []
    for v in found:
        rules = per_line.get(v.line, set()) | file_wide
        # a pragma on the enclosing def line covers the function body
        for ln, fn in def_lines.items():
            if fn.lineno <= v.line <= (fn.end_lineno or fn.lineno):
                rules |= per_line.get(ln, set())
        if v.rule in rules or "all" in rules:
            continue
        kept.append(v)
    return sorted(kept, key=lambda v: (v.line, v.rule))


def hc_leak_tree(root: Optional[str] = None) -> List[Violation]:
    base = Path(root if root else repo_root())
    out: List[Violation] = []
    for rel in (_SERVICE_MOD, _RESIDENCY_MOD):
        p = base / rel
        if p.exists():
            out.extend(hc_leak_source(p.read_text(), rel))
    return out


# --------------------------------------------------------------------------
# budgets: the committed hbm_budgets.json gate
# --------------------------------------------------------------------------


def _fingerprint(detail: Dict[str, Any]) -> str:
    return hashlib.sha1(
        json.dumps(detail, sort_keys=True).encode()
    ).hexdigest()[:12]


def collect_entries(
    model: Optional[Dict[str, Any]] = None,
) -> Dict[str, Dict[str, Any]]:
    """The budget entries the gate tracks: every term of the worst-case
    model plus the reference-scene estimate HC-ACCT audits."""
    from tpu_pbrt.serve.residency import scene_hbm_bytes

    m = model if model is not None else serve_model()
    ref_bytes = int(scene_hbm_bytes(reference_scene()))

    def entry(nbytes: int, **detail) -> Dict[str, Any]:
        return {
            "hbm_bytes": int(nbytes),
            "fingerprint": _fingerprint(detail),
            "detail": detail,
        }

    return {
        "serve.film_state": entry(
            m["film_state_bytes"], film=m["film"],
            per_pixel=film_state_bytes(1, 1),
        ),
        "serve.job": entry(
            m["job_bytes"], depth=m["depth"],
            counter_bytes_per_slice=COUNTER_BYTES_PER_SLICE,
        ),
        "serve.prefetch": entry(m["prefetch_bytes"], enabled=m["prefetch"]),
        "serve.staging": entry(m["staging_bytes"], film=m["film"]),
        "serve.worst_case": entry(
            m["total_bytes"], resident_bytes=m["resident_bytes"],
            max_active=m["max_active"], depth=m["depth"],
        ),
        "scene.reference": entry(ref_bytes, film=list(REF_FILM)),
    }


def load_budgets(path: Optional[Path] = None) -> Dict:
    p = Path(path) if path is not None else BUDGETS_PATH
    if not p.exists():
        return {"tolerance": DEFAULT_TOLERANCE, "entries": {}}
    return json.loads(p.read_text())


def save_budgets(
    entries: Dict[str, Dict[str, Any]], path: Optional[Path] = None,
    tolerance: float = DEFAULT_TOLERANCE,
) -> Path:
    import jax

    p = Path(path) if path is not None else BUDGETS_PATH
    data = {
        "_comment": (
            "Static HBM footprints of the serve memory model (hbmcheck, "
            "ISSUE 18): film carry, per-job worst case, prefetch slot, "
            "develop staging, the total worst-case watermark, and the "
            "residency estimate of the reference scene. Regenerate with "
            "`python -m tpu_pbrt.analysis --update-budgets` after an "
            "INTENTIONAL serve/film change; CI fails when a footprint "
            "drifts past tolerance or the worst case exceeds platform "
            "HBM with headroom."
        ),
        "tolerance": tolerance,
        "hbm_headroom": HBM_HEADROOM,
        "jax_version": jax.__version__,
        "entries": {k: dict(v) for k, v in sorted(entries.items())},
    }
    p.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    return p


def check_budgets(
    entries: Dict[str, Dict[str, Any]], budgets: Dict,
) -> Tuple[List[str], List[str]]:
    errors: List[str] = []
    warnings: List[str] = []
    tol = float(budgets.get("tolerance", DEFAULT_TOLERANCE))
    committed = budgets.get("entries", {})
    for key, info in sorted(entries.items()):
        b = committed.get(key)
        if b is None:
            errors.append(
                f"{key}: no committed HBM budget — run "
                "`python -m tpu_pbrt.analysis --update-budgets` and "
                "commit hbm_budgets.json"
            )
            continue
        base = int(b.get("hbm_bytes", 0))
        if base > 0:
            ratio = info["hbm_bytes"] / base
            if ratio > 1.0 + tol:
                errors.append(
                    f"{key}: static HBM footprint regressed {ratio:.2f}x "
                    f"({base} -> {info['hbm_bytes']} B, tolerance "
                    f"{tol:.0%}) — shrink the footprint or, if "
                    "intentional, refresh with --update-budgets"
                )
            elif ratio < 1.0 - tol:
                warnings.append(
                    f"{key}: static HBM footprint improved {ratio:.2f}x "
                    f"({base} -> {info['hbm_bytes']} B) — ratchet with "
                    "--update-budgets"
                )
        if b.get("fingerprint") and b["fingerprint"] != info["fingerprint"]:
            warnings.append(
                f"{key}: model structure fingerprint changed "
                f"({b['fingerprint']} -> {info['fingerprint']}) — refresh "
                "hbm_budgets.json if the footprint above looks right"
            )
    for key in committed:
        if key not in entries and not key.startswith("_"):
            warnings.append(
                f"{key}: committed HBM budget has no live model term — "
                "remove it with --update-budgets"
            )
    return errors, warnings


# --------------------------------------------------------------------------
# cap derivation: invert the model per platform (mirror of PC-CAPS)
# --------------------------------------------------------------------------


def derive_hbm_caps(headroom: float = HBM_HEADROOM) -> Dict:
    """Invert the serve model per platform: with the OTHER knobs at
    their configured values, the largest safe resident-scene budget
    (MB), the largest safe max_active, and the deepest safe pipeline
    window. The hand-set config.py serve knobs are validated against
    these (HC-CAP) — the knobs become consequences of the model, not
    folklore."""
    from tpu_pbrt.config import cfg

    rx, ry = REF_FILM
    fb = film_state_bytes(rx, ry)
    depth = int(cfg.pipeline)
    jb = job_hbm_bytes(fb, depth)
    pf = fb if cfg.serve_prefetch else 0
    st = develop_staging_bytes(rx, ry)
    cfg_res_mb = (
        float(cfg.serve_resident_mb) if cfg.serve_resident_mb else None
    )
    res_bytes = int(cfg_res_mb * 1e6) if cfg_res_mb else 0

    out: Dict[str, Any] = {
        "headroom": headroom,
        "configured": {
            "serve_resident_mb": cfg_res_mb,
            "pipeline_depth": depth,
            "max_active": REF_MAX_ACTIVE,
            "prefetch": bool(cfg.serve_prefetch),
            "film": [rx, ry],
        },
        "platforms": {},
    }
    for platform, cap in sorted(HBM_BYTES.items()):
        budget = int(cap * headroom)
        # resident cap: everything the live jobs need comes first
        resident_raw = budget - REF_MAX_ACTIVE * jb - pf - st
        max_resident_mb = max(resident_raw // 1_000_000, 0)
        free = budget - res_bytes - pf - st
        max_active = max(free // jb, 0)
        # depth cap: a depth-d job (d > 1) costs (d+1) carries + d
        # counter slots = d*(fb + CTR) + fb; invert for the configured
        # active-job load
        per_job = free // max(REF_MAX_ACTIVE, 1)
        max_depth = max(
            int((per_job - fb) // (fb + COUNTER_BYTES_PER_SLICE)), 1,
        )
        out["platforms"][platform] = {
            "hbm_bytes": int(cap),
            "budget_bytes": budget,
            "job_bytes": jb,
            "max_resident_mb": int(max_resident_mb),
            "max_resident_mb_aligned": int(max_resident_mb // 1024 * 1024),
            "max_active": int(max_active),
            "max_pipeline_depth": max_depth,
        }
    return out


def check_hbm_caps(derived: Optional[Dict] = None) -> List[str]:
    """HC-CAP over the derived caps: every CONFIGURED serve knob must
    sit at or under its model-safe maximum on the smallest platform."""
    d = derived if derived is not None else derive_hbm_caps()
    plats = d["platforms"].values()
    worst_res = min(p["max_resident_mb"] for p in plats)
    worst_active = min(p["max_active"] for p in plats)
    worst_depth = min(p["max_pipeline_depth"] for p in plats)
    c = d["configured"]
    errors: List[str] = []
    if c["serve_resident_mb"] is not None and c["serve_resident_mb"] > worst_res:
        errors.append(
            f"HC-CAP: TPU_PBRT_SERVE_RESIDENT_MB="
            f"{c['serve_resident_mb']:g} exceeds the model-safe maximum "
            f"{worst_res} MB on the smallest platform — resident scenes "
            "at the cap would overflow HBM under the live-job load; "
            "lower the budget or the job knobs"
        )
    if c["max_active"] > worst_active:
        errors.append(
            f"HC-CAP: the reference max_active={c['max_active']} exceeds "
            f"the model-safe maximum {worst_active} at the configured "
            "resident budget"
        )
    if c["pipeline_depth"] > worst_depth:
        errors.append(
            f"HC-CAP: TPU_PBRT_PIPELINE={c['pipeline_depth']} exceeds "
            f"the model-safe maximum depth {worst_depth} at the "
            "configured resident budget — un-donated in-flight carries "
            "would overflow HBM"
        )
    return errors


# --------------------------------------------------------------------------
# bench hook: the static HBM half of the bench JSON line
# --------------------------------------------------------------------------


def bench_fields(rx: int = 512, ry: int = 512) -> Dict[str, Any]:
    """`static_hbm_per_job` + `hbm_headroom` for cost.py --bench-wave:
    rides bench.py's schema-stable JSON line (measured AND infra-outage
    paths). `hbm_headroom` is the fraction of the smallest platform's
    HBM budget still free at the current knob settings — negative means
    the configured serve load cannot fit."""
    m = serve_model(rx=rx, ry=ry)
    budget = min(HBM_BYTES.values()) * HBM_HEADROOM
    return {
        "static_hbm_per_job": int(m["job_bytes"]),
        "hbm_headroom": round(1.0 - m["total_bytes"] / budget, 4),
    }


# --------------------------------------------------------------------------
# entry points
# --------------------------------------------------------------------------


def run_hbmcheck(
    update: bool = False, budgets_path: Optional[Path] = None,
    root: Optional[str] = None, check_caps_too: bool = True,
) -> Tuple[List[str], List[str]]:
    """The full layer-7 pass: HC-LEAK tree scan, HC-ACCT, HC-ALIAS,
    HC-CAP capacity + budget gate (or refresh), and the derived-caps
    validation. Returns (errors, warnings) like the other layers."""
    errors: List[str] = []
    warnings: List[str] = []
    errors.extend(str(v) for v in hc_leak_tree(root))
    errors.extend(acct_check())
    errors.extend(alias_audit())
    model = serve_model()
    errors.extend(check_capacity(model))
    entries = collect_entries(model)
    if update:
        prev_tol = float(
            load_budgets(budgets_path).get("tolerance", DEFAULT_TOLERANCE)
        )
        save_budgets(entries, budgets_path, tolerance=prev_tol)
    else:
        e, w = check_budgets(entries, load_budgets(budgets_path))
        errors.extend(e)
        warnings.extend(w)
    if check_caps_too:
        try:
            errors.extend(check_hbm_caps())
        except Exception as e:  # noqa: BLE001 — a crashed derivation is a finding
            errors.append(
                f"HC-CAP derivation crashed: {type(e).__name__}: {e}"
            )
    return errors, warnings


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m tpu_pbrt.analysis.hbmcheck"
    )
    ap.add_argument(
        "--derive-hbm-caps", action="store_true",
        help="invert the serve HBM model: per platform, the largest "
             "safe (resident MB, max_active, pipeline depth) triple",
    )
    ap.add_argument("--update-budgets", action="store_true")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    args = ap.parse_args(argv)

    if args.derive_hbm_caps:
        if args.update_budgets:
            prev = float(
                load_budgets().get("tolerance", DEFAULT_TOLERANCE)
            )
            save_budgets(collect_entries(), tolerance=prev)
            print(f"hbm budgets refreshed -> {BUDGETS_PATH}")
        derived = derive_hbm_caps()
        if args.format == "json":
            print(json.dumps(derived, indent=2, sort_keys=True))
        else:
            c = derived["configured"]
            res = (
                f"{c['serve_resident_mb']:g}"
                if c["serve_resident_mb"] is not None else "unbounded"
            )
            print(
                f"configured: serve_resident_mb={res} "
                f"pipeline={c['pipeline_depth']} "
                f"max_active={c['max_active']} "
                f"prefetch={c['prefetch']} "
                f"(headroom {derived['headroom']:.0%})"
            )
            for name, p in sorted(derived["platforms"].items()):
                print(
                    f"{name}: HBM {p['hbm_bytes']} B -> budget "
                    f"{p['budget_bytes']} B; max_resident_mb "
                    f"{p['max_resident_mb']} (aligned "
                    f"{p['max_resident_mb_aligned']}), max_active "
                    f"{p['max_active']}, max_pipeline_depth "
                    f"{p['max_pipeline_depth']}; job {p['job_bytes']} B"
                )
        errors = check_hbm_caps(derived)
        for e in errors:
            print(f"ERROR: {e}")
        return 1 if errors else 0

    errors, warnings = run_hbmcheck(update=args.update_budgets)
    if args.format == "json":
        print(json.dumps(
            {"errors": errors, "warnings": warnings,
             "ok": not errors}
        ))
    else:
        for w in warnings:
            print(f"WARN: {w}")
        for e in errors:
            print(f"ERROR: {e}")
        if args.update_budgets:
            print(f"hbm budgets refreshed -> {BUDGETS_PATH}")
    return 1 if errors else 0


if __name__ == "__main__":
    import sys

    from tpu_pbrt.analysis.__main__ import _setup_jax_env

    _setup_jax_env()
    sys.exit(_main())
