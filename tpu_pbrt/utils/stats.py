"""Statistics registry, profiler, and progress reporting.

Capability match for pbrt-v3 src/core/stats.{h,cpp} and
progressreporter.{h,cpp} (SURVEY.md §5.1/§5.5):
- STAT_COUNTER / STAT_RATIO / STAT_PERCENT / STAT_INT_DISTRIBUTION /
  STAT_MEMORY_COUNTER -> a process-global StatsRegistry with the same
  categorized "Statistics:" report format ("category/Title" strings).
  pbrt's per-thread accumulators + ReportThreadStats merging are
  unnecessary: counts are produced by in-kernel integer reductions
  (summed on device, fetched per chunk) or host-side increments.
- the SIGPROF sampling profiler -> phase timers around the host-side
  chunk loop; on TPU the per-phase breakdown of the device program comes
  from the XLA profile (`python -m tpu_pbrt.main --profile DIR`, reduced
  by `python -m tpu_pbrt.obs phases`), not signal sampling.
- ProgressReporter: same API (update/done), ETA bar on stderr, honoring
  PBRT_PROGRESS_FREQUENCY and quiet mode.
"""

from __future__ import annotations

import sys
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict


class StatsRegistry:
    """Global named counters/distributions (stats.cpp StatsAccumulator)."""

    def __init__(self):
        self.counters: Dict[str, int] = defaultdict(int)
        self.memory: Dict[str, int] = defaultdict(int)
        self.ratios: Dict[str, list] = defaultdict(lambda: [0, 0])
        self.percents: Dict[str, list] = defaultdict(lambda: [0, 0])
        self.distributions: Dict[str, list] = defaultdict(lambda: [0, 0, None, None])
        self.phase_times: Dict[str, float] = defaultdict(float)

    # -- STAT_* macro equivalents ----------------------------------------
    def counter(self, name: str, value: int = 1):
        self.counters[name] += int(value)

    def memory_counter(self, name: str, nbytes: int):
        self.memory[name] += int(nbytes)

    def ratio(self, name: str, num: int = 0, denom: int = 0):
        r = self.ratios[name]
        r[0] += int(num)
        r[1] += int(denom)

    def percent(self, name: str, num: int = 0, denom: int = 0):
        p = self.percents[name]
        p[0] += int(num)
        p[1] += int(denom)

    def distribution(self, name: str, value):
        d = self.distributions[name]
        d[0] += float(value)  # float sums: "rays per camera ray" is ~1.x
        d[1] += 1
        d[2] = value if d[2] is None else min(d[2], value)
        d[3] = value if d[3] is None else max(d[3], value)

    @contextmanager
    def phase(self, name: str):
        """ProfilePhase RAII equivalent: wall-time per named phase."""
        t0 = time.time()
        try:
            yield
        finally:
            self.phase_times[name] += time.time() - t0

    def clear(self):
        self.__init__()

    # -- reporting (PrintStats / ReportProfilerResults) ------------------
    def report(self, out=None) -> str:
        lines = ["Statistics:"]
        by_cat = defaultdict(list)

        def add(title, text):
            if "/" in title:
                cat, t = title.split("/", 1)
            else:
                cat, t = "", title
            by_cat[cat].append((t, text))

        for name, v in sorted(self.counters.items()):
            add(name, f"{v:>12d}")
        for name, v in sorted(self.memory.items()):
            mib = v / (1024.0 * 1024.0)
            add(name, f"{mib:>12.2f} MiB")
        for name, (n, d) in sorted(self.ratios.items()):
            if d:
                add(name, f"{n:>12d} / {d:d} ({n / d:.2f}x)")
        for name, (n, d) in sorted(self.percents.items()):
            if d:
                add(name, f"{n:>12d} / {d:d} ({100.0 * n / d:.2f}%)")
        for name, (total, count, mn, mx) in sorted(self.distributions.items()):
            if count:
                add(name, f"{total / count:>12.3f} avg [range {mn} - {mx}]")
        for cat in sorted(by_cat):
            lines.append(f"  {cat or 'Misc'}")
            for t, text in by_cat[cat]:
                lines.append(f"    {t:<42}{text}")
        if self.phase_times:
            total = sum(self.phase_times.values())
            lines.append("  Profile (wall time)")
            for name, secs in sorted(self.phase_times.items(), key=lambda kv: -kv[1]):
                lines.append(f"    {name:<42}{secs:>10.2f}s ({100.0 * secs / max(total, 1e-9):5.1f}%)")
        text = "\n".join(lines)
        if out is not None:
            print(text, file=out)
        return text


STATS = StatsRegistry()


class ProgressReporter:
    """progressreporter.cpp ProgressReporter: +-style ETA bar. Updates are
    driven by the chunk loop (no background thread needed — chunks complete
    at millisecond-to-second cadence)."""

    def __init__(self, total_work: int, title: str, quiet: bool = False):
        self.total = max(1, int(total_work))
        self.title = title
        self.done_work = 0
        self.start = time.time()
        from tpu_pbrt.config import cfg

        freq = cfg.progress_frequency
        # `is not None`, not truthiness: PBRT_PROGRESS_FREQUENCY=0 means
        # print on every update (pbrt's continuous mode)
        self.min_interval = float(freq) if freq is not None else 0.25
        self.quiet = quiet
        self._last_print = 0.0
        self._printed_len = 0
        if not quiet:
            self._print()

    def update(self, amount: int = 1):
        self.done_work += amount
        now = time.time()
        if not self.quiet and now - self._last_print >= self.min_interval:
            self._print()

    def _print(self):
        self._last_print = time.time()
        frac = min(1.0, self.done_work / self.total)
        elapsed = time.time() - self.start
        eta = elapsed / max(frac, 1e-9) * (1.0 - frac)
        bar_w = 40
        filled = int(bar_w * frac)
        bar = "+" * filled + " " * (bar_w - filled)
        msg = f"\r{self.title}: [{bar}] ({elapsed:.1f}s|{eta:.1f}s)  "
        sys.stderr.write(msg)
        sys.stderr.flush()
        self._printed_len = len(msg)

    def done(self):
        if not self.quiet:
            self.done_work = self.total
            self._print()
            sys.stderr.write("\n")
            sys.stderr.flush()
