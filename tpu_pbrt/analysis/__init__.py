"""jaxlint — repo-specific static analysis + jaxpr audit for TPU hot paths.

Six layers (ISSUE 2 + ISSUE 3 + ISSUE 17 + ISSUE 18):

- **Layer 1 (AST lint, `lint.py`)**: syntactic rules over the source tree.
  A per-module call graph seeded at `jax.jit` / `lax.while_loop` /
  `shard_map` boundaries marks *traced* functions, and the hot-path rules
  (host syncs, f64 leaks, dtype-less constructors, captured-array
  mutation) fire only inside them, so host-side driver/build code stays
  lintable Python. `# jaxlint: disable=RULE` pragmas suppress per line.

- **Layer 2 (jaxpr/compile audit, `audit.py`)**: traces the real render
  entry points (path bounce wave, persistent pool drain, stream
  traversal, film deposit, sharded mesh step) and asserts over the jaxpr
  and the compiled executable: no f64 anywhere, no callback primitives,
  donation materialized as input->output aliasing for the film/pool
  buffers, zero retraces across same-shape waves, and a clean smoke
  render under jax.transfer_guard("disallow").

- **Layer 3 (static roofline budgets, `cost.py`)**: an abstract
  interpreter charges every entry-point equation FLOPs and HBM bytes,
  rolls them up per wave, gates against the committed `budgets.json`
  (refresh: `--update-budgets`), and reports anti-pattern findings
  (dtype churn, hot-buffer relayouts, narrow unsorted gathers,
  broadcast blowups, tile-padding waste) — a perf regression signal
  that needs no accelerator.

- **Layer 4 (shard_map replication analysis, `shardcheck.py`)**: tracks
  replicated-vs-varying values through every shard_map body and errors
  when an output claimed replicated (out_spec P()) was never reduced
  over the mesh axis, or a collective sits inside a varying-trip-count
  loop — a second, independent checker beside jax's own check_vma
  (which the mesh renderers keep on), and the only one of the loop
  rule.

- **Layer 5 (serve/dispatch protocol verification, `protocheck.py`)**:
  the HOST-side state machine. Static SV-* rules (SV-CLOCK: wall clock
  sampled outside the injected `utils/clock.py` seam or twice in a
  deadline-scoped function; SV-DEFER: deferred checkpoint writes
  without retirement binding; SV-VTIME: fair-share vtime written
  outside the policy API), a seeded mutation-regression corpus of
  three historical bugs, and a bounded exhaustive exploration
  (`tools/explore.py`) of decision sequences — arrival orders x
  pipeline depths x CHAOS fault placements x preempt/resume timings —
  running the REAL RenderService under a VirtualClock and checking the
  PROTO-* invariants (counter reconciliation, deferred-write
  linearity, pin balance, backoff monotonicity, no wedge, schedule
  determinism, film bit-identity) after every decision.

- **Layer 6 (static HBM residency/liveness/capacity, `hbmcheck.py`)**:
  an aval-level model of device memory across the serve lifecycle,
  gated against a per-platform capacity table and the committed
  `hbm_budgets.json` (the HC-* rules; see the module's docstring).

Run `python -m tpu_pbrt.analysis` (see `__main__.py`), or the pytest
mirrors in tests/test_jaxlint.py, test_jaxpr_audit.py, test_cost.py,
test_shardcheck.py, test_protocheck.py and test_hbmcheck.py.
"""

from tpu_pbrt.analysis.lint import (  # noqa: F401
    RULES,
    Violation,
    lint_file,
    lint_tree,
)
