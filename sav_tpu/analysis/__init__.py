"""Static analysis + runtime sanitizers for TPU/JAX discipline (ISSUE 3).

Two complementary layers:

- **savlint** (:mod:`sav_tpu.analysis.lint`, :mod:`sav_tpu.analysis.rules`)
  — an AST pass over the repo with TPU-specific rules: host syncs in the
  hot loop, un-donated state-carrying jits, PRNG key reuse, retrace
  triggers, inline ``device_put`` in ``fit()``/``evaluate()``, unlocked
  cross-thread state, f32 literal promotion in bf16 paths. Run it via
  ``python tools/savlint.py`` or :func:`lint_paths`; tier-1
  (tests/test_savlint_self.py) runs it over the whole repo so new
  violations fail CI. Stdlib-only — importing this layer never imports
  jax, so the linter works in device-free contexts (pre-commit, CI
  frontends).
- **Runtime sanitizer** (:mod:`sav_tpu.analysis.sanitize`) — an opt-in
  hard-fail guard for the invariant statics cannot see:
  ``jax.transfer_guard("disallow")`` armed around the steady-state hot
  loop. Wired through ``TrainConfig.sanitize`` / ``train.py --sanitize``.

See docs/static_analysis.md for the rule catalogue, pragma/baseline
workflow, and how to add a rule.
"""

from sav_tpu.analysis.lint import (  # noqa: F401
    Finding,
    LintResult,
    lint_paths,
    load_baseline,
    write_baseline,
)
from sav_tpu.analysis.rules import ALL_RULES, rule_catalog  # noqa: F401
