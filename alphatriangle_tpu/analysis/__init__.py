"""graftlint: an AST-based JAX-hazard static analyzer (docs/ANALYSIS.md).

Makes this repo's worst silent bug classes mechanically impossible to
reintroduce: use-after-donation (PR 3), mixed-placement recompiles
(PR 5), host syncs in hot loops (PR 6), unbracketed hot dispatches
(PR 10's flight coverage), debug artifacts, and untracked RNG.

JAX-free by contract — `cli lint` runs in CI images and beside a
process that holds the chip, exactly like `cli mem` / `cli doctor` (pinned by a subprocess import-guard test).
"""

from .baseline import (
    BASELINE_SCHEMA,
    apply_baseline,
    load_baseline,
    write_baseline,
)
from .engine import LINT_SCHEMA, LintReport, run_lint
from .model import Finding, Module
from .rules import RULE_NAMES, RULES

__all__ = [
    "BASELINE_SCHEMA",
    "LINT_SCHEMA",
    "Finding",
    "LintReport",
    "Module",
    "RULES",
    "RULE_NAMES",
    "apply_baseline",
    "load_baseline",
    "run_lint",
    "write_baseline",
]
