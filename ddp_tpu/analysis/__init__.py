"""ddp_tpu.analysis — distributed-JAX hazard linter.

Static analysis over the package's own source for the hazard classes
every hard bug in the PR-1→5 arc belonged to (see docs/ANALYSIS.md
for the rule catalog and the war story motivating each):

  DDP001  collective under rank-divergent control flow (deadlocks)
  DDP002  host sync inside jit-reachable code (stalls / trace errors)
  DDP003  donated buffer read after donation (use-after-free)
  DDP004  recompile hazards (jit-in-loop, unhashable statics, shapes)
  DDP005  PRNG key reuse without split/fold_in (correlated sampling)

CLI: ``python scripts/lint.py [--self] [paths…]``. The runtime half —
``--sanitize`` (transfer guard + desync watchdog) — lives in
``ddp_tpu.runtime.sanitize``: static analysis finds the pattern, the
sanitizer proves the dynamic instance.
"""

from __future__ import annotations

import os

from ddp_tpu.analysis import (
    collective,
    donation,
    hostsync,
    prng,
    recompile,
)
from ddp_tpu.analysis.callgraph import build_project
from ddp_tpu.analysis.core import (  # noqa: F401 (public API)
    Finding,
    LintResult,
    RULE_TITLES,
    iter_py_files,
    load_module,
    run_checks,
)

CHECKS = {
    "DDP001": collective.check,
    "DDP002": hostsync.check,
    "DDP003": donation.check,
    "DDP004": recompile.check,
    "DDP005": prng.check,
}

# What `--self` lints: the package plus every entry point. One list,
# shared by the CLI and the smoke-tier CI gate — they must not drift.
SELF_LINT_TARGETS = ("ddp_tpu", "scripts", "train.py")


def lint_paths(
    paths, *, select: set[str] | None = None
) -> LintResult:
    """Lint files/dirs → LintResult (findings sorted, suppressions
    applied). ``select`` restricts to a subset of rule ids (DDP000
    suppression hygiene always runs)."""
    triples = iter_py_files(paths)
    modules = []
    pre_findings = []
    for path, modname, rel in triples:
        loaded = load_module(path, modname, rel)
        if isinstance(loaded, Finding):
            pre_findings.append(loaded)
        else:
            modules.append(loaded)
    project = build_project(modules)
    checks = [
        fn
        for rule, fn in CHECKS.items()
        if select is None or rule in select
    ]
    findings = run_checks(modules, checks, project, pre_findings)
    return LintResult(findings=findings, files=len(triples))


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))


def self_lint(*, select: set[str] | None = None) -> LintResult:
    root = repo_root()
    targets = [
        os.path.join(root, t)
        for t in SELF_LINT_TARGETS
        if os.path.exists(os.path.join(root, t))
    ]
    return lint_paths(targets, select=select)
