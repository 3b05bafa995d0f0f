"""ddp_tpu.analysis — the distributed-JAX hazard linter.

The fixture corpus under ``tests/lint_fixtures/`` pins every rule:
``*_tp.py`` files carry ``# ddp-expect: RULE`` markers on each line
the linter MUST flag (and nothing else may be flagged — a stray
finding in a TP file is a false positive too); ``*_tn.py`` files are
hazard-adjacent clean code that must produce ZERO findings. The
corpus is the rule contract: tightening a checker means updating the
fixtures, visibly.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

from ddp_tpu.analysis import lint_paths, self_lint  # noqa: E402

_EXPECT_RE = re.compile(r"#\s*ddp-expect:\s*(DDP\d{3})")


def _expected(path: str) -> set[tuple[str, int]]:
    out = set()
    with open(path) as f:
        for lineno, line in enumerate(f, start=1):
            m = _EXPECT_RE.search(line)
            if m:
                out.add((m.group(1), lineno))
    return out


def _found(path: str) -> set[tuple[str, int]]:
    result = lint_paths([path])
    return {(f.rule, f.line) for f in result.unsuppressed}


# ---- fixture corpus: every rule, TP + TN, zero false positives ------


@pytest.mark.parametrize(
    "rule", ["ddp001", "ddp002", "ddp003", "ddp004", "ddp005"]
)
def test_rule_true_positives_pinned(rule):
    path = os.path.join(FIXTURES, f"{rule}_tp.py")
    expected = _expected(path)
    assert expected, f"{path} has no ddp-expect markers"
    assert _found(path) == expected


@pytest.mark.parametrize(
    "rule", ["ddp001", "ddp002", "ddp003", "ddp004", "ddp005"]
)
def test_rule_true_negatives_clean(rule):
    path = os.path.join(FIXTURES, f"{rule}_tn.py")
    result = lint_paths([path])
    assert result.unsuppressed == [], [
        f.render() for f in result.unsuppressed
    ]


# ---- suppressions ---------------------------------------------------


def test_suppression_requires_justification():
    path = os.path.join(FIXTURES, "suppress.py")
    result = lint_paths([path])
    # the two justified disables silence their findings…
    suppressed = {(f.rule, f.justification) for f in result.suppressed}
    assert (
        "DDP001",
        "single-process tool path, guarded by caller",
    ) in suppressed
    assert (
        "DDP005",
        "deliberate twin draw: testing correlation itself",
    ) in suppressed
    # …the bare disable still suppresses BUT surfaces as DDP000
    # (unsuppressable), so the run fails until the why is written
    rules = {f.rule for f in result.unsuppressed}
    assert rules == {"DDP000"}


def test_suppression_of_ddp000_is_impossible(tmp_path):
    src = (
        "from jax import lax\n"
        "def f(x, rank):\n"
        "    if rank == 0:\n"
        "        # ddp-lint: disable=DDP000,DDP001\n"
        "        return lax.psum(x, 'data')\n"
        "    return x\n"
    )
    p = tmp_path / "meta.py"
    p.write_text(src)
    result = lint_paths([str(p)])
    assert {f.rule for f in result.unsuppressed} == {"DDP000"}


# ---- report formats (golden-pinned) ---------------------------------


def _run_cli(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"), *args],
        capture_output=True,
        text=True,
        cwd=REPO,
    )


def test_text_report_golden():
    proc = _run_cli("tests/lint_fixtures/ddp001_tp.py")
    assert proc.returncode == 1
    lines = proc.stdout.strip().splitlines()
    # golden first line: the format CI greps and humans click
    assert lines[0] == (
        "tests/lint_fixtures/ddp001_tp.py:14:8: DDP001 collective "
        "`ckpt.save` under rank-dependent branch — ranks that skip "
        "this branch desync and deadlock the world [hint: hoist the "
        "collective out of the divergent branch, or agree first "
        "(runtime/consensus.agree_any)]"
    )
    assert lines[-1] == (
        "ddp-lint: 8 finding(s) (0 suppressed) in 1 file(s)"
    )


def test_json_report_schema():
    proc = _run_cli("tests/lint_fixtures/ddp005_tp.py", "--json", "-")
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1
    assert doc["files"] == 1
    assert doc["counts"] == {"DDP005": 4}
    for f in doc["findings"]:
        assert set(f) >= {"rule", "path", "line", "col", "message"}


def test_self_json_relative_path_is_callers(tmp_path):
    """--self chdirs to the repo root for stable finding paths; a
    relative --json must still land in the CALLER's directory."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--self", "--json", "report.json"],
        capture_output=True, text=True, cwd=str(tmp_path),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads((tmp_path / "report.json").read_text())
    assert doc["version"] == 1


def test_clean_file_exits_zero():
    proc = _run_cli("tests/lint_fixtures/ddp001_tn.py")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 finding(s)" in proc.stdout


def test_select_filters_rules():
    proc = _run_cli(
        "tests/lint_fixtures/ddp002_tp.py", "--select", "DDP001"
    )
    assert proc.returncode == 0  # DDP002 findings not selected
    proc = _run_cli("nowhere", "--select", "DDP999")
    assert proc.returncode == 2


def test_syntax_error_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    result = lint_paths([str(p)])
    assert len(result.unsuppressed) == 1
    assert result.unsuppressed[0].rule == "DDP000"
    assert "syntax error" in result.unsuppressed[0].message


# ---- callgraph reachability -----------------------------------------


def test_callgraph_reaches_through_helpers():
    from ddp_tpu.analysis import iter_py_files, load_module
    from ddp_tpu.analysis.callgraph import build_project

    triples = iter_py_files(
        [os.path.join(FIXTURES, "ddp002_tp.py"),
         os.path.join(FIXTURES, "ddp002_tn.py")]
    )
    mods = [load_module(p, m, r) for p, m, r in triples]
    project = build_project(mods)
    assert project.is_ingraph("ddp002_tp", "traced_step")
    # reached THROUGH the jit root, not decorated itself
    assert project.is_ingraph("ddp002_tp", "log_softmax_stats")
    # lax.scan body counts as a root
    assert project.is_ingraph("ddp002_tp", "scan_body")
    # a body containing a device collective roots itself (the zero
    # strategy's scatter/gather helpers)
    assert project.is_ingraph("ddp002_tp", "bucket_scatter_update")
    assert project.is_ingraph("ddp002_tn", "zero_update_shard")
    # host code stays out
    assert not project.is_ingraph("ddp002_tn", "host_loop")
    assert not project.is_ingraph("ddp002_tn", "untraced_helper")


# ---- the CI gate + regression pins for the fixed real findings ------


def test_self_lint_clean():
    """Smoke-tier gate, the compileall gate's sibling: the repo's own
    tree has zero unsuppressed hazard findings. Runs the literal CI
    spelling — ``scripts/lint.py --self`` exits nonzero on any new
    unsuppressed finding."""
    proc = _run_cli("--self")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip().endswith("file(s)")
    # and the in-process API agrees
    assert self_lint().unsuppressed == []


def test_health_seg_constant_fixed():
    """Regression pin: obs/health.py materialized its segment ids
    through host numpy inside the traced stats pass (DDP002); now a
    device-resident jnp constant."""
    result = lint_paths(
        [os.path.join(REPO, "ddp_tpu", "obs", "health.py")],
        select={"DDP002"},
    )
    assert result.unsuppressed == []
