"""Bring-up contracts (ISSUE 21): where the compile cache lives, and a
chip smoke whose parent never touches JAX and never forgives a child.

The chip itself is reached only through ``chip_smoke.py`` on a TPU
machine; what is pinned here is everything about it that a CPU can
decide.
"""

import ast
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from ddp_tpu.runtime import dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- the compile cache helper ----------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_cache_env_var_set_means_code_sets_nothing(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dist.enable_compile_cache() == "/somewhere/outside"
    assert config_updates == []


def test_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, config_updates
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    first = dist.enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", first)]
    # Fixed: nothing of this process or this moment is in the path.
    assert dist.enable_compile_cache() == first
    assert str(os.getpid()) not in first and "tmp" not in first.lower()


def test_cache_stays_off_on_a_cpu_backend(monkeypatch, config_updates):
    """XLA:CPU cache-loaded executables crash resumed runs (conftest's
    round-6 finding), so the default never engages there."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert dist.enable_compile_cache() is None
    assert config_updates == []


def test_one_place_decides_the_cache_directory():
    """``grep -rn jax_compilation_cache_dir`` over the program finds the
    helper and nothing else."""
    hits = []
    roots = [os.path.join(REPO, d) for d in ("ddp_tpu", "scripts")]
    files = [
        os.path.join(REPO, f) for f in ("train.py", "chip_smoke.py")
    ]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files += [
                os.path.join(dirpath, n) for n in names if n.endswith(".py")
            ]
    for path in files:
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if "jax_compilation_cache_dir" in line:
                    hits.append((os.path.relpath(path, REPO), n))
    assert [h[0] for h in hits] == ["ddp_tpu/runtime/dist.py"], hits


# ---- chip_smoke.py's parent ------------------------------------------


def test_smoke_parent_imports_only_the_stdlib():
    """A parent that has touched JAX holds the chip and its children
    then fail or hang: it may import neither jax nor ddp_tpu (which
    imports jax) — nor anything else outside the standard library."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0
            imported.add(node.module.split(".")[0])
    assert not imported & {"jax", "jaxlib", "ddp_tpu", "numpy"}
    assert imported <= set(sys.stdlib_module_names), imported


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path))
    return chip_smoke


def test_smoke_fails_when_a_child_exits_nonzero(smoke):
    ch = smoke.Children(dict(os.environ), time.monotonic() + 60)
    log, _ = ch.run("fine", ["-c", "print('ok')"], 30.0)
    assert open(log).read().strip() == "ok"
    with pytest.raises(smoke.SmokeFailure, match="exit code 3"):
        ch.run("bad", ["-c", "import sys; print('why'); sys.exit(3)"], 30.0)


def test_smoke_kills_and_fails_a_child_that_outlasts_its_budget(smoke):
    ch = smoke.Children(dict(os.environ), time.monotonic() + 60)
    with pytest.raises(smoke.SmokeFailure, match="timed out"):
        ch.run("hang", ["-c", "import time; time.sleep(60)"], 1.0)
    assert all(p.poll() is not None for p in ch.live)


def test_smoke_alone_in_a_directory_fails_and_prints_no_result(tmp_path):
    """The contract's last clause: in a directory that holds
    chip_smoke.py and nothing else of the repo it exits non-zero and
    prints no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "chip_smoke FAILED" in proc.stderr
