"""Fixtures of the benchmark's own tests: a copy of the benchmark in a
temporary directory to which tiny cells are ADDED the way a later PR
adds a cell — new files and new entries, no edit to a file that is
there."""

from __future__ import annotations

import os
import shutil

import pytest

from bench_helpers import ROOT, _load, _write, add_entries


@pytest.fixture(scope="session")
def bench_copy(tmp_path_factory):
    """A checkout-like directory: BENCHMARK.json + benchmarks/, plus
    tiny train and serve cells added as new files and entries."""
    root = str(tmp_path_factory.mktemp("bench_copy"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    b = os.path.join(root, "benchmarks")
    before = {
        os.path.relpath(os.path.join(d, f), root): os.path.getmtime(
            os.path.join(d, f))
        for d, _, fs in os.walk(b) for f in fs
    }
    manifest = _load(os.path.join(root, "BENCHMARK.json"))

    cfg = _load(os.path.join(b, "configs", "cerebras-gpt-1.3b-train.json"))
    cfg["train_config"].update(
        model_dim=64, model_depth=2, num_heads=4, vocab_size=97,
        seq_len=32, batch_size=4, compute_dtype="float32",
    )
    # The tiny program computes in float32, so its control is bfloat16
    # and its limits sit between ~3e-7 (sound) and the control's ~1e-3.
    cfg["precision"] = {"compute": "float32", "control": "bfloat16"}
    cfg["correct"]["limits"] = {
        "loss_rel": 2e-5, "grad_leaf_rel": 2e-4, "delta_leaf_rel": 0.2}
    _write(os.path.join(b, "configs", "tiny-train.json"), cfg)
    tr = _load(os.path.join(b, "traffic", "train-steady.json"))
    tr.update(steps_per_epoch=8, block_steps=4, warm_steps=2)
    _write(os.path.join(b, "traffic", "tiny-steady.json"), tr)
    add_entries(
        manifest,
        config={"name": "tiny-train", "source": "tests",
                "file": "benchmarks/configs/tiny-train.json",
                "reduced": [], "why": "CPU rehearsal"},
        cells=[
            {"name": "tiny-train-1", "config": "tiny-train",
             "traffic": "tiny-steady", "chips": 1, "why": "rehearsal"},
            {"name": "tiny-train-4", "config": "tiny-train",
             "traffic": "tiny-steady", "chips": 4, "why": "rehearsal"},
        ],
        like={"tiny-train-1": "cgpt1.3b-train-1chip",
              "tiny-train-4": "cgpt1.3b-train-ddp4"},
    )

    cfg = _load(os.path.join(b, "configs", "cerebras-gpt-1.3b-serve.json"))
    cfg.update(vocab_size=1009, n_positions=64, n_embd=64, n_layer=2,
               n_head=4, n_inner=256)
    cfg["engine"].update(slots=4, max_queue=64)
    cfg["correct"]["pad_to"] = 16
    # On the CPU the tiny program multiplies in float32, so its control
    # is bfloat16 and its limit sits just above the sound runs' zero.
    cfg["precision"] = {"weights": "float32", "control": "bfloat16"}
    cfg["correct"]["limits"] = {"served_logit_gap": 1e-5}
    _write(os.path.join(b, "configs", "tiny-serve.json"), cfg)
    tr = _load(os.path.join(b, "traffic", "chat-saturated.json"))
    tr.update(rate_rps=20.0, prompt_median=8, prompt_min=2, prompt_max=16,
              new_median=6, new_min=2, new_max=12, burst=4, lead_s=0.5,
              tail_s=10.0, block_s=0.2, trace_s=0.4, checked_requests=4)
    _write(os.path.join(b, "traffic", "tiny-chat.json"), tr)
    add_entries(
        manifest,
        config={"name": "tiny-serve", "source": "tests",
                "file": "benchmarks/configs/tiny-serve.json",
                "reduced": [], "why": "CPU rehearsal"},
        cells=[{"name": "tiny-serve-1", "config": "tiny-serve",
                "traffic": "tiny-chat", "chips": 1, "why": "rehearsal"}],
        like={"tiny-serve-1": "cgpt1.3b-serve-chat-sat"},
    )
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    after = {p: os.path.getmtime(os.path.join(root, p)) for p in before}
    assert after == before, "adding cells edited a file that was there"
    return root
