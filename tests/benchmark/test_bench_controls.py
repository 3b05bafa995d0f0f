"""``correct`` has to be able to fail: the lower-precision control comes
out not correct, and a run whose timed path is broken underneath sees
``correct`` come out false. At a size a test run can hold; the same
controls were run on the chip at the cells' own sizes (PERF.md)."""

import json

import numpy as np
import pytest

from benchmarks.harness import manifest
from bench_helpers import run_cell


def _result(lines):
    return json.loads(lines[-1])


# ---- train ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_train_control_is_not_correct(bench_copy, seed, tmp_path):
    cell = manifest.load_cell("tiny-train-4", bench_copy)
    res = cell.driver().control(cell, seed, str(tmp_path))
    assert res["precision"] == "bfloat16"
    assert res["correct"] is False
    failing = [c["name"] for c in res["checks"] if not c["ok"]]
    assert "grad_leaf_rel" in failing, res["checks"]


def test_train_control_float8_is_worse_than_bfloat16(bench_copy):
    cell = manifest.load_cell("tiny-train-1", bench_copy)
    drv = cell.driver()
    rows = cell.generator().generate(
        cell.traffic, seed=4, vocab_size=97, seq_len=32, global_batch=4)
    batches = [rows[i * 4:(i + 1) * 4] for i in range(3)]
    sound = drv.reference_readings(4, cell.config, batches)
    gaps = {}
    for prec in ("bfloat16", "float8"):
        low = drv.reference_readings(4, cell.config, batches,
                                     precision=prec)
        gaps[prec] = drv.worst_leaf_gap(low["grad_norms"],
                                        sound["grad_norms"])[0]
    assert gaps["float8"] > gaps["bfloat16"] > 1e-4


def test_train_step_that_returns_its_state_unchanged(bench_copy):
    import jax
    import jax.numpy as jnp

    def break_path(h):
        real = h.step_fn

        def stuck(state, toks, lbls):
            kept = jax.tree.map(jnp.copy, state)
            _, metrics = real(state, toks, lbls)
            return kept, metrics

        h.step_fn = stuck

    rc, run, lines = run_cell(bench_copy, "tiny-train-1", seed=21,
                              break_path=break_path)
    assert rc == 0 and _result(lines)["correct"] is False
    bad = {c.name for c in run.checks if not c.ok}
    assert "delta_leaf_rel" in bad


def test_train_step_that_leaves_out_part_of_the_batch(bench_copy):
    def break_path(h):
        real = h.step_fn

        def half(state, toks, lbls):
            # the second half of the rows repeats the first
            n = toks.shape[0] // 2
            toks = toks.at[n:].set(toks[:n])
            return real(state, toks, lbls)

        h.step_fn = half

    rc, run, lines = run_cell(bench_copy, "tiny-train-1", seed=22,
                              break_path=break_path)
    assert rc == 0 and _result(lines)["correct"] is False
    assert not {c.name: c.ok for c in run.checks}["loss_rel"]


def test_worst_leaf_gap_uses_the_median_leaf_as_floor(bench_copy):
    drv = manifest.load_cell("tiny-train-1", bench_copy).driver()
    ref = {"a": 1.0, "b": 2.0, "c": 1e-12}
    prog = {"a": 1.01, "b": 2.0, "c": 3e-12}  # c: all but zero
    gap, where = drv.worst_leaf_gap(prog, ref)
    assert where == "a" and gap == pytest.approx(0.01)
    assert np.isnan(drv.worst_leaf_gap({**prog, "b": float("nan")}, ref)[0])


# ---- serve ---------------------------------------------------------------


def test_serve_token_altered_where_it_is_produced(bench_copy):
    def break_path(served):
        real = served.submit

        def altered(body, **kw):
            http, payload = real(body, **kw)
            if http == 200 and payload.get("tokens"):
                payload["tokens"] = [
                    (t + 1) % 1009 for t in payload["tokens"]]
            return http, payload

        served.submit = altered

    rc, run, lines = run_cell(bench_copy, "tiny-serve-1", seed=31,
                              seconds=1, break_path=break_path)
    assert rc == 0 and _result(lines)["correct"] is False
    assert not {c.name: c.ok for c in run.checks}["served_logit_gap"]


def test_serve_control_is_not_correct(bench_copy, tmp_path):
    """The bfloat16 reference over prompts and tokens a float32 run
    served: some token it puts first lies below the reference's best by
    more than the limit."""
    rc, run, lines = run_cell(bench_copy, "tiny-serve-1", seed=32,
                              seconds=2, out=str(tmp_path))
    assert rc == 0 and _result(lines)["correct"] is True
    cell = manifest.load_cell("tiny-serve-1", bench_copy)
    drv = cell.driver()
    # more positions than one run checks, so a flip is certain
    rng = np.random.default_rng(7)
    samples = [(rng.integers(0, 1009, 8).tolist(),
                rng.integers(0, 1009, 40).tolist()) for _ in range(8)]
    gaps = drv.reference_gaps(32, cell.config, samples, control="bfloat16")
    limit = cell.config["correct"]["limits"]["served_logit_gap"]
    assert gaps["control_flips"] > 0 and gaps["control_gap"] > limit
    res = drv.control(cell, 32, str(tmp_path))
    assert res["precision"] == "bfloat16" and res["tokens"] > 0
