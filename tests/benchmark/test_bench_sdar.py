"""The block-diffusion cell's benchmark files, rehearsed on the CPU at a
tiny size.

The cell's entries are in ``BENCHMARK.json``: one configuration, one
one-chip cell listed under ``serve_tokens_per_s`` (the accepted
whole-window quotient, under the bound it has) and nine per-layer
metrics. Here a copy of the benchmark gains a tiny configuration of the
``sdar_serve`` kind and a cell beside the real one, and runs through
``benchmarks/run.py``'s own ``main``.

One accepted case, ``test_bench_extend.py::test_what_the_contract_
refuses[second_four_chip_cell_among_four]``, opens with ``assert
len(m["workloads"]) == 4`` (the three cells PR 27 knew and its toy) and
so fails on ANY checkout whose manifest holds a fourth cell, this one
included; it is a file the benchmark has and only a ``benchmark`` PR
may edit it (PERF.md section 7)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import bench_contract as bc
from bench_helpers import ROOT, _load, _write, add_entries, run_cell

from benchmarks.harness import manifest as mf
from benchmarks.harness import sdar_flops, sdar_weights

CELL = "sdar30b-serve-fixedgen-sat"
RATE = "serve_tokens_per_s"
TINY = "tiny-sdar-1"


def test_the_cells_entries_are_additions_under_the_accepted_rate():
    m = bc.manifest_of(ROOT)
    assert [c["name"] for c in m["configs"]][-1] == "sdar-30b-a3b-serve"
    assert m["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert [(w["name"], w["config"], w["traffic"], w["chips"])
            for w in m["workloads"]][-1] == (
        CELL, "sdar-30b-a3b-serve", "fixedgen-saturated", 1)
    # no end-to-end entry of the cell's own: it reports the accepted
    # quotient under the bound that is there
    assert [e["name"] for e in m["end_to_end"]] == [
        "train_tokens_per_s_per_chip", RATE, "setup_s"]
    rate = m["end_to_end"][1]
    assert rate["workloads"] == ["cgpt1.3b-serve-chat-sat", CELL]
    assert rate["bound"] == 0.01
    new = [e for e in m["per_layer"] if CELL in e.get("workloads", ())]
    assert new == m["per_layer"][-9:]
    for e in new:
        assert e["workloads"] == [CELL] and e["moves"] == RATE
    # the accepted metrics list their own cells and not this one
    assert all(CELL not in e["workloads"] for e in m["per_layer"][:-9])


@pytest.fixture(scope="module")
def sdar_copy(tmp_path_factory):
    root = bc.copy_benchmark(str(tmp_path_factory.mktemp("sdar_copy")))
    b = os.path.join(root, "benchmarks")
    cfg = _load(os.path.join(b, "configs", "sdar-30b-a3b-serve.json"))
    cfg.update(vocab_size=1009, hidden_size=64, num_hidden_layers=3,
               num_attention_heads=8, num_key_value_heads=2, head_dim=16,
               num_experts=16, num_experts_per_tok=4,
               moe_intermediate_size=32)
    cfg["generation"]["mask_token_id"] = 1000
    # 2 chunk steps and 18 forwards a request on 4 lanes
    cfg["engine"].update(slots=4, cache_length=64, prefill_chunk=8,
                         min_bucket=8, max_queue=64, admit_every=4)
    # Sound runs read 0 here; the float8 control reads ~0.1.
    cfg["correct"].update(pad_rows=0, pad_len=0, blocks_per_request=3,
                          limits={"served_logit_gap": 1e-3})
    _write(os.path.join(b, "configs", "tiny-sdar.json"), cfg)
    tr = _load(os.path.join(b, "traffic", "fixedgen-saturated.json"))
    tr.update(rate_rps=30.0, prompt_median=14, prompt_min=14,
              prompt_max=14, new_median=12, new_min=12, new_max=12,
              burst=8, lead_s=1.0, tail_s=10.0, block_s=0.2, trace_s=0.4,
              checked_requests=3)
    _write(os.path.join(b, "traffic", "tiny-fixedgen.json"), tr)
    m = _load(os.path.join(root, "BENCHMARK.json"))
    add_entries(
        m,
        config={"name": "tiny-sdar", "source": "tests",
                "file": "benchmarks/configs/tiny-sdar.json",
                "reduced": [], "why": "CPU rehearsal"},
        cells=[{"name": TINY, "config": "tiny-sdar",
                "traffic": "tiny-fixedgen", "chips": 1, "why": "rehearsal"}],
        like={TINY: CELL},
    )
    _write(os.path.join(root, "BENCHMARK.json"), m)
    return root


@pytest.fixture(scope="module")
def rehearsal(sdar_copy):
    # long enough that requests finish inside the window on a loaded box
    return run_cell(sdar_copy, TINY, seed=2**31 + 5, seconds=3.0, trace=1)


def test_rehearsal_runs_and_is_correct(rehearsal):
    rc, run, lines = rehearsal
    assert rc == 0 and run.correct
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"] == {}  # a CPU's numbers get no device name
    names = [c.name for c in run.checks]
    assert names == ["served_logit_gap", "compiles_in_window",
                     "failed_requests"]
    assert run.notes["gaps"]["decisions"] >= 3 * 3  # 3 requests, 3 blocks
    assert run.notes["gaps"]["unmask_conf_gap"] <= 1e-3  # printed, no limit
    assert set(run.end_to_end) == {RATE, "setup_s"}
    w = run.window  # the accepted quotient: all tokens over all time
    assert run.end_to_end[RATE] == pytest.approx(w["window_quotient"])


def test_rehearsal_counts_committed_tokens_not_forwards(rehearsal):
    _, run, _ = rehearsal
    before, after = run.counters["block_counts_timed"]
    tokens = after["tokens_committed_total"] - before["tokens_committed_total"]
    forwards = after["block_forwards_total"] - before["block_forwards_total"]
    # the counters are read a poll before and after the blocks
    assert abs(tokens - sum(b.work for b in run.blocks
                            if not b.traced)) <= 32
    # prompts of 14, answers of 12: 18 forwards a request of 12 tokens
    assert forwards / tokens == pytest.approx(1.5, rel=0.15)
    assert run.counters["block_counts_traced"] is not None
    assert run.counters["compile_counts"]["block_step"] == 1


def test_readers_read_the_rehearsal_and_nothing_of_an_older_program(
        sdar_copy, rehearsal):
    """Every new per-layer metric has a reader that finds its counter in
    this run, and returns None (it does not raise) on a run of a
    program that has no such counter: what the parent commit gives."""
    from benchmarks.harness.result import Run

    _, run, _ = rehearsal
    cell = mf.load_cell(TINY, sdar_copy)
    readers = cell.layer_readers()
    new = {e["name"] for e in cell.manifest["per_layer"]
           if CELL in e.get("workloads", ())
           and "cgpt1.3b-serve-chat-sat" not in e["workloads"]}
    assert new == set(readers) and len(new) == 9
    run.device.setdefault("kind", "TPU v5 lite")
    got = {n: r.read(run) for n, r in readers.items()}
    assert got["serve_bd_forwards_per_token"] == pytest.approx(1.5, rel=0.15)
    assert 0 < got["serve_bd_occupancy_pct"] <= 100
    assert 1.0 <= got["serve_moe_expert_load_max_over_mean"] <= 16
    # no kernel ran on this CPU: the trace's readers find nothing
    for n in ("serve_bd_step_dev_ms", "serve_moe_dev_pct",
              "serve_moe_roofline_pct", "serve_bd_attn_pct",
              "serve_bd_prefill_dev_ms_per_chunk"):
        assert got[n] is None, n
    older = Run(cell=cell)
    older.blocks, older.trace = run.blocks, run.trace
    older.counters = {"slots": 4, "sizes": {}}
    older.device = dict(run.device)
    assert all(r.read(older) is None for r in readers.values())


def test_control_comes_out_not_correct(sdar_copy, rehearsal):
    _, run, _ = rehearsal
    cell = mf.load_cell(TINY, sdar_copy)
    out = os.path.join(sdar_copy, "out", TINY)
    res = cell.driver().control(cell, 2**31 + 5, out)
    assert res["precision"] == "float8" and res["correct"] is False
    assert res["sound_served_logit_gap"] <= 1e-3
    assert res["control_served_logit_gap"] > 2e-3


def test_reference_in_chunks_of_rows_reads_the_same(sdar_copy, rehearsal):
    """At the real size the reference takes ``pad_rows`` rows at a time
    (216 rows of attention scores do not fit beside a layer)."""
    cell = mf.load_cell(TINY, sdar_copy)
    driver, seed = cell.driver(), 2**31 + 5
    with open(os.path.join(sdar_copy, "out", TINY,
                           f"checked_seed{seed}.json")) as f:
        samples = json.load(f)
    rows = driver.decisions(samples, 4)
    n = len(rows["tokens"])
    whole = driver.reference_logits(seed, cell.config, rows)
    chunks = driver.reference_logits(seed, cell.config, rows, pad_rows=4,
                                     pad_len=32)
    assert n > 8 and n % 4  # several chunks, the last one padded
    assert whole.shape == chunks.shape == (n, 4, 1009)
    assert float(abs(np.asarray(whole) - np.asarray(chunks)).max()) < 1e-5


def test_a_broken_timed_path_is_not_correct(sdar_copy):
    """What ``correct`` reads is what the timed path produced: a block
    step that hands on other tokens than the model chose is caught."""

    def break_path(served):
        real = served.engine._decode

        def wrong(params, cache, lanes):
            cache, lanes, report, moe = real(params, cache, lanes)
            return (cache, lanes._replace(toks=(lanes.toks + 1) % 1000),
                    report, moe)

        wrong._cache_size = real._cache_size
        served.engine._decode = wrong

    rc, run, _ = run_cell(sdar_copy, TINY, seconds=1.0,
                          break_path=break_path)
    assert rc == 0 and not run.correct
    assert "served_logit_gap" in {c.name for c in run.checks if not c.ok}


def test_checked_blocks_are_spread_from_the_first_to_the_last(sdar_copy):
    driver = mf.load_cell(TINY, sdar_copy).driver()
    fw = [(pos, [1, 2, 3, 4], [m < k for m in range(4)])
          for pos in range(124, 124 + 64 * 4, 4) for k in (2, 1, 0)]
    got = driver.pick_blocks(fw, 9)
    at = [b["pos"] for b in got]
    assert len(at) == 9 and at[0] == 124 and at[-1] == 124 + 63 * 4
    gaps = np.diff(at)
    assert gaps.min() >= 7 * 4 and gaps.max() <= 9 * 4
    assert all(len(b["forwards"]) == 3 for b in got)
    # a request of fewer blocks than asked gives each of them once
    assert [b["pos"] for b in driver.pick_blocks(fw[:6], 9)] == [124, 128]


# ---- the weights and the counts -------------------------------------------


SIZES = dict(vocab_size=151936, d_model=2048, depth=7, num_heads=32,
             num_kv_heads=4, head_dim=128, num_experts=128,
             moe_intermediate=768)


def test_parameter_arithmetic_is_the_issues():
    assert sdar_flops.expert_params(2048, 768) == 4_718_592
    assert sdar_flops.layer_params(**SIZES) == 623_120_640
    assert sdar_flops.param_count(SIZES) == (
        7 * 623_120_640 + 622_329_856 + 2048)
    # 7 layers + embedding + head in bfloat16: the 9.97 GB of the cut
    assert 9.96e9 < sdar_flops.param_count(SIZES) * 2 < 9.98e9
    # a block step streams 9.3 GB: every expert of 7 layers and the head
    step = sdar_flops.forward_bytes(SIZES, experts_hit_per_layer=128)
    assert 9.3e9 < step < 9.4e9
    rows = 128 * 8
    assert sdar_flops.moe_kernels_flops(rows, 2048, 768) == (
        2.0 * rows * 4_718_592)
    # bandwidth-bound by far at 8 rows an expert
    b = sdar_flops.moe_kernels_bytes(rows, 128, 2048, 768)
    assert b / 819e9 > 10 * sdar_flops.moe_kernels_flops(
        rows, 2048, 768) / 197e12


def test_expected_forwards_per_token_is_the_schedules():
    tr = _load(os.path.join(ROOT, "benchmarks", "traffic",
                            "fixedgen-saturated.json"))
    assert tr["prompt_min"] == tr["prompt_max"] == 126
    assert tr["new_min"] == tr["new_max"] == 256
    assert tr["burst"] == 64 and tr["order_seed"] == 0
    assert tr["expected_forwards_per_token"] == (64 * 5 + 3) / 256


def test_weights_are_a_function_of_seed_and_layer_and_fit_the_program():
    import jax
    import jax.numpy as jnp

    from ddp_tpu.models import sdar
    from ddp_tpu.models.lm import LMSpec

    sizes = dict(vocab_size=97, d_model=32, depth=2, num_heads=4,
                 num_kv_heads=2, head_dim=16, num_experts=8,
                 moe_intermediate=16)
    seed = 2**31 + 77  # the driver's seeds pass 32 signed bits
    tree = sdar_weights.make_params(seed, sizes)
    spec = LMSpec(vocab_size=97, total_len=32, d_model=32, depth=2,
                  num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
                  moe_top_k=2, moe_intermediate=16, block="qwen3_moe",
                  block_length=4, denoise_steps=4, mask_token_id=96)
    from benchmarks.harness.weights import flatten

    assert {p: tuple(a.shape) for p, a in flatten(tree).items()} == (
        sdar.leaf_shapes(spec))
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(tree))
    again = sdar_weights.make_layer(seed, sizes, 1)
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        again, tree["layers"]["1"])
    assert all(jax.tree.leaves(same))
    other = sdar_weights.make_layer(seed, sizes, 0)
    assert not bool((other["mlp"]["gate"] == again["mlp"]["gate"]).all())
    assert not bool((sdar_weights.make_layer(seed + 1, sizes, 1)["mlp"][
        "gate"] == again["mlp"]["gate"]).all())
    f32 = sdar_weights.as_float32(again)
    assert f32["mlp"]["gate"].dtype == jnp.float32
    assert bool((f32["self_attn"]["q_norm"] == 1).all())
    assert 0.015 < float(np.std(np.asarray(f32["mlp"]["gate"]))) < 0.025


def test_the_cells_configuration_states_the_cut():
    cfg = _load(os.path.join(ROOT, "benchmarks", "configs",
                             "sdar-30b-a3b-serve.json"))
    assert cfg["num_hidden_layers"] == 7
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "vocab_size",
                "num_attention_heads", "num_key_value_heads",
                "max_position_embeddings", "intermediate_size"):
        assert cfg[key] == cfg["published"][key], key
    assert cfg["precision"]["control"] == "float8"
    # as ISSUE 28 gives the engine; admissions spaced under 325 / 32
    assert cfg["engine"] == {
        "slots": 32, "cache_length": 512, "prefill_chunk": 64,
        "min_bucket": 64, "admit_every": 9, "max_queue": 4096,
        "decode_attn": "auto"}
    assert 32 * cfg["engine"]["admit_every"] < 2 + 64 * 5 + 3
    assert cfg["generation"] == {
        "block_length": 4, "denoise_steps": 4, "mask_token_id": 151669,
        "unmask": "low_confidence_static", "unmask_threshold": 0.9}
    assert set(cfg["correct"]["limits"]) == {"served_logit_gap"}
    # some hundreds of served positions a run: 6 requests, 9 blocks of
    # 4 unmasking forwards (the first block of a request has 2)
    assert cfg["correct"]["blocks_per_request"] == 9
    assert 6 * (8 * 4 + 2) <= 3 * cfg["correct"]["pad_rows"]


def test_the_cell_resolves_on_the_checkout():
    cell = mf.load_cell(CELL)
    assert cell.chips == 1 and cell.config["kind"] == "sdar_serve"
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s", RATE}
    assert len(cell.per_layer()) == 9
    assert cell.traffic["rate_rps"] == 10.0
