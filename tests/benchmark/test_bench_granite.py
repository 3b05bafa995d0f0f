"""The hybrid (Mamba-2 and attention) cell's benchmark files, rehearsed
on the CPU at a tiny size.

The cell's entries are in ``BENCHMARK.json``: one configuration with
nothing reduced, one one-chip cell listed under ``serve_tokens_per_s``
(the accepted whole-window quotient, under the bound it has) and eight
per-layer metrics. Here a copy of the benchmark gains a tiny
configuration of the ``granite_serve`` kind and a cell beside the real
one, and runs through ``benchmarks/run.py``'s own ``main``: sound, the
float8 control, and three timed paths broken the ways a recurrent lane
can break (state not reset at admission, padding that moves the state,
state lost between chunks), each of which has to come out NOT correct.

Two accepted cases open with a count or an order of the manifest as
their PR left it and so fail on a checkout that holds this cell:
``test_bench_extend.py::test_what_the_contract_refuses[
second_four_chip_cell_among_four]`` (since PR 28) and
``test_bench_sdar.py::test_the_cells_entries_are_additions_under_the_
accepted_rate`` (it asserts that ITS configuration, cell and metrics
are the manifest's last). Both are files the benchmark has; only a
``benchmark`` PR may edit them (PERF.md section 7)."""

from __future__ import annotations

import json
import os
from unittest import mock

import pytest

import bench_contract as bc
from bench_helpers import ROOT, _load, _write, add_entries, run_cell

from benchmarks.harness import granite_flops, granite_weights
from benchmarks.harness import manifest as mf

CELL = "granite4hm-serve-chat-sat"
CONFIG = "granite-4.0-h-micro-serve"
RATE = "serve_tokens_per_s"
TINY = "tiny-granite-1"
SEED = 2**31 + 11  # the driver's seeds pass 32 signed bits
NEW = ["serve_hy_decode_dev_ms_per_step", "serve_hy_prefill_dev_ms_per_chunk",
       "serve_hy_host_ms_per_step", "serve_hy_occupancy_pct",
       "serve_ssm_update_dev_pct", "serve_ssm_update_roofline_pct",
       "serve_hy_attn_pct", "serve_hy_window_mfu_pct"]


def test_the_cells_entries_are_additions_under_the_accepted_rate():
    """Found by NAME, not by place or count: a later PR's entries come
    after these and must not fail this case (PERF.md section 7)."""
    m = bc.manifest_of(ROOT)
    by_name = lambda entries: {e["name"]: e for e in entries}
    assert by_name(m["configs"])[CONFIG]["reduced"] == []
    cell = by_name(m["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "chat-saturated-64", 1)
    # no end-to-end entry of the cell's own: it reports the accepted
    # quotient under the bound that is there
    assert all(CELL not in e["name"] and CONFIG not in e["name"]
               for e in m["end_to_end"])
    rate = by_name(m["end_to_end"])[RATE]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    names = [e["name"] for e in m["per_layer"]]
    assert [n for n in names if n in NEW] == NEW
    for e in m["per_layer"]:
        if e["name"] in NEW:
            assert e["workloads"] == [CELL] and e["moves"] == RATE
        elif names.index(e["name"]) < names.index(NEW[0]):
            # the metrics accepted before it list their own cells
            assert CELL not in e["workloads"]


def test_the_checkout_with_the_cell_keeps_all_sixteen_rules():
    assert len(bc.CHECKS) == 16
    assert bc.failures(ROOT) == {}


# ---- the counts, against hand counts --------------------------------------


def _sizes():
    cell = mf.load_cell(CELL)
    return cell.driver().model_sizes(cell.config)


def test_parameter_arithmetic_is_the_issues():
    s = _sizes()
    # in_proj 2048 x 8512, conv 4 x 4352 + 4352, 3 x 64 vectors, norm
    # 4096, out_proj 4096 x 2048, two RMSNorms, MLP 3 x 2048 x 8192
    assert granite_flops.mamba_layer_params(s) == 76_182_976
    assert granite_flops.attention_layer_params(s) == 60_821_504
    assert granite_flops.param_count(s) == 3_191_396_096 == (
        36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2_048 + 2_048)
    assert 6.38e9 < granite_flops.weight_bytes(s) < 6.39e9
    assert granite_flops.layer_counts(s) == (36, 4)


def test_state_and_step_bytes_are_the_issues():
    s = _sizes()
    # 64 heads x 64 channels x 128 state dimensions, float32
    assert granite_flops.state_bytes_per_layer_per_lane(s) == 2_097_152
    assert granite_flops.tail_bytes_per_layer_per_lane(s) == 3 * 4352 * 4
    assert granite_flops.kv_row_bytes(s) == 2 * 8 * 64 * 4
    # one kernel call at 64 live lanes: 128 MB in and 128 MB out of
    # state, and 52 KB of vectors a lane
    call = granite_flops.ssm_update_bytes(s, 64)
    assert call == 64 * (2 * 2_097_152 + (3 * 4096 + 2 * 128) * 4)
    # a decode step at 64 live lanes attending 400 rows each: the
    # weights once, 36 layers of state and tail in and out, the rows
    step = granite_flops.decode_step_bytes(
        s, live_lanes=64, rows_attended=64 * 400)
    assert step == (2 * 3_191_396_096
                    + 2 * 36 * 64 * (2_097_152 + 52_224)
                    + 4 * (64 * 400 + 64) * 4096)
    assert 16.6e9 < step < 16.8e9  # 20.4 ms at 819 GB/s
    # the state is 58% of it, and bytes bound the step by far
    assert 0.57 < 2 * 36 * 64 * 2_097_152 / step < 0.59
    flops = granite_flops.decode_step_flops(
        s, live_lanes=64, rows_attended=64 * 400)
    assert step / 819e9 > 8 * flops / 197e12
    # idle lanes count for nothing
    assert granite_flops.decode_step_bytes(
        s, live_lanes=1, rows_attended=400) == (
        2 * 3_191_396_096 + 2 * 36 * (2_097_152 + 52_224) + 4 * 401 * 4096)
    # a full chunk of 256 is near the ridge: operations over the peak
    # and bytes over the bandwidth within a factor of 1.3
    cb = granite_flops.prefill_chunk_bytes(s, tokens=256, start=0) / 819e9
    cf = granite_flops.prefill_chunk_flops(s, tokens=256, start=0) / 197e12
    assert 0.75 < cf / cb < 1.3


def test_the_cells_configuration_states_everything_published():
    cfg = _load(os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json"))
    row = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"granite-4.0-h-micro"' in line] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for r in row:  # every key of the catalog row, at its value
        assert cfg["source"] == r["source_url"]
        for key, value in r["config"].items():
            assert cfg[key] == value, key
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 40
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key
    assert cfg["precision"]["control"] == "float8"
    assert cfg["engine"] == {
        "slots": 64, "cache_length": 2048, "prefill_chunk": 256,
        "min_bucket": 128, "max_queue": 4096, "decode_attn": "auto"}
    assert set(cfg["correct"]["limits"]) == {"served_logit_gap"}
    for key in ("weights", "storage", "state", "state_layout", "engine"):
        assert key in cfg["assumed"], key


def test_the_cell_resolves_and_its_traffic_is_the_chat_shape():
    cell = mf.load_cell(CELL)
    assert cell.chips == 1 and cell.config["kind"] == "granite_serve"
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s", RATE}
    assert [m["name"] for m in cell.per_layer()] == NEW
    chat = _load(os.path.join(ROOT, "benchmarks", "traffic",
                              "chat-saturated.json"))
    tr = cell.traffic
    for key in ("generator", "prompt_median", "prompt_min", "prompt_max",
                "new_median", "new_min", "new_max", "lead_s", "tail_s",
                "block_s", "trace_s", "checked_requests", "order_seed"):
        assert tr[key] == chat[key], key
    # the issue's named fallback: with the chat shape's own sigmas six
    # runs spread by 0.7-1.0% (PERF.md section 6), so both are halved
    assert tr["prompt_sigma"] == chat["prompt_sigma"] / 2 == 0.35
    assert tr["new_sigma"] == chat["new_sigma"] / 2 == 0.3
    assert tr["burst"] == 2 * cell.config["engine"]["slots"] == 128


def test_weights_are_a_function_of_seed_and_layer_and_fit_the_program():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.weights import flatten
    from ddp_tpu.models import granite_hybrid as gh

    cell = mf.load_cell(CELL)
    driver = cell.driver()
    cfg = dict(cell.config, vocab_size=97, hidden_size=128,
               num_attention_heads=2, num_key_value_heads=2,
               mamba_n_heads=4, mamba_d_head=64, mamba_d_state=16,
               shared_intermediate_size=64, num_hidden_layers=4,
               layer_types=["mamba", "attention", "mamba", "mamba"],
               engine={"cache_length": 32})
    sizes, spec = driver.model_sizes(cfg), driver.lm_spec(cfg)
    tree = granite_weights.make_params(SEED, sizes)
    assert {p: tuple(a.shape) for p, a in flatten(tree).items()} == (
        gh.leaf_shapes(spec))
    again = granite_weights.make_layer(SEED, sizes, 2)
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        again, tree["layers"]["2"])
    assert all(jax.tree.leaves(same))
    other = granite_weights.make_layer(SEED, sizes, 3)["mamba"]
    assert not bool((other["in_proj"] == again["mamba"]["in_proj"]).all())
    assert not bool((other["A_log"] == again["mamba"]["A_log"]).all())
    m = again["mamba"]
    assert m["in_proj"].dtype == jnp.bfloat16
    # the recurrence's vectors stay float32, by Mamba-2's initialisation
    assert m["A_log"].dtype == m["dt_bias"].dtype == jnp.float32
    assert 0.0 <= float(m["A_log"].min()) <= float(m["A_log"].max()) <= 2.78
    dt = jax.nn.softplus(m["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) <= float(dt.max()) <= 0.1 * 1.01
    assert bool((m["D"] == 1).all()) and bool((m["norm"] == 1).all())
    assert float(jnp.abs(m["conv1d"]["weight"]).max()) <= 0.5


# ---- the rehearsal -----------------------------------------------------------


@pytest.fixture(scope="module")
def granite_copy(tmp_path_factory):
    root = bc.copy_benchmark(str(tmp_path_factory.mktemp("granite_copy")))
    b = os.path.join(root, "benchmarks")
    cfg = _load(os.path.join(b, "configs", CONFIG + ".json"))
    # head size 64 as published (two kv heads to a 128-lane group). At
    # width 256 and depth 6 the tied head would hand every token back
    # (the embedding, times 12, is most of the residual stream), so the
    # two multipliers around it are 1 here: the layers decide the token.
    cfg.update(vocab_size=1009, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2, mamba_expand=1, mamba_n_heads=4,
               mamba_d_head=64, mamba_d_state=64, mamba_chunk_size=8,
               shared_intermediate_size=128, intermediate_size=128,
               num_hidden_layers=6,
               layer_types=["mamba", "mamba", "attention"] * 2,
               embedding_multiplier=1, logits_scaling=1)
    cfg["engine"].update(slots=4, cache_length=64, prefill_chunk=8,
                         min_bucket=4, max_queue=64)
    cfg["correct"].update(pad_rows=0, pad_len=0, pad_new=0,
                          limits={"served_logit_gap": TINY_LIMIT})
    _write(os.path.join(b, "configs", "tiny-granite.json"), cfg)
    tr = _load(os.path.join(b, "traffic", "chat-saturated-64.json"))
    # prompts of several chunks of 8 with a padded last one
    tr.update(rate_rps=30.0, prompt_median=12, prompt_sigma=0.4,
              prompt_min=9, prompt_max=30, new_median=10, new_sigma=0.3,
              new_min=6, new_max=16, burst=8, lead_s=1.0, tail_s=10.0,
              block_s=0.2, trace_s=0.4, checked_requests=48)
    _write(os.path.join(b, "traffic", "tiny-chat-64.json"), tr)
    m = _load(os.path.join(root, "BENCHMARK.json"))
    add_entries(
        m,
        config={"name": "tiny-granite", "source": "tests",
                "file": "benchmarks/configs/tiny-granite.json",
                "reduced": [], "why": "CPU rehearsal"},
        cells=[{"name": TINY, "config": "tiny-granite",
                "traffic": "tiny-chat-64", "chips": 1, "why": "rehearsal"}],
        like={TINY: CELL},
    )
    _write(os.path.join(root, "BENCHMARK.json"), m)
    return root


# 48 requests are checked, nearly all a run finishes, because WHICH
# requests finish inside a window depends on the machine's load while a
# request's own gap does not: at this seed the 131 requests a sound run
# finishes read at most 0.0061 (bf16 operands against float32 near a
# tie; the same values run after run), whichever 48 are taken. Over 48
# requests the weakest break, a state not reset (what a lane's last
# request left decays through the prompt), reads 0.030-0.105 in fifteen
# runs, ten of them on a loaded machine (0.0055-0.028 over 6 requests:
# too near), the float8 control 0.16, padding that moves the state ~2,
# a state lost between chunks ~1.
TINY_LIMIT = 0.012


@pytest.fixture(scope="module")
def rehearsal(granite_copy):
    return run_cell(granite_copy, TINY, seed=SEED, seconds=3.0, trace=1)


def test_rehearsal_runs_and_is_correct(rehearsal):
    rc, run, lines = rehearsal
    assert rc == 0 and run.correct
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"] == {}  # a CPU's numbers get no device name
    assert [c.name for c in run.checks] == [
        "served_logit_gap", "compiles_in_window", "failed_requests"]
    assert run.notes["gaps"]["tokens"] >= 4 * 6
    assert set(run.end_to_end) == {RATE, "setup_s"}
    assert run.end_to_end[RATE] == pytest.approx(
        run.window["window_quotient"])
    # chunk buckets x 2 + 1 programs, none after warm-up
    assert sum(run.counters["compile_counts"].values()) <= 2 * 2 + 1


def test_rehearsal_counts_what_the_lanes_did(rehearsal):
    _, run, _ = rehearsal
    before, after = run.counters["hybrid_counts_timed"]
    d = {k: after[k] - before[k] for k in after}
    # every token but a request's first comes from a live lane's step
    assert 0 < d["ssm_lane_updates_total"] <= 4 * d["steps"]
    assert d["ssm_state_resets_total"] > 0
    # prompts of 9-30: at least 9 real positions a reset lane
    assert d["ssm_prefill_tokens_total"] >= 9 * d["ssm_state_resets_total"] - 30
    assert d["kv_rows_attended_total"] > d["ssm_lane_updates_total"]
    assert after["ssm_state_bytes_per_slot"] == 4 * 4 * (64 * 256 + 3 * 384)
    assert after["kv_bytes_per_slot"] == 2 * 2 * 64 * 128 * 4
    assert run.counters["hybrid_counts_traced"] is not None


def test_readers_read_the_rehearsal_and_nothing_of_an_older_program(
        granite_copy, rehearsal):
    """Every new per-layer metric has a reader that finds its counter
    or span in this run, and returns None (it does not raise) on a run
    of a program that has none: what the parent commit gives."""
    from benchmarks.harness.result import Run

    _, run, _ = rehearsal
    cell = mf.load_cell(TINY, granite_copy)
    readers = cell.layer_readers()
    assert list(readers) == NEW
    run.device.setdefault("kind", "TPU v5 lite")
    got = {n: r.read(run) for n, r in readers.items()}
    assert 0 < got["serve_hy_occupancy_pct"] <= 100
    assert got["serve_hy_host_ms_per_step"] > 0
    # no kernel ran and no program was named on this CPU: the trace's
    # readers find nothing
    for n in ("serve_hy_decode_dev_ms_per_step", "serve_ssm_update_dev_pct",
              "serve_hy_prefill_dev_ms_per_chunk", "serve_hy_attn_pct",
              "serve_ssm_update_roofline_pct"):
        assert got[n] is None, n
    older = Run(cell=cell)
    older.blocks, older.trace = run.blocks, run.trace
    older.counters = {"slots": 4, "sizes": {}}
    older.device = dict(run.device)
    assert all(r.read(older) is None for r in readers.values())


def test_the_window_share_counts_spans_by_what_they_needed(rehearsal):
    """``serve_hy_window_mfu_pct``'s sum, on this run's own spans with a
    device busy time put in by hand: the decode steps' weights, live
    state and rows and the chunks' real positions, nothing else."""
    from benchmarks.layer_metrics import _hy_common as hy

    _, run, _ = rehearsal
    decodes = hy.traced_spans(run, "serve.decode")
    chunks = hy.traced_spans(run, "serve.prefill_chunk")
    assert decodes and chunks
    assert all(1 <= e[4][0] <= 4 and e[4][1] >= e[4][0] for e in decodes)
    d = hy.delta(run, "traced")
    assert set(d) == {"ssm_lane_updates_total", "ssm_prefill_tokens_total",
                      "ssm_state_resets_total", "kv_rows_attended_total"}


def test_control_comes_out_not_correct(granite_copy, rehearsal):
    cell = mf.load_cell(TINY, granite_copy)
    out = os.path.join(granite_copy, "out", TINY)
    res = cell.driver().control(cell, SEED, out)
    assert res["precision"] == "float8" and res["correct"] is False
    assert res["served_gap"] <= TINY_LIMIT < res["control_gap"]


def _jitted(fn):
    import jax

    return jax.jit(fn, donate_argnums=(1,))


def _state_not_reset(served):
    """Admission's first chunk runs the continuing program: the lane's
    K/V rows are masked by position as ever, its recurrent state is
    whatever the last request left."""
    served.engine._chunk_first = served.engine._chunk_cont


def _padding_moves_the_state(served):
    from ddp_tpu.models import granite_hybrid as gh

    real = gh.mamba_run

    def every_position_is_real(spec, p, u, tail, state, length):
        return real(spec, p, u, tail, state, u.shape[0])

    def chunk(lane_attend):
        def fn(p, c, *rest):
            with mock.patch.object(gh, "mamba_run", every_position_is_real):
                return gh.prefill_chunk(served.spec, p, c, *rest,
                                        lane_attend=lane_attend)
        return _jitted(fn)

    served.engine._chunk_first = chunk(False)
    served.engine._chunk_cont = chunk(True)
    served.engine.warmup()  # compiled before the window, as the real ones


def _state_lost_between_chunks(served):
    import jax.numpy as jnp

    from ddp_tpu.models import granite_hybrid as gh

    def fn(p, c, *rest):
        with mock.patch.object(
                gh, "_lane", lambda buf, row, slot: jnp.zeros(buf.shape[2:])):
            return gh.prefill_chunk(served.spec, p, c, *rest,
                                    lane_attend=True)

    served.engine._chunk_cont = _jitted(fn)
    served.engine.warmup()


@pytest.mark.parametrize("break_path", [
    _state_not_reset, _padding_moves_the_state, _state_lost_between_chunks])
def test_a_broken_timed_path_is_not_correct(granite_copy, break_path):
    """What ``correct`` reads is what the timed path produced, in lanes
    others used before, through several chunks and a padded last
    bucket: each way a recurrent lane can go wrong is caught by the
    logits."""
    rc, run, _ = run_cell(granite_copy, TINY, seed=SEED, seconds=2.0,
                          break_path=break_path)
    assert rc == 0 and not run.correct
    assert "served_logit_gap" in {c.name for c in run.checks if not c.ok}
