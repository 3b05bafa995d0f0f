"""The SambaY cell's benchmark files (Phi-4-mini-flash-reasoning),
rehearsed on the CPU at a tiny size.

The cell's entries are in ``BENCHMARK.json``: one configuration with
nothing reduced, one one-chip cell listed under ``serve_tokens_per_s``
(the accepted whole-window quotient, under the bound it has) and eleven
per-layer metrics. EVERY entry is found by NAME, never by count or by
position in the manifest: a later PR's entries come after these and
must not fail a case here. Here a copy of the benchmark gains a tiny
configuration of the ``sambay_serve`` kind and a cell beside the real
one, and runs through ``benchmarks/run.py``'s own ``main``: sound, the
float8 control, and four timed paths broken the ways a lane with a ring
and a cross-decoder can break (a padded position written into the ring,
a chunk that writes its rows before its window layers read, state not
reset at admission, the read-out taken from the wrong position), each
of which has to come out NOT correct."""

from __future__ import annotations

import json
import os
from unittest import mock

import pytest

import bench_contract as bc
from bench_helpers import ROOT, _load, _write, add_entries, run_cell

from benchmarks.harness import manifest as mf
from benchmarks.harness import sambay_flops as sf
from benchmarks.harness import sambay_weights

CELL = "phi4mf-serve-reason-sat"
CONFIG = "phi-4-mini-flash-reasoning-serve"
TRAFFIC = "reason-saturated-64"
RATE = "serve_tokens_per_s"
TINY = "tiny-sambay-1"
SEED = 2**31 + 13  # the driver's seeds pass 32 signed bits
NEW = ["serve_sy_decode_dev_ms_per_step", "serve_sy_prefill_dev_ms_per_chunk",
       "serve_sy_host_ms_per_step", "serve_sy_occupancy_pct",
       "serve_sy_attn_dev_pct", "serve_sy_attn_roofline_pct",
       "serve_sy_ssm_update_roofline_pct", "serve_sy_scan_dev_pct",
       "serve_sy_scan_roofline_pct", "serve_sy_prefill_cross_positions_pct",
       "serve_sy_window_mfu_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _by_name(entries):
    return {e["name"]: e for e in entries}


def test_the_cells_entries_are_additions_under_the_accepted_rate():
    m = bc.manifest_of(ROOT)
    config = _by_name(m["configs"])[CONFIG]
    assert config["reduced"] == [] and config["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    cell = _by_name(m["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    # no end-to-end entry of the cell's own: it reports the accepted
    # quotient under the bound that is there
    assert all(CELL not in e["name"] and CONFIG not in e["name"]
               for e in m["end_to_end"])
    rate = _by_name(m["end_to_end"])[RATE]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    per_layer = _by_name(m["per_layer"])
    for name in NEW:
        e = per_layer[name]
        assert e["workloads"] == [CELL] and e["moves"] == RATE
        assert e["unit"] == ("ms" if "_ms_" in name else "%")
    # the metrics of other cells do not list this one
    assert all(CELL not in e.get("workloads", [])
               for n, e in per_layer.items() if n not in NEW)
    # one four-chip cell among all of them
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1


def test_the_checkout_with_the_cell_keeps_every_rule():
    assert bc.failures(ROOT) == {}


# ---- the counts, against hand counts --------------------------------------


def _sizes():
    cell = mf.load_cell(CELL)
    return cell.driver().model_sizes(cell.config)


def test_parameter_arithmetic_is_the_issues():
    s = _sizes()
    assert s["layer_types"][16:20] == ["mamba", "full", "gmu", "cross"]
    assert sf.counts(s) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                            "cross": 7}
    assert sf.mlp_params(s) == 78_653_440
    assert sf.mixer_params(s, "mamba") == 41_241_600
    assert sf.mixer_params(s, "window") == sf.mixer_params(
        s, "full") == 19_668_864
    assert sf.mixer_params(s, "gmu") == 26_214_400
    assert sf.mixer_params(s, "cross") == 13_112_704
    assert sf.param_count(s) == 3_852_562_944 == (
        32 * 78_653_440 + 9 * 41_241_600 + 9 * 19_668_864
        + 7 * 26_214_400 + 7 * 13_112_704 + 200_064 * 2_560 + 2 * 2_560)
    assert 7.70e9 < sf.weight_bytes(s) < 7.71e9
    # a chunk that does not sample streams the self-decoder alone
    assert sf.weight_bytes(s, sf.SELF, head=False) == 2 * (
        18 * 78_653_440 + 9 * 41_241_600 + 9 * 19_668_864)
    # 7 x (26,214,400 + 13,112,704 + 2 x 78,653,440) parameters
    assert sf.weight_bytes(s, sf.CROSS, head=False) == 2_752_875_776


def test_lane_and_step_bytes_are_the_issues():
    s = _sizes()
    assert sf.kv_row_bytes(s) == 10_240
    lane = sf.lane_bytes(s, 4096)
    assert lane == {"ring": 8 * 512 * 10_240, "shared": 4096 * 10_240,
                    "state": 9 * 5120 * 4 * (16 + 3)}
    assert sum(lane.values()) == 87_388_160  # 87.4 MB; x 64 = 5.59 GB
    # one update call at 64 live lanes: 327,680 B of state in and out a
    # lane, its vectors, and A once: 42 MB
    call = sf.ssm_update_bytes(s, 64)
    assert call == 64 * (2 * 327_680 + (3 * 5120 + 32) * 4) + 327_680
    assert 42e6 < call < 47e6
    # a decode step at 64 live lanes, every lane at position 1,349:
    # rings wrapped (512 rows x 8 layers), 1,350 shared rows x 8 readers
    work = dict(live_lanes=64, ring_rows=64 * 8 * 512,
                shared_rows=64 * 8 * 1350)
    step = sf.decode_step_bytes(s, **work)
    assert step == (2 * 3_852_562_944
                    + 2 * 9 * 64 * (327_680 + 61_440)
                    + (64 * 8 * 512 + 64 * 8 * 1350 + 64 * 9) * 10_240)
    assert 17.9e9 < step < 18.0e9  # 21.9 ms at 819 GB/s
    # the three kinds of lane state are more than half of what it moves
    assert (step - sf.weight_bytes(s)) / step > 0.55
    assert step / 819e9 > 4 * sf.decode_step_flops(s, **work) / 197e12
    # idle lanes count for nothing
    assert sf.decode_step_bytes(
        s, live_lanes=1, ring_rows=8 * 512, shared_rows=8 * 1350) == (
        2 * 3_852_562_944 + 2 * 9 * (327_680 + 61_440)
        + (8 * 512 + 8 * 1350 + 9) * 10_240)
    # a chunk of 512 is bound by operations; one that samples adds the
    # cross-decoder's weights and the head, once
    plain = dict(tokens=512, start=512, final=False)
    last = dict(plain, final=True)
    assert sf.prefill_chunk_flops(s, **plain) / 197e12 > (
        sf.prefill_chunk_bytes(s, **plain) / 819e9)
    assert (sf.prefill_chunk_bytes(s, **last)
            - sf.prefill_chunk_bytes(s, **plain)) == (
        sf.weight_bytes(s, sf.CROSS, head=True) + 7 * 1024 * 10_240)
    assert 0 < (sf.prefill_chunk_flops(s, **last)
                - sf.prefill_chunk_flops(s, **plain)) < 4e9
    # the scan of a chunk: 3 arrays of [512, 5120], B and C, the state
    assert sf.scan_bytes(s, 512) == 4 * (512 * (3 * 5120 + 32)
                                         + 3 * 16 * 5120)


def test_the_cells_configuration_states_everything_published():
    cfg = _load(os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json"))
    rows = [json.loads(line) for line in open(CATALOG)
            if '"Phi-4-mini-flash-reasoning"' in line] if os.path.exists(
        CATALOG) else []
    for r in rows:  # every key of the catalog row, at its value
        assert cfg["source"] == r["source_url"]
        for key, value in r["config"].items():
            assert cfg[key] == value, key
    assert cfg["reduced"] == [] and cfg["num_hidden_layers"] == 32
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["sliding_window"], cfg["vocab_size"]) == (
        2560, 10240, 512, 200064)
    for key, value in cfg["published"].items():
        assert cfg[key] == value, key
    assert cfg["precision"]["control"] == "float8"
    assert cfg["engine"] == {
        "slots": 64, "cache_length": 4096, "prefill_chunk": 512,
        "min_bucket": 256, "max_queue": 4096, "decode_attn": "auto"}
    assert set(cfg["correct"]["limits"]) == {"served_logit_gap"}
    for key in ("mamba_sizes", "layer_table", "differential_attention",
                "biases", "window", "read_out", "weights", "storage",
                "state", "engine"):
        assert key in cfg["assumed"], key
    assert cfg["assumed"]["mamba_sizes"]["dt_rank"] == -(-2560 // 16)


def test_the_cell_resolves_and_its_traffic_is_the_issues():
    cell = mf.load_cell(CELL)
    assert cell.chips == 1 and cell.config["kind"] == "sambay_serve"
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s", RATE}
    assert [m["name"] for m in cell.per_layer()] == NEW
    tr = cell.traffic
    want = dict(generator="poisson_lognormal", prompt_median=1024,
                prompt_sigma=0.35, prompt_min=256, prompt_max=2048,
                new_median=512, new_sigma=0.3, new_min=192, new_max=1024,
                burst=128, tail_s=2.0, block_s=2.0, trace_s=3.0,
                checked_requests=6, order_seed=0)
    assert {k: tr[k] for k in want} == want
    assert tr["burst"] == 2 * cell.config["engine"]["slots"]
    assert tr["lead_s"] in (10.0, 20.0)  # 20: the issue's named fallback
    # the longest prompt and answer fit a lane
    assert tr["prompt_max"] + tr["new_max"] <= cell.config["engine"][
        "cache_length"]


def test_weights_are_a_function_of_seed_and_layer_and_fit_the_program():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.weights import flatten
    from ddp_tpu.models import sambay as sy

    cell = mf.load_cell(CELL)
    driver = cell.driver()
    cfg = _tiny_config(cell.config)
    sizes, spec = driver.model_sizes(cfg), driver.lm_spec(cfg)
    assert tuple(sizes["layer_types"]) == sy.layer_table(8)
    tree = sambay_weights.make_params(SEED, sizes)
    assert {p: tuple(a.shape) for p, a in flatten(tree).items()} == (
        sy.leaf_shapes(spec))
    again = sambay_weights.make_layer(SEED, sizes, 2)
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        again, tree["layers"]["2"])
    assert all(jax.tree.leaves(same))
    other = sambay_weights.make_layer(SEED, sizes, 4)["mamba"]
    m = again["mamba"]
    assert not bool((other["in_proj"] == m["in_proj"]).all())
    assert m["in_proj"].dtype == jnp.bfloat16
    # the recurrence's vectors stay float32, by Mamba-1's initialisation
    assert m["A_log"].dtype == m["dt_proj"]["bias"].dtype == jnp.float32
    assert bool((jnp.exp(m["A_log"][:, 7]) - jnp.arange(1.0, 5.0) < 1e-5
                 ).all())
    dt = jax.nn.softplus(m["dt_proj"]["bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) <= float(dt.max()) <= 0.1 * 1.01
    assert bool((m["D"] == 1).all())
    assert float(jnp.abs(m["conv1d"]["weight"]).max()) <= 0.5
    a = tree["layers"]["1"]["attn"]
    assert bool((a["Wqkv"]["bias"] == 0).all()) and bool(
        (a["subln"] == 1).all())
    assert 0.03 < float(jnp.std(a["lambda_q1"])) < 0.3


# ---- the rehearsal -----------------------------------------------------------


def _tiny_config(cfg: dict) -> dict:
    """Heads of 32 on width 128, Mamba-1 of 256 channels x 4 state
    indices, a window of 8, and the published table at depth 8."""
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=1009, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=128,
               num_hidden_layers=8, sliding_window=8)
    cfg["assumed"]["mamba_sizes"].update(d_inner=256, d_state=4, dt_rank=8)
    # max_queue above the load's 160 connections: on a loaded machine
    # the engine falls behind 30 requests/s, and a full queue refuses
    cfg["engine"].update(slots=4, cache_length=64, prefill_chunk=8,
                         min_bucket=4, max_queue=256)
    return cfg


@pytest.fixture(scope="module")
def sambay_copy(tmp_path_factory):
    root = bc.copy_benchmark(str(tmp_path_factory.mktemp("sambay_copy")))
    b = os.path.join(root, "benchmarks")
    cfg = _tiny_config(_load(os.path.join(b, "configs", CONFIG + ".json")))
    cfg["correct"].update(pad_rows=0, pad_len=0, pad_new=0,
                          limits={"served_logit_gap": TINY_LIMIT})
    _write(os.path.join(b, "configs", "tiny-sambay.json"), cfg)
    tr = _load(os.path.join(b, "traffic", TRAFFIC + ".json"))
    # prompts of several chunks of 8 with a padded last one, longer than
    # the window of 8; answers that wrap the ring
    tr.update(rate_rps=30.0, prompt_median=14, prompt_sigma=0.4,
              prompt_min=9, prompt_max=30, new_median=12, new_sigma=0.3,
              new_min=8, new_max=20, burst=8, lead_s=1.0, tail_s=10.0,
              block_s=0.2, trace_s=0.4, checked_requests=48)
    _write(os.path.join(b, "traffic", "tiny-reason-64.json"), tr)
    m = _load(os.path.join(root, "BENCHMARK.json"))
    add_entries(
        m,
        config={"name": "tiny-sambay", "source": "tests",
                "file": "benchmarks/configs/tiny-sambay.json",
                "reduced": [], "why": "CPU rehearsal"},
        cells=[{"name": TINY, "config": "tiny-sambay",
                "traffic": "tiny-reason-64", "chips": 1, "why": "rehearsal"}],
        like={TINY: CELL},
    )
    _write(os.path.join(root, "BENCHMARK.json"), m)
    return root


# At the published 0.02 and width 128 the mixers add little to the
# residual stream and the tied head hands most tokens back, whatever a
# lane held: a read-out from the wrong position then reads as a sound
# run does (0.0018 against 0.0014). So the rehearsal draws its matrices
# at 0.08, program and reference alike (both take them from
# ``sambay_weights``), and the layers decide the token. 48 requests are
# checked, nearly all a run finishes (which requests finish inside a
# window depends on the machine's load; a request's own gap does not).
# Two seeds read: sound 0.025, 0.030 (bf16 operands against float32
# near a tie); the read-out from the wrong position, which moves a
# request's FIRST token only, 0.50, 0.52; the float8 control 1.26, 1.58;
# state not reset 2.0, 1.6; rows written before the read 2.0, 1.9;
# padding written into the ring 2.4, 2.6. The limit lies between the
# first two in equal ratio.
TINY_STD = 0.08
TINY_LIMIT = 0.12


@pytest.fixture(scope="module", autouse=True)
def matrices_drawn_so_that_the_layers_decide():
    """``sambay_weights`` compiles one builder a set of shapes and the
    standard deviation is a constant of it: builders made under another
    value are dropped on the way in and on the way out."""
    sambay_weights._BUILDERS.clear()
    with mock.patch.object(sambay_weights, "INIT_STD", TINY_STD):
        yield
    sambay_weights._BUILDERS.clear()


@pytest.fixture(scope="module")
def rehearsal(sambay_copy):
    return run_cell(sambay_copy, TINY, seed=SEED, seconds=3.0, trace=1)


def test_rehearsal_runs_and_is_correct(rehearsal):
    rc, run, lines = rehearsal
    assert rc == 0 and run.correct, [
        (c.name, c.value, c.limit) for c in run.checks]
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"] == {}  # a CPU's numbers get no device name
    assert [c.name for c in run.checks] == [
        "served_logit_gap", "compiles_in_window", "failed_requests"]
    assert run.notes["gaps"]["tokens"] >= 4 * 8
    assert set(run.end_to_end) == {RATE, "setup_s"}
    assert run.end_to_end[RATE] == pytest.approx(
        run.window["window_quotient"])
    # chunk buckets x 2 + 1 programs, none after warm-up
    assert sum(run.counters["compile_counts"].values()) <= 2 * 2 + 1


def test_rehearsal_counts_what_the_lanes_did(rehearsal):
    _, run, _ = rehearsal
    before, after = run.counters["sambay_counts_timed"]
    d = {k: after[k] - before[k] for k in after}
    assert 0 < d["ssm_lane_updates_total"] <= 4 * d["steps"]
    assert d["ssm_state_resets_total"] > 0
    # a live lane at pos reads min(pos + 1, 8) ring rows in each of 2
    # window layers and pos + 1 shared rows in each of 2 readers; every
    # lane is past the window (prompts of 9 or more)
    assert d["kv_ring_rows_attended_total"] == 2 * 8 * d[
        "ssm_lane_updates_total"]
    assert d["kv_shared_rows_attended_total"] > d[
        "kv_ring_rows_attended_total"]
    # prefill stops at the full layer: one position a request goes on
    assert d["prefill_self_positions_total"] == d["ssm_prefill_tokens_total"]
    assert 0 < d["prefill_cross_positions_total"] <= d[
        "ssm_state_resets_total"] + 4
    assert d["prefill_self_positions_total"] >= 9 * (
        d["prefill_cross_positions_total"] - 4)
    assert after["kv_ring_bytes_per_slot"] == 2 * 8 * 2 * 64 * 4
    assert after["kv_shared_bytes_per_slot"] == 64 * 2 * 64 * 4
    assert after["ssm_state_bytes_per_slot"] == 3 * 256 * 4 * (4 + 3)
    assert run.counters["sambay_counts_traced"] is not None


def test_readers_read_the_rehearsal_and_nothing_of_an_older_program(
        sambay_copy, rehearsal):
    """Every new per-layer metric has a reader that finds its counter
    or span in this run, and returns None (it does not raise) on a run
    of a program that has none: what the parent commit gives."""
    from benchmarks.harness.result import Run

    _, run, _ = rehearsal
    cell = mf.load_cell(TINY, sambay_copy)
    readers = cell.layer_readers()
    assert list(readers) == NEW
    run.device["kind"] = "TPU v5 lite"  # the readers look its peaks up
    got = {n: r.read(run) for n, r in readers.items()}
    assert 0 < got["serve_sy_occupancy_pct"] <= 100
    assert got["serve_sy_host_ms_per_step"] > 0
    assert 0 < got["serve_sy_prefill_cross_positions_pct"] < 12
    # no kernel ran and no program was named on this CPU: the trace's
    # readers find nothing
    for n in ("serve_sy_decode_dev_ms_per_step", "serve_sy_attn_dev_pct",
              "serve_sy_prefill_dev_ms_per_chunk", "serve_sy_scan_dev_pct",
              "serve_sy_attn_roofline_pct", "serve_sy_scan_roofline_pct",
              "serve_sy_ssm_update_roofline_pct"):
        assert got[n] is None, n
    older = Run(cell=cell)
    older.blocks, older.trace = run.blocks, run.trace
    older.counters = {"slots": 4, "sizes": {}}
    older.device = dict(run.device)
    assert all(r.read(older) is None for r in readers.values())


def test_the_window_share_counts_spans_by_what_they_needed(rehearsal):
    """What ``serve_sy_window_mfu_pct`` and the attention roofline sum:
    this run's own spans carry live lanes, ring rows and shared rows,
    and the chunks say whether they sampled."""
    from benchmarks.layer_metrics import _sy_common as sy

    _, run, _ = rehearsal
    from benchmarks.harness import program_spans as ps

    # a step has one decode span: a record finds its own by the parent
    decodes = {e[3]: e for e in ps.ring() if e[0] == "serve.decode"}
    records = sy.traced_spans(run, "serve.decode_rows")
    chunks = sy.traced_spans(run, "serve.prefill_chunk")
    assert records and chunks
    for e in records:
        ring, shared, live = e[4]
        lanes, rows = decodes[e[3]][4]
        assert 1 <= live <= lanes <= 4 and ring == 2 * 8 * live
        assert shared >= ring and shared <= 2 * rows
    assert {bool(e[4][4]) for e in chunks} <= {True, False}
    d = sy.delta(run, "traced")
    assert {"kv_ring_rows_attended_total", "kv_shared_rows_attended_total",
            "prefill_self_positions_total", "prefill_cross_positions_total",
            "ssm_lane_updates_total"} <= set(d)


def test_control_comes_out_not_correct(sambay_copy, rehearsal):
    cell = mf.load_cell(TINY, sambay_copy)
    out = os.path.join(sambay_copy, "out", TINY)
    res = cell.driver().control(cell, SEED, out)
    assert res["precision"] == "float8" and res["correct"] is False
    assert res["served_gap"] <= TINY_LIMIT < res["control_gap"]


def _jitted(fn):
    import jax

    return jax.jit(fn, donate_argnums=(1,))


def _chunks_under(served, patch):
    """Both chunk programs traced with ``patch`` (a ``mock.patch``
    factory) in place, compiled before the window as the real ones."""
    from ddp_tpu.models import sambay as sy

    def chunk(lane_attend):
        def fn(p, c, *rest):
            with patch(sy):
                return sy.prefill_chunk(served.spec, p, c, *rest,
                                        lane_attend=lane_attend)
        return _jitted(fn)

    served.engine._chunk_first = chunk(False)
    served.engine._chunk_cont = chunk(True)
    served.engine.warmup()


def _padding_written_into_the_ring(served):
    """Every position of a chunk's bucket is taken for real when the
    ring is written: a padded last chunk wraps onto live rows."""
    from ddp_tpu.models import sambay as sy

    real = sy.ring_update
    _chunks_under(served, lambda m: mock.patch.object(
        m, "ring_update",
        lambda old, new, start, length: real(old, new, start, new.shape[0])))


def _rows_written_before_the_window_reads(served):
    """The chunk's rows replace the ring's BEFORE the window layers
    attend: the rows they replace are gone, and the chunk's are there
    twice."""
    from ddp_tpu.models import sambay as sy

    real = sy.ring_attend

    def attend(q, k, v, old_k, old_v, start, length, W, *, lane_attend):
        return real(q, k, v, sy.ring_update(old_k, k, start, length),
                    sy.ring_update(old_v, v, start, length), start, length,
                    W, lane_attend=lane_attend)

    _chunks_under(served, lambda m: mock.patch.object(
        m, "ring_attend", attend))


def _state_not_reset(served):
    """Admission's first chunk runs the continuing program: the lane's
    recurrent state and tail are whatever the last request left."""
    served.engine._chunk_first = served.engine._chunk_cont


def _read_out_from_the_wrong_position(served):
    """The gated memory units of the chunk that samples read the
    read-out of the position BEFORE the one they stand at."""
    import jax.numpy as jnp

    from ddp_tpu.models import sambay as sy

    real = sy.mamba_run

    def shifted(*a, **kw):
        out, y, tail, state = real(*a, **kw)
        return out, jnp.roll(y, 1, axis=0), tail, state

    _chunks_under(served, lambda m: mock.patch.object(
        m, "mamba_run", shifted))


@pytest.mark.parametrize("break_path", [
    _padding_written_into_the_ring, _rows_written_before_the_window_reads,
    _state_not_reset, _read_out_from_the_wrong_position])
def test_a_broken_timed_path_is_not_correct(sambay_copy, break_path):
    """What ``correct`` reads is what the timed path produced, in lanes
    others used before, through several chunks and a padded last
    bucket, rings wrapped: each way such a lane can go wrong is caught
    by the logits."""
    rc, run, _ = run_cell(sambay_copy, TINY, seed=SEED, seconds=1.5,
                          break_path=break_path)
    assert rc == 0 and not run.correct
    assert "served_logit_gap" in {c.name for c in run.checks if not c.ok}


def test_the_time_zero_burst_is_queued_in_order_before_the_loop_starts():
    """The driver's ``Load``: the requests due at time zero reach the
    server one after another, in the generator's order, each on a
    connection of its own, and only then is the server started; the
    requests after them go at their due times and keep their index."""
    import random
    import threading
    import time
    from types import SimpleNamespace as Req

    from benchmarks.drivers import sambay_serve as drv

    class Stub:
        def __init__(self):
            self.engine = Req(accepted_total=0)
            self.started, self.order, self.before_start = False, [], 0
            self.lock, self.go = threading.Lock(), threading.Event()

        def submit(self, body):
            time.sleep(random.random() * 0.003)  # connections of their own
            with self.lock:
                self.order.append(body["max_new_tokens"])
                self.engine.accepted_total += 1
            self.go.wait(5.0)  # no answer before the loop runs
            return 200, {"status": "complete", "tokens": [1]}

        def start_server(self):
            self.before_start = self.engine.accepted_total
            self.started = True
            self.go.set()

    requests = [Req(due_s=0.0, prompt=[i], max_new_tokens=100 + i)
                for i in range(24)]
    requests += [Req(due_s=0.01 * (i + 1), prompt=[24 + i],
                     max_new_tokens=124 + i) for i in range(4)]
    served = Stub()
    load = drv.Load(served, requests)
    clock0 = load.start()
    time.sleep(0.2)
    load.stop()
    load.join()
    assert served.before_start == 24 and served.started
    assert served.order[:24] == [100 + i for i in range(24)]
    records = sorted(load.snapshot(), key=lambda r: r.index)
    assert [r.index for r in records] == list(range(28))
    assert [r.prompt for r in records] == [[i] for i in range(28)]
    assert all(r.due <= clock0 for r in records[:24])
    assert all(r.due > clock0 for r in records[24:])
