"""The reduction from a profiler trace to numbers, checked on a small
trace recorded on the chip (``data/train_step_trace.json``: one train
step on one TPU v5 lite) and on hand-made events."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.harness import flops, manifest
from benchmarks.harness import trace as bt

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "train_step_trace.json")) as f:
        return bt.Trace.from_json(json.load(f))


def test_recorded_busy_and_window(recorded):
    b = bt.busy(recorded)
    assert b["window_s"] == pytest.approx(0.226408623)
    assert b["busy_s"] == pytest.approx(0.223935165)
    assert b["busy_s"] < b["window_s"]


def test_recorded_one_whole_step(recorded):
    (step,) = bt.modules(recorded, "step")
    assert step[2] == "jit_step" and step[6] == 222408623
    assert bt.modules(recorded, "decode") == []


def test_recorded_classes_under_pr22s_names(recorded):
    by = bt.seconds_by_class(recorded)
    top = list(by)[:5]
    assert top == ["matmul:fusion", "matmul:multiply_reduce_fusion",
                   "custom-call:attn", "matmul:convolution_add_fusion",
                   "matmul:convert_reduce_fusion"]
    assert by["matmul:fusion"] == pytest.approx(0.085240186)
    assert sum(by.values()) >= bt.busy(recorded)["busy_s"] - 1e-9
    matmul = sum(v for k, v in by.items() if k.startswith("matmul:"))
    assert 0.70 < matmul / bt.busy(recorded)["busy_s"] < 0.74


def test_recorded_flash_kernels(recorded):
    kernels = [e for e in recorded.device_ops if bt.is_kernel(e)]
    assert len(kernels) == 24  # 8 layers x (forward, dq, dkv)
    secs = bt.seconds_where(recorded, bt.is_kernel)
    assert secs == pytest.approx(0.034798108)
    need = flops.attention_train_flops_per_token(
        seq_len=2048, d_model=2048, depth=8) * 4 * 2048
    share = need / 197e12 / secs * 100
    assert share == pytest.approx(24.06, abs=0.01) and share < 100


def test_recorded_idle_gaps_named_by_the_host_span(recorded):
    gaps = bt.idle_gaps(recorded)
    assert set(gaps) <= {"bench.fence", "bench.loader_fetch",
                         "bench.dispatch", "unspanned", "under_20us"}
    idle = bt.busy(recorded)["window_s"] - bt.busy(recorded)["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle)


def test_recorded_breakdown_shape(recorded):
    b = bt.breakdown(recorded)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in b["device_ops"])


def test_layer_readers_on_the_recorded_trace(recorded):
    cell = manifest.load_cell("cgpt1.3b-train-1chip")
    readers = cell.layer_readers()
    run = SimpleNamespace(
        trace=recorded, spans=None, blocks=[],
        window={"median_block_rate": 36756.0},
        end_to_end={"train_tokens_per_s_per_chip": 31197.0},  # a stall
        device={"kind": "TPU v5 lite"},
        counters={"traced_steps": 1, "tokens_per_step_per_chip": 8192,
                  "timed_steps": 153,
                  "sizes": dict(vocab_size=50257, seq_len=2048,
                                d_model=2048, depth=8)},
    )
    assert readers["train_dev_ms_per_step"].read(run) == pytest.approx(
        223.935165)
    assert readers["train_attn_roofline_pct"].read(run) == pytest.approx(
        24.06, abs=0.01)
    # the step's own pace, whatever a host stall cost the window
    assert readers["train_mfu_pct"].read(run) == pytest.approx(
        60.35, abs=0.01)
    assert readers["train_block_median_tokens_per_s_per_chip"].read(
        run) == 36756.0
    # nothing to read -> nothing reported
    assert readers["train_host_ms_per_step"].read(run) is None
    assert readers["train_stall_pct"].read(run) is None
    run.counters.pop("timed_steps")
    assert readers["train_mfu_pct"].read(run) is None
    assert "train_allreduce_exposed_ms_per_step" not in readers


HLO = {
    "%fusion.310 = (f32[2048,8192]{1,0:T(8,128)}, f32[]{:T(128)}) "
    "fusion(f32[2048,8192]{1,0} %p, f32[] %sub.199), kind=kOutput, "
    "calls=%fused_computation": ("fusion.310", "fusion", "kOutput"),
    "%attn.24 = (bf16[64,2048,128]{2,1,0}, f32[64,2048,128]{2,1,0}) "
    "custom-call(bf16[64,2048,128]{2,1,0} %bitcast.999), "
    'custom_call_target="tpu_custom_call", frontend_attributes={}':
        ("attn.24", "custom-call", "tpu_custom_call"),
    "%all-reduce.3 = f32[2048,2048]{1,0} all-reduce(f32[2048,2048]{1,0} "
    "%g), replica_groups={{0,1,2,3}}, to_apply=%add":
        ("all-reduce.3", "all-reduce", ""),
    "%all-reduce-start.1 = (f32[8]{0}, f32[8]{0}) all-reduce-start("
    "f32[8]{0} %x), to_apply=%add": ("all-reduce-start.1",
                                     "all-reduce-start", ""),
    "%copy-done.89 = bf16[4,2048,2048]{2,1,0} copy-done((bf16[4], u32[]) "
    "%copy-start.89)": ("copy-done.89", "copy-done", ""),
    "%broadcast.98 = bf16[4,2048,16,128]{1,3,2,0:T(8,128)(2,1)} "
    "broadcast(bf16[]{:T(256)} %constant.170), dimensions={}":
        ("broadcast.98", "broadcast", ""),
}


@pytest.mark.parametrize("text", list(HLO))
def test_parse_hlo(text):
    assert bt.parse_hlo(text) == HLO[text]


@pytest.mark.parametrize("inst,opcode,detail,cls", [
    ("fusion.22", "fusion", "kOutput", "matmul:fusion"),
    ("convolution_add_fusion.1", "fusion", "kOutput",
     "matmul:convolution_add_fusion"),
    ("fusion.7", "fusion", "kLoop", "fusion:loop:fusion"),
    ("fusion.2", "fusion", "kCustom", "fusion:custom:fusion"),
    ("attn.24", "custom-call", "tpu_custom_call", "custom-call:attn"),
    ("custom-call.14", "custom-call", "ConcatBitcast",
     "custom-call:ConcatBitcast"),
    ("all-reduce.3", "all-reduce", "", "collective:all-reduce"),
    ("all-reduce-start.1", "all-reduce-start", "", "collective:all-reduce"),
    ("copy-done.89", "copy-done", "", "copy:copy-done"),
    ("sort.1", "sort", "", "op:sort"),
])
def test_op_class(inst, opcode, detail, cls):
    assert bt.op_class((DEV, "ops", inst, opcode, detail, 0, 1)) == cls


def ev(line, inst, opcode, start, dur, detail="", dev=DEV):
    return (dev, line, inst, opcode, detail, start, dur)


def test_exposed_collective_is_what_no_compute_hides():
    tr = bt.Trace(window_ns=(0, 1000), device_ops=[
        ev("ops", "fusion.1", "fusion", 0, 400, "kOutput"),
        # synchronous all-reduce: 100 ns, nothing beside it
        ev("ops", "all-reduce.1", "all-reduce", 400, 100),
        ev("ops", "fusion.2", "fusion", 500, 200, "kOutput"),
        # asynchronous one: in flight 600..900, compute covers 600..700
        ev("ops", "all-reduce-start.2", "all-reduce-start", 600, 1),
        ev("async", "all-reduce-start.2", "all-reduce-start", 600, 300),
        ev("ops", "all-reduce-done.2", "all-reduce-done", 899, 1),
    ])
    assert bt.exposed_collective_s(tr, "all-reduce") * 1e9 == pytest.approx(
        100 + 200)
    assert bt.exposed_collective_s(tr, "all-gather") == 0.0
    b = bt.busy(tr)
    assert b["busy_s"] * 1e9 == pytest.approx(400 + 100 + 200 + 1 + 1 - 1)


def test_busy_is_a_union_and_containers_do_not_count():
    tr = bt.Trace(window_ns=(0, 100), device_ops=[
        ev("ops", "while.1", "while", 0, 100),
        ev("ops", "fusion.1", "fusion", 10, 20, "kLoop"),
        ev("ops", "fusion.2", "fusion", 20, 20, "kLoop"),  # overlaps
        ev("ops", "fusion.3", "fusion", 90, 30, "kLoop"),  # leaves window
    ])
    assert bt.busy(tr)["busy_s"] * 1e9 == pytest.approx(30 + 10)
    assert "op:while" not in bt.seconds_by_class(tr)


def test_busy_averages_over_devices():
    tr = bt.Trace(window_ns=(0, 100), device_ops=[
        ev("ops", "f.1", "fusion", 0, 100, "kLoop", dev="/device:TPU:0"),
        ev("ops", "f.1", "fusion", 0, 50, "kLoop", dev="/device:TPU:1"),
    ])
    assert bt.busy(tr)["busy_s"] * 1e9 == pytest.approx(75)


def test_idle_gap_attribution():
    tr = bt.Trace(
        window_ns=(0, 200_000),
        device_ops=[ev("ops", "f.1", "fusion", 0, 50_000, "kLoop"),
                    ev("ops", "f.2", "fusion", 100_000, 40_000, "kLoop"),
                    ev("ops", "f.3", "fusion", 150_000, 50_000, "kLoop")],
        host_spans=[("bench.loader_fetch", 45_000, 40_000)],
    )
    gaps = bt.idle_gaps(tr)
    assert gaps == {"bench.loader_fetch": pytest.approx(50_000 / 1e9),
                    "under_20us": pytest.approx(10_000 / 1e9)}


def test_roundtrip_json(recorded):
    again = bt.Trace.from_json(json.loads(json.dumps(recorded.to_json())))
    assert again.device_ops == recorded.device_ops
    assert again.window_ns == recorded.window_ns
