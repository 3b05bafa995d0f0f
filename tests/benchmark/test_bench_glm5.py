"""The GLM-5 cell's benchmark files, rehearsed on the CPU at a tiny size.

The cell's entries are in ``BENCHMARK.json``: one configuration with the
depth, the experts held and the vocabulary reduced, one one-chip cell
listed under ``serve_tokens_per_s`` (the accepted whole-window quotient,
under the bound it has) and eleven per-layer metrics. EVERY entry is
found by NAME, never by count or by position in the manifest: a later
PR's entries come after these and must not fail a case here. Here a
copy of the benchmark gains a tiny configuration of the
``glm_dsa_serve`` kind (``index_topk`` 8 under contexts of 20 to 60, so
the selection is live) and a cell beside the real one, and runs through
``benchmarks/run.py``'s own ``main``: sound, the float8 control, and
four timed paths broken the ways a lane of latent rows whose keys are
selected can break (the indexer skipped, a reused lane's stale indexer
rows, the rope key stored unrotated, the router's bias added to the
weight), each of which has to come out NOT correct, by the limit that
is there for it."""

from __future__ import annotations

import contextlib
import json
import os
from unittest import mock

import pytest

import bench_contract as bc
from bench_helpers import ROOT, _load, _write, add_entries, run_cell

from benchmarks.harness import glm_dsa_flops as gf
from benchmarks.harness import glm_dsa_weights
from benchmarks.harness import manifest as mf

CELL = "glm5-serve-longctx-sat"
CONFIG = "glm-5-serve-ep16"
TRAFFIC = "longctx-saturated-16"
RATE = "serve_tokens_per_s"
TINY = "tiny-glm-1"
SEED = 2**31 + 13  # the driver's seeds pass 32 signed bits
NEW = ["serve_gd_occupancy_pct", "serve_gd_host_ms_per_step",
       "serve_gd_decode_dev_ms_per_step", "serve_gd_prefill_dev_ms_per_chunk",
       "serve_gd_select_dev_pct", "serve_gd_attn_dev_pct",
       "serve_gd_selected_rows_pct", "serve_gd_moe_dev_pct",
       "serve_gd_moe_roofline_pct", "serve_gd_pairs_held_pct",
       "serve_gd_window_mfu_pct"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _by_name(entries):
    return {e["name"]: e for e in entries}


def test_the_cells_entries_are_additions_under_the_accepted_rate():
    m = bc.manifest_of(ROOT)
    config = _by_name(m["configs"])[CONFIG]
    assert config["reduced"] == REDUCED and config["source"].endswith(
        "zai-org/GLM-5/blob/main/config.json")
    cell = _by_name(m["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert "16x its share" in cell["why"] and "3 of 7" in cell["why"]
    # no end-to-end entry of the cell's own: it reports the accepted
    # quotient under the bound that is there
    assert all(CELL not in e["name"] and CONFIG not in e["name"]
               for e in m["end_to_end"])
    rate = _by_name(m["end_to_end"])[RATE]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    per_layer = _by_name(m["per_layer"])
    for name in NEW:
        e = per_layer[name]
        # by name: a later cell may be appended to any of these lists
        assert CELL in e["workloads"] and e["moves"] == RATE
        assert e["unit"] == ("ms" if "_ms_" in name else "%")


def test_the_checkout_with_the_cell_keeps_every_rule():
    assert bc.failures(ROOT) == {}


# ---- the counts, against hand counts --------------------------------------


def _sizes():
    cell = mf.load_cell(CELL)
    return cell.driver().model_sizes(cell.config)


def test_parameter_arithmetic_is_the_issues():
    s = _sizes()
    assert gf.mla_params(s) == 165_019_648 == (
        6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
        + 64 * 256 * 6144)
    assert gf.indexer_params(s) == 9_371_648
    assert gf.ffn_params(s, True) == 226_492_416
    assert gf.expert_params(s) == 37_748_736
    assert 400.8e6 < gf.layer_params(s, 0) < 401.0e6
    assert 817.6e6 < gf.layer_params(s, 3) < 817.8e6
    assert gf.layer_params(s, 2) == gf.layer_params(s, 0)
    assert gf.layer_params(s, 6) == gf.layer_params(s, 3)
    assert 4_711e6 < gf.param_count(s) < 4_712e6
    assert 9.42e9 < gf.weight_bytes(s) < 9.43e9
    # the same count over the whole model is the source's "744B"
    whole = dict(s, depth=78, experts_held=256, vocab_size=154880)
    assert 743.8e9 < gf.param_count(whole) < 744.0e9


def test_lane_and_step_bytes_are_the_issues():
    s = _sizes()
    assert gf.row_bytes(s) == {"latent": 1152, "index": 256}
    assert gf.lane_bytes(s, 1) == 9_856  # 704 values x 2 B x 7 layers
    assert 2.74e9 < 16 * gf.lane_bytes(s, 17408) < 2.75e9
    # stored: the latent row padded to 640 lanes
    assert gf.stored_lane_bytes(s, 1) == 7 * (640 + 128) * 2
    assert 2.99e9 < 16 * gf.stored_lane_bytes(s, 17408) < 3.0e9
    # 16 live lanes at 9,000 rows each: ~8 pairs fall on ~6.5 experts
    assert gf.pairs_held(s, 16) == 8.0
    assert 6.0 < gf.experts_hit(s, 16) < 7.0
    assert 15.9 < gf.experts_hit(s, 2048) <= 16.0
    work = dict(live_lanes=16, rows_scored=16 * 7 * 9000,
                rows_selected=16 * 7 * 2048)
    step = gf.decode_step_bytes(s, **work)
    assert 6.5e9 < step < 7.1e9  # 8.3 ms at 819 GB/s
    # bound by bytes: the weights are most of what a step moves
    assert step / 819e9 > 10 * gf.decode_step_flops(s, **work) / 197e12
    # idle lanes count for nothing
    one = gf.decode_step_bytes(s, live_lanes=1, rows_scored=7 * 9000,
                               rows_selected=7 * 2048)
    assert one < step - 15 * 7 * (9000 * 256 + 2048 * 1152)
    # a chunk of 2,048 is bound by operations; one that samples adds
    # the head, once
    from ddp_tpu.models.glm_dsa import dsa_rows

    cell = mf.load_cell(CELL)
    scored, selected = dsa_rows(cell.driver().lm_spec(cell.config), 6144,
                                2048)
    assert selected == 7 * 2048 * 2048 < scored
    plain = dict(tokens=2048, start=6144, final=False)
    rows = dict(rows_scored=scored, rows_selected=selected)
    assert gf.prefill_chunk_flops(s, **plain, **rows) / 197e12 > 3 * (
        gf.prefill_chunk_bytes(s, **plain) / 819e9)
    assert (gf.prefill_chunk_bytes(s, **dict(plain, final=True))
            - gf.prefill_chunk_bytes(s, **plain)) == 2 * (
        19360 * 6144 + 6144)
    # the two grouped calls of a routed layer in a decode step: ~6.5
    # experts' matrices, 8 pairs
    assert 0.45e9 < gf.grouped_expert_bytes(
        s, 8.0, gf.experts_hit(s, 16)) < 0.5e9


def test_the_expert_kernels_roofline_counts_the_need_call_by_call(
        monkeypatch):
    """The window that read 168% when the need was taken of the MEAN
    call (my chip run, PR 43, second round: ~133 decode steps of 16
    tokens and ~8 chunks of ~1,900 in 4.23 s, the two kernels 0.4955 s
    over 4 routed layers a call, 6.43% of the pairs held): a step needs
    ~0.6 ms a layer (6-7 experts' matrices), a chunk ~1.5 ms (all 16);
    the mean call of ~120 tokens would need all 16 as well."""
    from types import SimpleNamespace

    from benchmarks.layer_metrics import _gd_common as gd
    from benchmarks.layer_metrics import serve_gd_moe_roofline_pct as reader

    s = _sizes()
    steps, chunks, layers = 133, 8, 4
    routed = (steps * 16 + chunks * 1900) * 8 * layers
    run = SimpleNamespace(
        trace=object(), device={"kind": "TPU v5 lite"},
        counters={"glm_dsa_slots": 16, "sizes": s,
                  "glm_dsa_counts_traced": (
                      {"moe_pairs_held_total": 0,
                       "moe_pairs_routed_total": 0},
                      {"moe_pairs_held_total": int(0.0643 * routed),
                       "moe_pairs_routed_total": routed})})
    spans = {"serve.decode_selected": [(0, 0, 0, 0, (1, 1, 16))] * steps,
             "serve.chunk_selected": [(0, 0, 0, 0, (1, 1, 1900, 0, 0))]
             * chunks}
    monkeypatch.setattr(gd, "traced_spans", lambda run, name: spans[name])
    monkeypatch.setattr(gd, "kernel_seconds", lambda run, names: 0.4955)
    monkeypatch.setattr(gd, "kernel_events",
                        lambda run, name: (steps + chunks) * layers)
    got = reader.read(run)
    by_hand = 100 * layers * (
        steps * gf.grouped_expert_bytes(s, 8.23, 6.59)
        + chunks * gf.grouped_expert_bytes(s, 977.0, 16.0)) / 819e9 / 0.4955
    assert 70 < got < 80 and abs(got - by_hand) < 1.0
    # no record of a call, or no kernel in the trace: nothing to read
    spans = {k: [] for k in spans}
    assert reader.read(run) is None
    monkeypatch.setattr(gd, "kernel_seconds", lambda run, names: None)
    assert reader.read(run) is None


def test_the_cells_configuration_states_everything_published():
    cfg = _load(os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json"))
    rows = [json.loads(line) for line in open(CATALOG)
            if '"name": "GLM-5"' in line] if os.path.exists(CATALOG) else []
    for r in rows:  # every key of the catalog row, at its value
        assert cfg["source"] == r["source_url"]
        for key, value in r["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert {k: r["config"][k] for k in REDUCED} == {
            k: cfg["published"][k] for k in REDUCED}
    assert cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 19360)
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["vocab_size"] == 8 * cfg["vocab_size"]
    # every width, the dense layers and the router's choice as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"], cfg["index_topk"],
            cfg["first_k_dense_replace"], cfg["num_experts_per_tok"]) == (
        6144, 12288, 2048, 2048, 512, 192, 64, 256, 32, 128, 2048, 3, 8)
    for key, value in cfg["published"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    for word in ("16 v5e chips", "EP16", "8 ways", "first"):
        assert word in cfg["deployment"], word
    assert cfg["share"]["experts_held"] == cfg["n_routed_experts"]
    assert cfg["precision"]["control"] == "float8"
    assert cfg["engine"] == {
        "slots": 16, "cache_length": 17408, "prefill_len": 16384,
        "prefill_chunk": 2048,
        "min_bucket": 1024, "max_queue": 4096, "decode_attn": "auto"}
    assert set(cfg["correct"]["limits"]) == {
        "served_logit_gap", "selection_overlap"}
    assert 0.5 < cfg["correct"]["limits"]["selection_overlap"] < 1.0
    for key in ("multi_token_prediction", "ep_size", "stored_row",
                "indexer_precision",
                "indexer_norm", "rotary", "selection", "router",
                "router_bias", "weights", "engine", "depth"):
        assert key in cfg["assumed"], key


def test_the_cell_resolves_and_its_traffic_is_the_issues():
    cell = mf.load_cell(CELL)
    assert cell.chips == 1 and cell.config["kind"] == "glm_dsa_serve"
    assert {m["name"] for m in cell.end_to_end()} == {"setup_s", RATE}
    # by name: the cell may gain metrics that a later PR lists it under
    assert set(NEW) <= {m["name"] for m in cell.per_layer()}
    tr = cell.traffic
    want = dict(generator="poisson_lognormal", prompt_median=8192,
                prompt_sigma=0.35, prompt_min=4096, prompt_max=16384,
                new_median=512, new_sigma=0.3, new_min=192, new_max=1024,
                burst=32, tail_s=2.0, block_s=2.0, trace_s=3.0,
                checked_requests=6, order_seed=0)
    assert {k: tr[k] for k in want} == want
    assert tr["burst"] == 2 * cell.config["engine"]["slots"]
    assert tr["lead_s"] in (10.0, 20.0)  # 20: the issue's named fallback
    # every prompt is past the indexer's top-k: the selection is live
    assert tr["prompt_min"] > cell.config["index_topk"]
    # the longest prompt and answer fit a lane
    assert tr["prompt_max"] + tr["new_max"] <= cell.config["engine"][
        "cache_length"]


def _tiny_config(cfg: dict) -> dict:
    """Heads of 16 + 16 on width 128, a latent of 32, an indexer of 2
    heads of 32 choosing 8 keys, 1 dense and 2 routed layers of 16
    experts (4 held, numbers 4-7), top-4."""
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=1009, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=16, qk_head_dim=32,
               v_head_dim=16, index_n_heads=2, index_head_dim=32,
               index_topk=8, intermediate_size=256, moe_intermediate_size=64,
               num_hidden_layers=3, first_k_dense_replace=1,
               n_routed_experts=4, num_experts_per_tok=4)
    cfg["published"]["n_routed_experts"] = 16
    cfg["share"]["first_expert"] = 4
    # max_queue above the load's 160 connections: on a loaded machine
    # the engine falls behind 30 requests/s, and a full queue refuses
    cfg["engine"].update(slots=4, cache_length=64, prefill_len=40,
                         prefill_chunk=8, min_bucket=4, max_queue=256)
    return cfg


def test_weights_are_a_function_of_seed_and_layer_and_fit_the_program():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.weights import flatten
    from ddp_tpu.models import glm_dsa as gd

    cell = mf.load_cell(CELL)
    driver = cell.driver()
    cfg = _tiny_config(cell.config)
    sizes, spec = driver.model_sizes(cfg), driver.lm_spec(cfg)
    gd.validate(spec)
    assert (spec.n_routed_experts, spec.num_experts, spec.expert_offset) == (
        16, 4, 4)
    tree = glm_dsa_weights.make_params(SEED, sizes)
    assert {p: tuple(a.shape) for p, a in flatten(tree).items()} == (
        gd.leaf_shapes(spec))
    again = glm_dsa_weights.make_layer(SEED, sizes, 2)
    same = jax.tree.map(lambda a, b: bool((a == b).all()),
                        again, tree["layers"]["2"])
    assert all(jax.tree.leaves(same))
    other = glm_dsa_weights.make_layer(SEED, sizes, 1)["mlp"]
    m = again["mlp"]
    assert not bool((other["gate"] == m["gate"]).all())
    # stored as published where nothing says otherwise (this file's
    # rehearsal says float32); the choice bias is float32 in both
    assert glm_dsa_weights.make_layer(
        SEED, sizes, 2, jnp.bfloat16)["mlp"]["gate"].dtype == jnp.bfloat16
    assert glm_dsa_weights.make_layer.__defaults__ == (None,)
    assert "gate" not in tree["layers"]["0"]["mlp"]  # the dense layer
    # the choice bias is float32 and small beside the scores' spread
    assert m["gate_bias"].dtype == jnp.float32
    assert 0.003 < float(jnp.std(m["gate_bias"])) < 0.03
    ix = again["self_attn"]["indexer"]
    assert bool((ix["k_norm"]["weight"] == 1).all()) and bool(
        (ix["k_norm"]["bias"] == 0).all())
    # the published sizes count what the issue counts
    real = driver.model_sizes(cell.config)
    shapes = {**glm_dsa_weights.top_shapes(**real)}
    n = sum(int(jnp.prod(jnp.asarray(s))) for s in shapes.values())
    for i in range(real["depth"]):
        n += sum(int(jnp.prod(jnp.asarray(s))) for s in
                 glm_dsa_weights.layer_shapes(
                     glm_dsa_weights.is_dense(real, i), **real).values())
    assert n == gf.param_count(real)


# ---- the rehearsal -----------------------------------------------------------


@pytest.fixture(scope="module")
def glm_copy(tmp_path_factory):
    root = bc.copy_benchmark(str(tmp_path_factory.mktemp("glm_copy")))
    b = os.path.join(root, "benchmarks")
    cfg = _tiny_config(_load(os.path.join(b, "configs", CONFIG + ".json")))
    cfg["correct"].update(pad_len=0, pad_new=0, limits=dict(TINY_LIMITS))
    _write(os.path.join(b, "configs", "tiny-glm.json"), cfg)
    tr = _load(os.path.join(b, "traffic", TRAFFIC + ".json"))
    # prompts of several chunks of 8 with a padded last one, all longer
    # than the indexer's top-8; answers that fill a lane of 64
    tr.update(rate_rps=30.0, prompt_median=20, prompt_sigma=0.4,
              prompt_min=12, prompt_max=40, new_median=12, new_sigma=0.3,
              new_min=8, new_max=20, burst=8, lead_s=1.0, tail_s=10.0,
              block_s=0.2, trace_s=0.4, checked_requests=48)
    _write(os.path.join(b, "traffic", "tiny-longctx.json"), tr)
    m = _load(os.path.join(root, "BENCHMARK.json"))
    add_entries(
        m,
        config={"name": "tiny-glm", "source": "tests",
                "file": "benchmarks/configs/tiny-glm.json",
                "reduced": REDUCED, "why": "CPU rehearsal"},
        cells=[{"name": TINY, "config": "tiny-glm",
                "traffic": "tiny-longctx", "chips": 1, "why": "rehearsal"}],
        like={TINY: CELL},
    )
    _write(os.path.join(root, "BENCHMARK.json"), m)
    return root


# At the published 0.02 and width 128 the mixers add little to the
# residual stream, whatever a lane held; so the rehearsal draws its
# matrices at 0.08, program and reference alike (both take them from
# ``glm_dsa_weights``), and the layers decide the token. And it stores
# them, and with them the lanes, in FLOAT32: where a query attends 8
# rows, one row swapped near a tie is an eighth of its attention, and
# bfloat16 rounding against the float32 reference then reads as a broken
# path does (gaps to 2.2, overlaps to 0.75, on this seed); what is left
# in float32 is the order of the sums. The chip's cell selects 2,048,
# where a row is a 2,048th. 48 requests are checked, nearly all a run
# finishes. The limits lie between the sound readings and the broken
# paths' (the comment at ``BROKEN``).
TINY_STD = 0.08
TINY_LIMITS = {"served_logit_gap": 0.02, "selection_overlap": 0.9}


@pytest.fixture(scope="module", autouse=True)
def matrices_drawn_so_that_the_layers_decide():
    """``glm_dsa_weights`` compiles one builder a set of shapes and the
    standard deviation is a constant of it; the program walks a lane 16
    keys at a time and the reference 16 queries, so both loops run."""
    from benchmarks.reference import glm_dsa_ref as ref
    from ddp_tpu.models import glm_dsa as gd

    glm_dsa_weights._BUILDERS.clear()
    import jax.numpy as jnp

    with mock.patch.object(glm_dsa_weights, "INIT_STD", TINY_STD), \
            mock.patch.object(glm_dsa_weights, "DTYPE", jnp.float32), \
            mock.patch.object(gd, "KEY_BLOCK", 16), \
            mock.patch.object(ref, "Q_BLOCK", 16):
        yield
    glm_dsa_weights._BUILDERS.clear()


@pytest.fixture(scope="module")
def rehearsal(glm_copy):
    return run_cell(glm_copy, TINY, seed=SEED, seconds=3.0, trace=1)


def test_rehearsal_runs_and_is_correct(rehearsal):
    rc, run, lines = rehearsal
    assert rc == 0 and run.correct, [
        (c.name, c.value, c.limit) for c in run.checks]
    result = json.loads(lines[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"] == {}  # a CPU's numbers get no device name
    assert [c.name for c in run.checks] == [
        "served_logit_gap", "selection_overlap_shortfall",
        "compiles_in_window", "failed_requests"]
    gaps = run.notes["gaps"]
    assert gaps["tokens"] >= 4 * 8
    # every checked request recorded its last step's rows: 8 a layer
    assert gaps["rows_selected"] and all(
        row == [8, 8, 8] for row in gaps["rows_selected"])
    assert set(run.end_to_end) == {RATE, "setup_s"}
    assert run.end_to_end[RATE] == pytest.approx(
        run.window["window_quotient"])
    # chunk buckets x 2 + decode + the selection copy, none after warm-up
    assert sum(run.counters["compile_counts"].values()) <= 2 * 2 + 2


def test_rehearsal_counts_what_the_lanes_did(rehearsal):
    _, run, _ = rehearsal
    before, after = run.counters["glm_dsa_counts_timed"]
    d = {k: after[k] - before[k] for k in after}
    # every prompt is past the top-8: far fewer rows read than scored
    assert 0 < d["dsa_rows_selected_total"] < d["dsa_rows_scored_total"] / 2
    # 4 of 16 experts held, top-4 of a router that favours none
    assert d["moe_pairs_routed_total"] > 0
    share = d["moe_pairs_held_total"] / d["moe_pairs_routed_total"]
    assert 0.15 < share < 0.35
    assert after["latent_bytes_per_slot"] == 3 * 64 * (128 + 32) * 4
    assert run.counters["glm_dsa_counts_traced"] is not None


def test_readers_read_the_rehearsal_and_nothing_of_an_older_program(
        glm_copy, rehearsal):
    """Every new per-layer metric has a reader that finds its counter
    or span in this run, and returns None (it does not raise) on a run
    of a program that has none: what the parent commit gives."""
    from benchmarks.harness.result import Run

    _, run, _ = rehearsal
    cell = mf.load_cell(TINY, glm_copy)
    readers = cell.layer_readers()
    assert set(NEW) <= set(readers)
    run.device["kind"] = "TPU v5 lite"  # the readers look its peaks up
    got = {n: r.read(run) for n, r in readers.items()}
    assert 0 < got["serve_gd_occupancy_pct"] <= 100
    assert got["serve_gd_host_ms_per_step"] > 0
    assert 0 < got["serve_gd_selected_rows_pct"] < 50
    assert 15 < got["serve_gd_pairs_held_pct"] < 35
    # no kernel ran, no program was named and no name stack was kept on
    # this CPU: the trace's readers find nothing
    for n in ("serve_gd_decode_dev_ms_per_step", "serve_gd_moe_dev_pct",
              "serve_gd_prefill_dev_ms_per_chunk", "serve_gd_select_dev_pct",
              "serve_gd_attn_dev_pct", "serve_gd_moe_roofline_pct"):
        assert got[n] is None, n
    older = Run(cell=cell)
    older.blocks, older.trace = run.blocks, run.trace
    older.counters = {"slots": 4, "sizes": {}}
    older.device = dict(run.device)
    assert all(r.read(older) is None for r in readers.values())


def test_the_scopes_are_found_in_a_name_stack():
    from benchmarks.layer_metrics import _gd_common as gd

    stack = "jit(serve_prefill_chunk)/jit(main)/mla_prefill/dsa_index/dot_general"
    assert gd.innermost_scope(stack) == "dsa_index"
    assert gd.innermost_scope(
        "jit(serve_decode)/jit(main)/mla_decode/reduce_max") == "mla_decode"
    assert gd.innermost_scope("jit(serve_decode)/jit(main)/moe/dot") is None
    assert gd.innermost_scope("jit(f)/my_dsa_index_like/dot") is None


def test_device_time_is_joined_to_scopes_by_instruction_name(rehearsal):
    """The profile names a device operation by its HLO instruction and
    keeps no name stack; the compiled text has both. An operation is
    looked up in the map of the program execution it ran inside."""
    from benchmarks.harness.trace import Trace
    from benchmarks.layer_metrics import _gd_common as gd

    text = '''HloModule jit_serve_decode
  %fusion.7 = f32[16,17408]{1,0} fusion(%a, %b), kind=kOutput, calls=%fc.7, metadata={op_name="jit(serve_decode)/jit(main)/dsa_index/dot_general" source_file="x.py"}
  %sort.3 = (f32[16,17408]{1,0}, s32[16,17408]{1,0}) sort(%p, %q), metadata={op_name="jit(serve_decode)/jit(main)/dsa_select/top_k"}
  ROOT %fusion.9 = f32[16,64,512]{2,1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(serve_decode)/jit(main)/mla_decode/reduce_max"}
  %copy.1 = f32[8]{0} copy(%z)
  %fusion.2 = f32[8]{0} fusion(%z), kind=kLoop, metadata={op_name="jit(serve_decode)/jit(main)/mul"}
'''
    assert gd.scope_map(text) == {
        "fusion.7": "dsa_index", "sort.3": "dsa_select",
        "fusion.9": "mla_decode"}
    maps = gd.scope_maps({"serve_decode": text,
                          "serve_prefill_chunk:8": text.replace(
                              "dsa_index", "mla_prefill"),
                          "serve_prefill_chunk:4": text})
    assert maps["jit_serve_prefill_chunk"]["fusion.7"] == "mla_prefill"
    # the rehearsal carried the maps of its own five programs
    _, run, _ = rehearsal
    own = run.counters["scope_maps"]
    assert {"jit_serve_decode", "jit_serve_prefill_first",
            "jit_serve_prefill_chunk"} <= set(own)
    assert {"dsa_index", "dsa_select", "mla_decode"} <= set(
        own["jit_serve_decode"].values())
    assert "mla_prefill" in set(own["jit_serve_prefill_chunk"].values())
    # a trace of two executions: the same instruction name counts for
    # the scope its own program gives it
    dev, ms = "/device:TPU:0", 1_000_000
    op = lambda inst, t0, dur, code="fusion": (
        dev, "ops", inst, code, "", t0, dur)
    tr = Trace(window_ns=(0, 100 * ms), device_ops=[
        (dev, "module", "jit_serve_decode", "", "", 0, 10 * ms),
        op("fusion.7", 1 * ms, 2 * ms), op("sort.3", 3 * ms, 1 * ms, "sort"),
        op("fusion.2", 4 * ms, 5 * ms),
        (dev, "module", "jit_serve_prefill_chunk", "", "", 20 * ms, 30 * ms),
        op("fusion.7", 21 * ms, 8 * ms), op("while.1", 21 * ms, 20 * ms,
                                            "while"),
        op("fusion.7", 60 * ms, 3 * ms),  # inside no execution
    ])

    class R:
        counters = {"glm_dsa_slots": 4, "scope_maps": maps}
        trace = tr

    table = gd.scope_table(R)
    assert table == pytest.approx(
        {"dsa_index": 0.002, "dsa_select": 0.001, "mla_prefill": 0.008})
    busy = 0.008 + 0.008 + 0.003  # the union; a loop is not its own op
    assert gd.scope_share_of_busy(R, ("dsa_index", "dsa_select")) == (
        pytest.approx(0.003 / busy * 100))
    R.counters = {"glm_dsa_slots": 4}
    assert gd.scope_table(R) is None


def test_the_window_share_counts_spans_by_what_they_needed(rehearsal):
    """What ``serve_gd_window_mfu_pct`` sums: this run's own records
    carry the rows scored and selected and the live lanes of a step,
    and a chunk's real positions, start and whether it sampled."""
    from benchmarks.harness import program_spans as ps
    from benchmarks.layer_metrics import _gd_common as gd

    _, run, _ = rehearsal
    decodes = {e[3]: e for e in ps.ring() if e[0] == "serve.decode"}
    records = gd.traced_spans(run, "serve.decode_selected")
    chunks = gd.traced_spans(run, "serve.chunk_selected")
    assert records and chunks
    for e in records:
        scored, selected, live = e[4]
        lanes, _ = decodes[e[3]][4]
        assert 1 <= live <= lanes <= 4
        assert selected == 3 * 8 * live < scored
    for e in chunks:
        scored, selected, tokens, start, final = e[4]
        assert 1 <= tokens <= 8 and final in (0, 1)
        assert selected == 3 * sum(
            min(t + 1, 8) for t in range(start, start + tokens)) <= scored
    assert {"dsa_rows_scored_total", "dsa_rows_selected_total",
            "moe_pairs_routed_total", "moe_pairs_held_total"} <= set(
        gd.delta(run, "traced"))


def test_control_comes_out_not_correct(glm_copy, rehearsal):
    cell = mf.load_cell(TINY, glm_copy)
    out = os.path.join(glm_copy, "out", TINY)
    res = cell.driver().control(cell, SEED, out)
    assert res["precision"] == "float8" and res["correct"] is False
    assert res["served_gap"] <= TINY_LIMITS["served_logit_gap"] < res[
        "control_gap"]
    assert res["overlap"] >= TINY_LIMITS["selection_overlap"]


# ---- timed paths broken underneath ----------------------------------------


def _programs_under(served, patch=contextlib.nullcontext, *, first=None):
    """The engine's three programs traced anew with ``patch`` (a context
    manager factory) in place, compiled before the window as the real
    ones are. ``first`` wraps the first-chunk program's result."""
    import jax

    from ddp_tpu.models import glm_dsa as gd

    spec = served.spec

    def chunk(lane_attend):
        def fn(p, c, *rest):
            with patch():
                out = gd.prefill_chunk(spec, p, c, *rest,
                                       lane_attend=lane_attend)
            return first(c, out) if first and not lane_attend else out
        return jax.jit(fn, donate_argnums=(1,))

    def decode(p, c, *rest):
        with patch():
            return gd.slot_decode_sample_step(spec, p, c, *rest)

    served.engine._chunk_first = chunk(False)
    served.engine._chunk_cont = chunk(True)
    served.engine._decode = jax.jit(decode, donate_argnums=(1,))
    served.engine.warmup()


def _indexer_skipped(served):
    """A decode step attends a lane's LAST ``index_topk`` rows, whatever
    the indexer scored."""
    import jax.numpy as jnp

    from ddp_tpu.models import glm_dsa as gd

    def last_rows(scores, pos, top_k):
        K = min(top_k, scores.shape[-1])
        rows = pos[:, None] - jnp.arange(K, dtype=jnp.int32)[None, :]
        return jnp.maximum(rows, 0), rows >= 0

    _programs_under(served, lambda: mock.patch.object(
        gd, "select_rows", last_rows))


def _stale_indexer_rows_of_a_reused_lane(served):
    """Admission's first chunk leaves the lane's indexer rows as the
    last request left them: the latent rows are the new request's, what
    selects among them is not."""
    _programs_under(served, first=lambda cache, out: (
        out[0]._replace(index_k=cache.index_k),) + tuple(out[1:]))


def _rope_key_unrotated_in_the_cache(served):
    """A position's rope key is stored as projected: the queries turn
    with their positions, the keys do not."""
    import jax.numpy as jnp

    from ddp_tpu.models import glm_dsa as gd

    real = gd.attn_inputs

    def unrotated(spec, p, u, positions):
        out = list(real(spec, p, u, positions))
        out[2] = real(spec, p, u, jnp.zeros_like(positions))[2]
        return tuple(out)

    _programs_under(served, lambda: mock.patch.object(
        gd, "attn_inputs", unrotated))


def _bias_added_to_the_weight(served):
    """The router's weights are taken from score + bias, as its choice
    is, not from the score alone."""
    import jax
    import jax.numpy as jnp

    from ddp_tpu.ops import moe

    real = moe.route

    def biased(logits, top_k, normalize=True, *, scoring="softmax",
               bias=None, scale=1.0):
        idx, _ = real(logits, top_k, normalize, scoring=scoring, bias=bias,
                      scale=scale)
        # a trained bias is of the order of the scores it balances
        p = jax.nn.sigmoid(logits.astype(jnp.float32)) + 40.0 * bias
        w = jnp.take_along_axis(p, idx, axis=-1)
        if normalize:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return idx, w * scale

    _programs_under(served, lambda: mock.patch.object(moe, "route", biased))


BROKEN = {
    "indexer_skipped": (_indexer_skipped, "selection_overlap_shortfall"),
    "stale_indexer_rows_of_a_reused_lane": (
        _stale_indexer_rows_of_a_reused_lane, "selection_overlap_shortfall"),
    "rope_key_unrotated_in_the_cache": (
        _rope_key_unrotated_in_the_cache, "served_logit_gap"),
    "bias_added_to_the_weight": (
        _bias_added_to_the_weight, "served_logit_gap"),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_broken_timed_path_is_not_correct(glm_copy, how):
    """What ``correct`` reads is what the timed path produced, in lanes
    others used before, through several chunks and a padded last
    bucket: each way such a lane can go wrong is caught, the selection's
    by the overlap and the arithmetic's by the logits."""
    break_path, caught_by = BROKEN[how]
    rc, run, _ = run_cell(glm_copy, TINY, seed=SEED, seconds=1.5,
                          break_path=break_path)
    assert rc == 0 and not run.correct
    assert caught_by in {c.name for c in run.checks if not c.ok}, [
        (c.name, c.value, c.limit) for c in run.checks]
