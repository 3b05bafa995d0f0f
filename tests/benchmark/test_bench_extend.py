"""Driven by data, and open to additions: a configuration of another
architecture (HF-style keys, a kind and a driver of its own, its
reference, traffic and generator), a cell and a per-layer metric are
each added as NEW files and NEW entries — no edit to a file that is
there, but for the cell's name appended to the ``workloads`` of the
end-to-end metric it reports — and the harness resolves them, the whole
contract of the manifest passes on the copy, and each way of dropping,
loosening or cutting what the benchmark has fails it."""

import contextlib
import importlib
import json
import os

import pytest

import bench_contract as bc
from bench_helpers import _load, _write
from benchmarks.harness import manifest as mf

CONFIG = "toy-moe"
CELL = "toy-moe-serve"
METRIC = "toy_expert_load_pct"
SERVE_RATE = bc.SERVE_RATE

# What a public ``config.json`` of another family looks like: its own
# key names, a count reduced, every width as published.
TOY_CONFIG = {
    "kind": "toy_moe",
    "source": "a paper",
    "deployment": "one stage of a pipeline: 4 of 12 layers, whole",
    "model_type": "toy_moe",
    "vocab_size": 4096,
    "max_position_embeddings": 512,
    "hidden_size": 64,
    "intermediate_size": 256,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 4,
    "num_attention_heads": 8,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_experts": 16,
    "num_experts_per_tok": 4,
    "norm_topk_prob": True,
    "rms_norm_eps": 1e-06,
    "rope_theta": 1000000,
    "tie_word_embeddings": False,
    "published": {
        "vocab_size": 4096, "max_position_embeddings": 512,
        "hidden_size": 64, "intermediate_size": 256,
        "moe_intermediate_size": 32, "num_hidden_layers": 12,
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "head_dim": 16, "num_experts": 16, "num_experts_per_tok": 4,
    },
    "reduced": ["num_hidden_layers"],
    "assumed": {"block_length": "4, the family's convention"},
    "precision": {"weights": "bfloat16", "control": "float8"},
    "correct": {"limits": {"served_logit_gap": 0.1}},
}


def _put(path: str, text: str) -> None:
    with open(path, "x") as f:  # a NEW file: never over one that is there
        f.write(text)


def add_another_architecture(root: str) -> None:
    """What a PR of another kind brings: files of its own and entries."""
    b = os.path.join(root, "benchmarks")
    _put(os.path.join(b, "configs", CONFIG + ".json"),
         json.dumps(TOY_CONFIG, indent=1))
    _put(os.path.join(b, "traffic", "toy-mix.json"),
         json.dumps({"generator": "toy_gen", "n": 3}))
    _put(os.path.join(b, "generators", "toy_gen.py"),
         "def generate(params, *, seed):\n"
         "    return [seed + i for i in range(params['n'])]\n")
    _put(os.path.join(b, "reference", "toy_moe_ref.py"),
         "def forward(config, tokens):\n"
         "    return [t % config['vocab_size'] for t in tokens]\n")
    _put(os.path.join(b, "drivers", "toy_moe.py"),
         "import os\n\n"
         "from benchmarks.harness.manifest import load_module\n\n"
         "HERE = os.path.dirname(os.path.abspath(__file__))\n\n\n"
         "def run(cell, args, ctx):\n"
         "    ref = load_module(os.path.join(HERE, os.pardir, 'reference',\n"
         "                                   'toy_moe_ref.py'), 'toy_ref')\n"
         "    tokens = cell.generator().generate(cell.traffic, "
         "seed=args.seed)\n"
         "    return ref.forward(cell.config, tokens)\n")
    _put(os.path.join(b, "layer_metrics", METRIC + ".py"),
         f"NAME = '{METRIC}'\nUNIT = '%'\nLAYER = 'Expert layer'\n"
         f"MOVES = '{SERVE_RATE}'\nSOURCE = 'program_counter'\n\n\n"
         "def read(run):\n    return run.counters.get('expert_load')\n")

    m = bc.manifest_of(root)
    m["configs"].append({
        "name": CONFIG, "source": TOY_CONFIG["source"],
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "toy-mix", "chips": 1,
        "why": "test"})
    # the one entry that is not new: the end-to-end metric it reports
    rate = next(e for e in m["end_to_end"] if e["name"] == SERVE_RATE)
    rate["workloads"].append(CELL)
    m["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Expert layer",
        "moves": SERVE_RATE, "workloads": [CELL]})
    _write(os.path.join(root, "BENCHMARK.json"), m)


def _digest(root: str) -> dict:
    return {
        os.path.join(d, f): open(os.path.join(d, f), "rb").read()
        for d, _, fs in os.walk(root) for f in fs
    }


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """(root, digest of every file that was there) of a copy of the
    benchmark to which another architecture has been added."""
    root = bc.copy_benchmark(str(tmp_path_factory.mktemp("extended")))
    before = _digest(root)
    add_another_architecture(root)
    return root, before


def test_add_config_traffic_cell_and_metric_without_an_edit(extended):
    root, before = extended
    # nothing that was there changed, but for the manifest's new entries
    for p, content in before.items():
        if p != os.path.join(root, "BENCHMARK.json"):
            assert open(p, "rb").read() == content, p
    was = json.loads(before[os.path.join(root, "BENCHMARK.json")])
    now = bc.manifest_of(root)
    for section in ("configs", "workloads", "per_layer"):
        assert now[section][:len(was[section])] == was[section]
    rate = next(e for e in now["end_to_end"] if e["name"] == SERVE_RATE)
    rate["workloads"].remove(CELL)
    assert now["end_to_end"] == was["end_to_end"]
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} == {
        k: was[k] for k in ("command", "paths", "run_seconds")}

    cell = mf.load_cell(CELL, root)
    assert cell.config["hidden_size"] == 64 and cell.traffic["n"] == 3
    assert {x["name"] for x in cell.end_to_end()} == {SERVE_RATE, "setup_s"}
    assert list(cell.layer_readers()) == [METRIC]

    class Args:
        seed = 4100

    assert cell.driver().run(cell, Args, None) == [4, 5, 6]

    class Run:
        counters = {"expert_load": 7}

    assert cell.layer_readers()[METRIC].read(Run) == 7
    # and the cells that were there are untouched by the newcomer
    old = mf.load_cell("cgpt1.3b-serve-chat-sat", root)
    assert METRIC not in old.layer_readers()
    assert [x["name"] for x in old.end_to_end()] == [SERVE_RATE, "setup_s"]


@pytest.mark.parametrize("check", bc.CHECKS, ids=bc.check_id)
def test_the_extended_copy_keeps_the_contract(extended, check):
    check(extended[0])


def test_a_metric_and_a_cell_of_its_own_pass_too(tmp_path):
    """Beside the three end-to-end metrics a newcomer may bring its
    own, with a bound of its own, and a traffic mix for a cell of a
    configuration that is there."""
    root = bc.copy_benchmark(str(tmp_path))
    add_another_architecture(root)
    m = bc.manifest_of(root)
    m["end_to_end"].append({
        "name": "toy_blocks_per_s", "unit": "blocks/s", "better": "higher",
        "bound": 0.02, "source": "host_clock", "workloads": [CELL]})
    _write(os.path.join(root, "benchmarks", "traffic", "chat-again.json"),
           _load(os.path.join(root, "benchmarks", "traffic",
                              "chat-saturated.json")))
    m["workloads"].append({
        "name": "cgpt1.3b-serve-chat-again",
        "config": "cerebras-gpt-1.3b-serve", "traffic": "chat-again",
        "chips": 1, "why": "test"})
    _list_beside(m, "cgpt1.3b-serve-chat-again", "cgpt1.3b-serve-chat-sat")
    _write(os.path.join(root, "BENCHMARK.json"), m)
    assert bc.failures(root) == {}


# ---- what must fail -------------------------------------------------------


@contextlib.contextmanager
def _editing(root, config=CONFIG):
    """A configuration's file, to change in place."""
    path = os.path.join(root, "benchmarks", "configs", config + ".json")
    cfg = _load(path)
    yield cfg
    _write(path, cfg)


def _toy(root, **changes):
    """Rewrite the newcomer's configuration file (its own, so it may)."""
    with _editing(root) as cfg:
        cfg.update(changes)


def _reduce(root, m, key, value):
    """List one more key under ``reduced``, in the file and the entry."""
    _toy(root, **{key: value, "reduced": ["num_hidden_layers", key]})
    m["configs"][-1]["reduced"] = ["num_hidden_layers", key]


def _list_beside(m, new, old=CELL):
    """``new`` wherever a metric lists the cell ``old``."""
    for e in m["end_to_end"] + m["per_layer"]:
        if old in e.get("workloads", ()):
            e["workloads"].append(new)


def reduced_width(root, m):
    _reduce(root, m, "hidden_size", 32)


def reduced_experts_per_token(root, m):
    _reduce(root, m, "num_experts_per_tok", 2)


def width_differs_from_published(root, m):
    _toy(root, moe_intermediate_size=16)


def width_cut_with_its_published_value(root, m):
    with _editing(root, "cerebras-gpt-1.3b-serve") as cfg:
        cfg["n_inner"] = cfg["published"]["n_inner"] = 4096


def train_config_narrower_than_the_file_says(root, m):
    with _editing(root, "cerebras-gpt-1.3b-train") as cfg:
        cfg["train_config"]["model_dim"] = 1024


def published_left_out(root, m):
    with _editing(root) as cfg:
        del cfg["published"]


def published_lacks_a_width_the_file_sets(root, m):
    with _editing(root) as cfg:
        del cfg["published"]["head_dim"]
        cfg["head_dim"] = 8


def published_names_no_model_width(root, m):
    with _editing(root) as cfg:
        del cfg["published"]["hidden_size"], cfg["hidden_size"]


def three_layers_left(root, m):
    _toy(root, num_hidden_layers=3)


def reduced_to_more_than_published(root, m):
    _toy(root, num_hidden_layers=16)


def four_experts_left(root, m):
    _reduce(root, m, "num_experts", 4)


def a_sixteenth_of_the_vocabulary(root, m):
    _reduce(root, m, "vocab_size", 256)


def reduced_in_the_file_alone(root, m):
    _toy(root, num_experts=8, reduced=["num_hidden_layers", "num_experts"])


def metric_removed(root, m):
    m["per_layer"] = [e for e in m["per_layer"]
                      if e["name"] != "serve_step_ms_p50"]


def metrics_reordered(root, m):
    m["per_layer"][0], m["per_layer"][1] = m["per_layer"][1], m["per_layer"][0]


def cell_taken_out_of_a_metrics_workloads(root, m):
    e = next(e for e in m["per_layer"] if e["name"] == "train_mfu_pct")
    e["workloads"].remove("cgpt1.3b-train-ddp4")


def cell_taken_out_of_its_end_to_end_metric(root, m):
    m["end_to_end"][0]["workloads"].remove("cgpt1.3b-train-ddp4")


def cell_removed(root, m):
    m["workloads"] = [w for w in m["workloads"]
                      if w["name"] != "cgpt1.3b-train-ddp4"]
    for e in m["end_to_end"] + m["per_layer"]:
        if "cgpt1.3b-train-ddp4" in e.get("workloads", ()):
            e["workloads"].remove("cgpt1.3b-train-ddp4")
    m["per_layer"] = [e for e in m["per_layer"] if e["workloads"]]


def cell_given_other_traffic(root, m):
    m["workloads"][1]["traffic"] = "toy-mix"


def end_to_end_reordered(root, m):
    m["end_to_end"].insert(0, m["end_to_end"].pop(2))


def kind_without_a_driver(root, m):
    _toy(root, kind="nobody")


def driver_without_run(root, m):
    _put(os.path.join(root, "benchmarks", "drivers", "idle.py"),
         "def walk(cell, args, ctx):\n    return None\n")
    _toy(root, kind="idle")


def _clones(root, m, n):
    src = os.path.join(root, "benchmarks", "traffic", "toy-mix.json")
    for i in range(n):
        _write(os.path.join(root, "benchmarks", "traffic",
                            f"toy-mix-{i}.json"), _load(src))
        name = f"{CELL}-{i}"
        m["workloads"].append({
            "name": name, "config": CONFIG, "traffic": f"toy-mix-{i}",
            "chips": 1, "why": "test"})
        _list_beside(m, name)


def a_25th_cell(root, m):
    _clones(root, m, 25 - len(m["workloads"]))


def second_four_chip_cell_among_four(root, m):
    assert len(m["workloads"]) == 4
    m["workloads"][-1]["chips"] = 4


def one_pair_names_two_cells(root, m):
    m["workloads"].append(dict(m["workloads"][-1], name=CELL + "-twin"))
    _list_beside(m, CELL + "-twin")


def metric_lists_a_cell_that_is_not_there(root, m):
    m["per_layer"][-1]["workloads"].append("nowhere")


def metric_without_a_reader(root, m):
    m["per_layer"].append(dict(m["per_layer"][-1], name="toy_unread_pct"))


def bound_over_a_tenth(root, m):
    m["end_to_end"][1]["bound"] = 0.2


def bound_loosened(root, m):
    m["end_to_end"][1]["bound"] = 0.02


def longer_runs(root, m):
    m["run_seconds"] = 45


# case -> the check that has to refuse it
MUST_FAIL = [
    (reduced_width, "configs_files_and_reduced"),
    (reduced_experts_per_token, "configs_files_and_reduced"),
    (width_differs_from_published, "published_widths_are_never_cut"),
    (width_cut_with_its_published_value, "published_widths_are_never_cut"),
    (train_config_narrower_than_the_file_says,
     "published_widths_are_never_cut"),
    (published_left_out, "published_widths_are_never_cut"),
    (published_lacks_a_width_the_file_sets,
     "published_widths_are_never_cut"),
    (published_names_no_model_width, "published_widths_are_never_cut"),
    (three_layers_left, "published_widths_are_never_cut"),
    (reduced_to_more_than_published, "published_widths_are_never_cut"),
    (four_experts_left, "published_widths_are_never_cut"),
    (a_sixteenth_of_the_vocabulary, "published_widths_are_never_cut"),
    (reduced_in_the_file_alone, "configs_files_and_reduced"),
    (metric_removed, "the_benchmark_lost_nothing_it_had"),
    (metrics_reordered, "the_benchmark_lost_nothing_it_had"),
    (cell_taken_out_of_a_metrics_workloads,
     "the_benchmark_lost_nothing_it_had"),
    (cell_taken_out_of_its_end_to_end_metric,
     "the_benchmark_lost_nothing_it_had"),
    (cell_removed, "the_benchmark_lost_nothing_it_had"),
    (cell_given_other_traffic, "the_benchmark_lost_nothing_it_had"),
    (end_to_end_reordered, "the_benchmark_lost_nothing_it_had"),
    (kind_without_a_driver, "configs_files_and_reduced"),
    (driver_without_run, "configs_files_and_reduced"),
    (a_25th_cell, "counts_and_the_four_chip_quarter"),
    (second_four_chip_cell_among_four, "counts_and_the_four_chip_quarter"),
    (one_pair_names_two_cells, "names_are_unique_and_well_formed"),
    (metric_lists_a_cell_that_is_not_there,
     "every_cell_resolves_and_reports_enough"),
    (metric_without_a_reader, "each_per_layer_metric_is_a_reader_of_its_own"),
    (bound_over_a_tenth, "units_sources_and_directions"),
    (bound_loosened, "the_benchmark_lost_nothing_it_had"),
    (longer_runs, "the_benchmark_lost_nothing_it_had"),
]


@pytest.mark.parametrize("case,refused_by", MUST_FAIL,
                         ids=[c.__name__ for c, _ in MUST_FAIL])
def test_what_the_contract_refuses(tmp_path, case, refused_by):
    root = bc.copy_benchmark(str(tmp_path))
    add_another_architecture(root)
    m = bc.manifest_of(root)
    case(root, m)
    _write(os.path.join(root, "BENCHMARK.json"), m)
    failed = bc.failures(root)
    assert refused_by in failed, failed


# ---- what benchmarks/ADDING.md promises a newcomer ------------------------


def test_a_new_driver_may_import_the_serve_drivers_load_and_sampler():
    """``benchmarks`` is a namespace package: a driver of a new kind
    imports the load generator and the block sampler, it does not copy
    them; these are the names ``run.py``, ``sweep.py`` and
    ``check_controls.py`` call on a driver."""
    serve = importlib.import_module("benchmarks.drivers.serve")
    for name in ("run", "control", "Served", "Load", "sample_blocks",
                 "tpot_engine_ms", "window_quotient", "pick_checked"):
        assert callable(getattr(serve, name)), name
    for name in ("tokens_total", "gauges", "stop_and_free"):
        assert callable(getattr(serve.Served, name)), name
    train = importlib.import_module("benchmarks.drivers.train")
    for name in ("run", "control", "readings"):
        assert callable(getattr(train, name)), name
    assert not os.path.exists(os.path.join(mf.BENCH_DIR, "__init__.py"))
    assert os.path.isfile(os.path.join(mf.BENCH_DIR, "ADDING.md"))
