"""The manifest's contract as functions of a root, so that the same
rules run on the checkout and on a copy that a later PR's additions
have been made to.

What the benchmark HAS is guarded to the letter (``ACCEPTED_*``: it may
gain beside them and lose none of them); what is ADDED is checked by
rule. Each ``check_*`` takes the root of a checkout and asserts;
``CHECKS`` lists them and ``failures`` runs them all.
"""

from __future__ import annotations

import os
import re
import shutil

from benchmarks.harness import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# ---- what the benchmark has today: lost none, gained any -----------------

TRAIN_CELLS = ["cgpt1.3b-train-1chip", "cgpt1.3b-train-ddp4"]
SERVE_CELLS = ["cgpt1.3b-serve-chat-sat"]
TRAIN_RATE, SERVE_RATE = "train_tokens_per_s_per_chip", "serve_tokens_per_s"

ACCEPTED = [
    "train_host_ms_per_step", "train_stall_pct",
    "train_block_median_tokens_per_s_per_chip", "train_dev_ms_per_step",
    "train_mfu_pct", "train_attn_roofline_pct",
    "train_allreduce_exposed_ms_per_step", "serve_occupancy_pct",
    "serve_block_median_tokens_per_s", "serve_step_ms_p50",
    "serve_tpot_engine_p50_ms", "serve_decode_dev_ms_per_step",
    "serve_decode_attn_pct",
]
SERVE_NEW = [
    "serve_host_ms_per_step", "serve_submit_lock_wait_ms_p50",
    "serve_result_pickup_ms_p50", "serve_prefill_dev_ms_per_chunk",
    "serve_idle_attributed_pct",
]
TRAIN_NEW = [
    "train_loader_ms_per_step", "train_dispatch_ms_per_step",
    "train_host_max_span_ms", "train_attn_fwd_ms_per_step",
    "train_attn_bwd_ms_per_step",
]
ACCEPTED_PER_LAYER = ACCEPTED + SERVE_NEW + TRAIN_NEW


def accepted_cells_of(metric: str) -> list[str]:
    """The cells an accepted per-layer metric lists today."""
    if metric == "train_allreduce_exposed_ms_per_step":
        return ["cgpt1.3b-train-ddp4"]
    return TRAIN_CELLS if metric.startswith("train_") else SERVE_CELLS


# name -> (config, traffic, chips)
ACCEPTED_CELLS = {
    "cgpt1.3b-train-1chip": ("cerebras-gpt-1.3b-train", "train-steady", 1),
    "cgpt1.3b-serve-chat-sat": ("cerebras-gpt-1.3b-serve",
                                "chat-saturated", 1),
    "cgpt1.3b-train-ddp4": ("cerebras-gpt-1.3b-train",
                            "train-steady-dp4", 4),
}
ACCEPTED_CONFIGS = ["cerebras-gpt-1.3b-train", "cerebras-gpt-1.3b-serve"]
ACCEPTED_END_TO_END = [TRAIN_RATE, SERVE_RATE, "setup_s"]
# A bound may come down; only a ``benchmark`` PR, which edits this table
# with its reasons in PERF.md, lets one up. No PR changes the run length.
ACCEPTED_BOUNDS = {TRAIN_RATE: 0.01, SERVE_RATE: 0.01, "setup_s": 0.1}
ACCEPTED_RUN_SECONDS = 35

CEREBRAS = "https://huggingface.co/cerebras/Cerebras-GPT-1.3B"
# (n_embd, n_head, n_inner, vocab_size, n_positions), and n_layer
CEREBRAS_WIDTHS, CEREBRAS_DEPTH = (2048, 16, 8192, 50257, 2048), 24

# ---- what a key of a model's config.json means ---------------------------

COUNTS = ("layers", "experts", "heads", "vocabulary")
# The guide's floors (model-configs, section 4) for a reduced count whose
# published value is above the floor; a smaller group is cut to 1 at least.
FLOORS = {"layers": 4, "experts": 8, "heads": 1}
MODEL_WIDTH_KEYS = ("hidden_size", "n_embd", "d_model", "model_dim", "dim")

_NOT_A_SHAPE = {"pdrop", "eps", "epsilon", "theta", "offset", "period",
                "freq", "pattern", "every", "interval", "idx", "id", "ids"}
# A size of something: a width, a head size, a latent rank, a window, a
# state or convolution size, an expansion, how many experts or groups a
# token is routed over.
_WIDTH = {"dim", "dims", "rank", "size", "sizes", "intermediate", "embd",
          "inner", "width", "d", "channels", "window", "windows", "state",
          "conv", "kernel", "ssm", "expand", "expansion", "per", "tok",
          "topk", "top", "active", "group", "groups", "ngroups"}
_COUNT_WORDS = {"n", "num", "number"}


def key_meaning(key: str) -> str | None:
    """What a top-level key of a public ``config.json`` counts or
    measures: ``layers``, ``experts``, ``heads`` or ``vocabulary`` (a
    count: a chip may hold its share, so it may be in ``reduced``),
    ``width`` (a size: never cut), or None where the rule cannot place
    it (then it may not be in ``reduced`` either)."""
    t = key.lower().split("_")
    if set(t) & _NOT_A_SHAPE:
        return None
    if "vocab" in t or "vocabulary" in t:
        # rows of the embedding and the head: ``vocab_size`` and its
        # prefixed forms, not a ratio or a base of it
        rest = set(t[:-1]) - {"vocab", "vocabulary"}
        if t[-1] == "size" and not rest & _WIDTH:
            return "vocabulary"
    if set(t) & _WIDTH or any(x.endswith("dim") for x in t):
        return "width"
    counted = bool(set(t) & _COUNT_WORDS)
    if t[-1] in ("layer", "layers") and counted:
        return "layers"
    if ({"expert", "experts"} & set(t) and counted
            and not {"shared", "share"} & set(t)):
        return "experts"  # routed ones: a shared expert is on every chip
    if t[-1] == "heads" or (t[-1] == "head" and counted):
        return "heads"
    return None


def may_be_reduced(key: str) -> bool:
    return key_meaning(key) in COUNTS


def floor_of(key: str, published: float) -> float:
    """The least a reduced count may be."""
    meaning = key_meaning(key)
    if meaning == "vocabulary":
        return published / 8
    floor = FLOORS[meaning]
    return floor if published > floor else 1


# ---- copies ---------------------------------------------------------------


def copy_benchmark(dst: str, src: str = mf.ROOT) -> str:
    """``BENCHMARK.json`` and every directory under its ``paths``: what
    a check of the manifest reads. Returns ``dst``."""
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    for p in manifest_of(src)["paths"]:
        shutil.copytree(
            os.path.join(src, p), os.path.join(dst, p),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    return dst


def manifest_of(root: str) -> dict:
    return mf.load_json(os.path.join(root, "BENCHMARK.json"))


def _metrics(m: dict) -> list[dict]:
    return m["end_to_end"] + m["per_layer"]


def _config_file(root: str, entry: dict) -> dict:
    return mf.load_json(os.path.join(root, entry["file"]))


# ---- the checks -----------------------------------------------------------


def check_top_level_keys_and_size(root):
    m = manifest_of(root)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51


def check_run_seconds_fits_a_full_check_of_24_cells(root):
    m = manifest_of(root)
    runs = 2 + 14 * 24
    total = runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def check_command_and_paths(root):
    m = manifest_of(root)
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(root, p)), p
    assert len(m["command"]) <= 32
    script = m["command"][1]
    assert any(script.startswith(p + "/") for p in m["paths"])
    assert os.path.isfile(os.path.join(root, script))


def check_names_are_unique_and_well_formed(root):
    m = manifest_of(root)
    for section in ("configs", "workloads"):
        names = [e["name"] for e in m[section]]
        assert len(set(names)) == len(names), names
        assert all(NAME.match(n) for n in names), names
    names = [e["name"] for e in _metrics(m)]
    assert len(set(names)) == len(names), names
    assert all(NAME.match(n) for n in names), names
    for w in m["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs), "a pair of configuration and " \
        "traffic names one cell"


def check_units_sources_and_directions(root):
    m = manifest_of(root)
    for e in _metrics(m):
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.1
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    assert any(e["name"] == "setup_s" and "workloads" not in e
               for e in m["end_to_end"])


def check_entries_have_just_the_keys_shown(root):
    m = manifest_of(root)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "bound" not in e


def check_one_line_texts(root):
    m = manifest_of(root)
    texts = [w["why"] for w in m["workloads"]]
    texts += [c["why"] for c in m["configs"]]
    texts += [c["source"] for c in m["configs"]]
    texts += [e["layer"] for e in m["per_layer"]] + m["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t


def check_counts_and_the_four_chip_quarter(root):
    m = manifest_of(root)
    assert 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["configs"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16
    assert 1 <= len(m["per_layer"]) <= 128
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4), four


def check_configs_files_and_reduced(root):
    m = manifest_of(root)
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert c["name"] in used, f"{c['name']}: no cell uses it"
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        cfg = _config_file(root, c)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert may_be_reduced(key), (
                f"{c['name']}: {key!r} reads as {key_meaning(key)}: only "
                f"a count of {', '.join(COUNTS)} may be reduced")
        # ``kind`` names a driver: a file of the benchmark with ``run``
        kind = cfg.get("kind")
        assert isinstance(kind, str) and NAME.match(kind), kind
        path = os.path.join(root, "benchmarks", "drivers", kind + ".py")
        assert os.path.isfile(path), f"{c['name']}: no driver {path}"
        driver = mf.load_module(path, f"contract_driver_{kind}")
        assert callable(getattr(driver, "run", None)), path


def check_published_widths_are_never_cut(root):
    m = manifest_of(root)
    for c in m["configs"]:
        cfg = _config_file(root, c)
        pub = cfg.get("published")
        who = c["name"]
        assert isinstance(pub, dict) and pub, f"{who}: no `published`"
        assert any(k in pub for k in MODEL_WIDTH_KEYS), (
            f"{who}: `published` names no model width")
        shapes = [k for k, v in cfg.items() if key_meaning(k)
                  and isinstance(v, (int, float)) and not isinstance(v, bool)]
        lacking = [k for k in shapes + c["reduced"] if k not in pub]
        assert not lacking, f"{who}: `published` lacks {lacking}"
        for key, said in pub.items():
            assert key in cfg, f"{who}: publishes {key}, sets none"
            if key not in c["reduced"]:
                assert cfg[key] == said, (
                    f"{who}: {key} is {cfg[key]}, published {said}, and "
                    f"is not in `reduced`")
                continue
            assert may_be_reduced(key), f"{who}: {key} is no count"
            assert floor_of(key, said) <= cfg[key] < said, (
                f"{who}: reduced {key} is {cfg[key]} of {said}; the "
                f"least is {floor_of(key, said)}")
        if c["source"] == CEREBRAS:  # to the letter, as before the rule
            widths = (cfg["n_embd"], cfg["n_head"], cfg["n_inner"],
                      cfg["vocab_size"], cfg["n_positions"])
            assert widths == CEREBRAS_WIDTHS, (who, widths)
            assert (cfg["n_layer"] == CEREBRAS_DEPTH
                    or "n_layer" in c["reduced"]), who
            assert pub["n_layer"] == CEREBRAS_DEPTH, who
            tc = cfg.get("train_config")
            if tc:  # what the train driver builds the program from
                built = (tc["model_dim"], tc["num_heads"], tc["vocab_size"],
                         tc["seq_len"], tc["model_depth"])
                assert built == (2048, 16, 50257, 2048, cfg["n_layer"]), (
                    who, built)


def check_every_cell_resolves_and_reports_enough(root):
    m = manifest_of(root)
    e2e = {e["name"] for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        cell = mf.load_cell(w["name"], root)
        assert os.path.isfile(os.path.join(
            cell.bench_dir, "drivers", cell.config["kind"] + ".py"))
        assert os.path.isfile(os.path.join(
            cell.bench_dir, "generators",
            cell.traffic["generator"] + ".py"))
        names = [x["name"] for x in cell.end_to_end()]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert len(cell.per_layer()) >= 1, w["name"]
        for x in cell.per_layer():
            assert x["moves"] in names  # the cell reports what it moves
    for e in m["per_layer"]:
        assert e["moves"] in e2e
    for e in _metrics(m):
        unknown = set(e.get("workloads", ())) - cells
        assert not unknown, (e["name"], unknown)


def check_each_per_layer_metric_is_a_reader_of_its_own(root):
    m = manifest_of(root)
    d = os.path.join(root, "benchmarks", "layer_metrics")
    for e in m["per_layer"]:
        mod = mf.load_module(os.path.join(d, e["name"] + ".py"),
                             "t_" + e["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            e["name"], e["unit"], e["layer"], e["moves"], e["source"])
        assert callable(mod.read)


def check_roofline_and_mfu_shares_are_percentages(root):
    for e in _metrics(manifest_of(root)):
        if e["name"].endswith("_roofline_pct") or "mfu" in e["name"]:
            assert e["unit"] == "%"


def check_harness_holds_no_cell_configuration_or_metric_name(root):
    m = manifest_of(root)
    names = {e["name"] for s in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in m[s]}
    names -= {"setup_s"}
    for rel in ("run.py", "sweep.py", "harness/manifest.py",
                "harness/window.py", "harness/trace.py",
                "harness/result.py", "harness/device.py"):
        with open(os.path.join(root, "benchmarks", rel)) as f:
            text = f.read()
        held = [n for n in names if n in text]
        assert not held, (rel, held)


def check_files_under_paths_are_named_from_allowed_characters(root):
    for p in manifest_of(root)["paths"]:
        for d, _, fs in os.walk(os.path.join(root, p)):
            if "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), root)
                assert PATH.match(rel), rel


def check_the_benchmark_lost_nothing_it_had(root):
    """Today's 23 per-layer metrics in their order, each with the cells
    it lists and the metric it moves; today's cells, configurations and
    first three end-to-end metrics with bounds no looser, the run
    length. Anything after or beside them is a gain, and allowed."""
    m = manifest_of(root)
    names = [e["name"] for e in m["per_layer"]]
    kept = [n for n in names if n in set(ACCEPTED_PER_LAYER)]
    assert kept == ACCEPTED_PER_LAYER, (
        sorted(set(ACCEPTED_PER_LAYER) - set(kept)) or "order changed")
    by = {e["name"]: e for e in m["per_layer"]}
    for n in ACCEPTED_PER_LAYER:
        assert "workloads" in by[n], n
        gone = set(accepted_cells_of(n)) - set(by[n]["workloads"])
        assert not gone, (n, gone)
        assert by[n]["moves"] == (
            TRAIN_RATE if n.startswith("train_") else SERVE_RATE), n
    cells = {w["name"]: (w["config"], w["traffic"], w["chips"])
             for w in m["workloads"]}
    for name, was in ACCEPTED_CELLS.items():
        assert cells.get(name) == was, (name, cells.get(name))
    configs = {c["name"]: c for c in m["configs"]}
    for name in ACCEPTED_CONFIGS:
        assert name in configs, name
        assert configs[name]["source"] == CEREBRAS
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert list(e2e)[:3] == ACCEPTED_END_TO_END
    for rate, had in ((TRAIN_RATE, TRAIN_CELLS), (SERVE_RATE, SERVE_CELLS)):
        assert set(had) <= set(e2e[rate]["workloads"]), rate
    assert "workloads" not in e2e["setup_s"]
    for name, bound in ACCEPTED_BOUNDS.items():
        assert e2e[name]["bound"] <= bound, (name, e2e[name]["bound"])
    assert m["run_seconds"] == ACCEPTED_RUN_SECONDS


CHECKS = [
    check_top_level_keys_and_size,
    check_run_seconds_fits_a_full_check_of_24_cells,
    check_command_and_paths,
    check_names_are_unique_and_well_formed,
    check_units_sources_and_directions,
    check_entries_have_just_the_keys_shown,
    check_one_line_texts,
    check_counts_and_the_four_chip_quarter,
    check_configs_files_and_reduced,
    check_published_widths_are_never_cut,
    check_every_cell_resolves_and_reports_enough,
    check_each_per_layer_metric_is_a_reader_of_its_own,
    check_roofline_and_mfu_shares_are_percentages,
    check_harness_holds_no_cell_configuration_or_metric_name,
    check_files_under_paths_are_named_from_allowed_characters,
    check_the_benchmark_lost_nothing_it_had,
]


def check_id(check) -> str:
    return check.__name__[len("check_"):]


def failures(root: str) -> dict[str, str]:
    """Every check on one root: the name of each that fails (without
    its ``check_``) and what it said."""
    out = {}
    for check in CHECKS:
        try:
            check(root)
        except (AssertionError, KeyError, OSError) as e:
            # a key or a file that is not there fails the contract too
            out[check_id(check)] = f"{type(e).__name__}: {e}"
    return out
