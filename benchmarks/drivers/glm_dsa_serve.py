"""Driver for ``kind: glm_dsa_serve`` cells: GLM-5's block
(``ddp_tpu/models/glm_dsa.py``: latent attention over the keys a
learned indexer selects, leading dense layers, then sigmoid-routed
experts of which this chip holds a share beside a shared one) served
one token a step by the program's ``LMServer`` over a ``ServeEngine``
and entered by ``submit_and_wait``, exactly as ``drivers/serve.py``
enters the GPT-2 block. Load (the SambaY driver's: the time-zero burst
queued in the generator's order before the engine loop starts), the
block sampler, the choice of checked requests and the window arithmetic
are those drivers', imported; the model, its weights, its reference and
its counters are this one's.

The rate is the accepted whole-window quotient (``window_quotient``:
every token of the window over its wall time), reported as
``serve_tokens_per_s`` under the bound that metric has.

``correct`` is decided on what the timed path produced. Every request
asks the engine to keep what the decode step of its LAST token selected
in each layer (``record_selection``, as the block-diffusion cell asks
for its blocks). After the window, the program's state freed,
``checked_requests`` finished requests (the longest among them) go
through the plain reference (``reference/glm_dsa_ref.py``) ONCE, layer
by layer with each layer's float32 copy of the stored weights remade
from the seed: the whole sequence (prompt and served tokens) from
scratch, no cache, no absorption, the per-query selection an explicit
mask, the same share of the experts and slice of the vocabulary.

- ``served_logit_gap``: the widest gap by which a served token's
  reference logit lies below the reference's best at that position,
  over every generated position. Those requests were prefilled in two
  to eight chunks (expanded attention under a per-query mask) with a
  padded last bucket, in lanes other requests filled before them, while
  the other lanes decoded (selected rows gathered, the up-projections
  absorbed): a rope key stored unrotated, a bias added to a router
  weight, an indexer row never written shows here.
- ``selection_overlap``: over the checked requests and the layers, the
  LEAST share of the rows the program selected for a request's last
  step that the reference selects for that position too. A program
  that takes a lane's last ``index_topk`` rows, every row, or rows a
  former request left in the lane's indexer cache falls below the
  floor; a request for which the program recorded nothing counts 0.

The control (``check_controls.py``) puts the reference computed with
float8 matmul operands in the program's place at the same positions; it
has to come out NOT correct.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import time

import numpy as np

from benchmarks.drivers import sambay_serve
from benchmarks.drivers.serve import (
    pick_checked,
    sample_blocks,
    tpot_engine_ms,
    tpot_ms,
)
from benchmarks.harness import glm_dsa_weights
from benchmarks.harness import trace as btrace
from benchmarks.harness.result import Check, Run, emit
from benchmarks.harness.window import Block, describe, window_quotient

__all__ = ["Load", "Served", "control", "readings", "run", "sample_blocks",
           "tpot_engine_ms", "window_quotient"]

BLOCK = "glm_dsa"
Load = sambay_serve.Load


def model_sizes(config: dict) -> dict:
    """The published keys under the names the harness's weight and
    operation counts take. ``n_routed_experts`` is the file's REDUCED
    key: the experts held here; the router keeps the published count."""
    return dict(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        depth=int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        index_n_heads=int(config["index_n_heads"]),
        index_head_dim=int(config["index_head_dim"]),
        index_topk=int(config["index_topk"]),
        first_k_dense_replace=int(config["first_k_dense_replace"]),
        router_outputs=int(config["published"]["n_routed_experts"]),
        experts_held=int(config["n_routed_experts"]),
        expert_offset=int(config["share"]["first_expert"]),
        n_shared_experts=int(config["n_shared_experts"]),
        moe_top_k=int(config["num_experts_per_tok"]),
        moe_intermediate=int(config["moe_intermediate_size"]),
        mlp_intermediate=int(config["intermediate_size"]),
    )


def ref_cfg(config: dict) -> dict:
    """What the reference reads, under the config's own keys."""
    keys = ("num_hidden_layers", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "index_n_heads", "index_head_dim", "index_topk",
            "first_k_dense_replace", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps")
    return {**{k: config[k] for k in keys},
            "rope_theta": config["rope_parameters"]["rope_theta"],
            "expert_offset": int(config["share"]["first_expert"])}


def lm_spec(config: dict):
    from ddp_tpu.models.lm import LMSpec

    s = model_sizes(config)
    if (config["tie_word_embeddings"] or config["attention_bias"]
            or config["scoring_func"] != "sigmoid"
            or (config["n_group"], config["topk_group"]) != (1, 1)
            or config["moe_layer_freq"] != 1
            or not (config["rope_interleave"]
                    and config["indexer_rope_interleave"])
            or config["rope_parameters"]["rope_type"] != "default"):
        raise ValueError(
            "the driver serves the published model: an untied head, no "
            "attention bias, sigmoid scores with no group limit, every "
            "layer past the dense ones routed, interleaved rotary with "
            "no scaling")
    return LMSpec(
        vocab_size=s["vocab_size"],
        total_len=int(config["engine"]["cache_length"]),
        d_model=s["d_model"], depth=s["depth"], num_heads=s["num_heads"],
        block=BLOCK, q_lora_rank=s["q_lora_rank"],
        kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        index_n_heads=s["index_n_heads"],
        index_head_dim=s["index_head_dim"], index_topk=s["index_topk"],
        first_k_dense_replace=s["first_k_dense_replace"],
        n_routed_experts=s["router_outputs"], num_experts=s["experts_held"],
        expert_offset=s["expert_offset"],
        n_shared_experts=s["n_shared_experts"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        moe_top_k=s["moe_top_k"],
        moe_normalize_gates=bool(config["norm_topk_prob"]),
        moe_intermediate=s["moe_intermediate"],
        mlp_intermediate=s["mlp_intermediate"],
        rms_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
    )


class Served:
    """The server, built the way ``scripts/serve.py`` builds it, over
    the benchmark's seeded weights."""

    def __init__(self, config: dict, seed: int):
        import jax

        from ddp_tpu.serve.engine import ServeEngine
        from ddp_tpu.serve.server import LMServer

        self.spec = lm_spec(config)
        self.sizes = s = model_sizes(config)
        t = time.perf_counter()
        self.params = glm_dsa_weights.make_params(seed, s)
        jax.block_until_ready(self.params)
        self.weights_s = time.perf_counter() - t
        knobs = {k: v for k, v in config["engine"].items()
                 if k != "cache_length"}
        t = time.perf_counter()
        self.engine = ServeEngine(self.spec, self.params, **knobs)
        self.engine_s = time.perf_counter() - t
        t = time.perf_counter()
        self.engine.warmup()
        self.warmup_s = time.perf_counter() - t
        self.slots = self.engine.num_slots
        # started by the ``Load``, once the time-zero burst is queued
        self.server = LMServer(self.engine, port=0)
        self.started = False
        # what each request's last step selected, by its prompt
        self.selected: dict = {}

    def submit(self, body: dict):
        http, payload = self.server.submit_and_wait(
            {**body, "record_selection": True})
        rows = payload.pop("selected_rows", None)
        if rows is not None:
            self.selected[tuple(body["prompt_tokens"])] = rows
        return http, payload

    def start_server(self):
        if not self.started:
            self.server.start()
            self.started = True

    def tokens_total(self) -> int:
        return int(self.engine.tokens_emitted_total)

    def gauges(self) -> dict:
        """Unlocked reads of plain host-side state."""
        e = self.engine
        return {"active": int(e.active),
                "queue_depth": int(e.scheduler.depth)}

    def counts(self) -> dict:
        """The engine's own counters the readers take differences of:
        plain host ints, read without the server's lock."""
        e = self.engine
        return {**e.latent_stats(), "steps": int(e._steps),
                "kv_rows_attended_total": int(e.kv_rows_attended_total)}

    def stop_and_free(self):
        import jax

        self.start_server()  # ``stop`` waits for a loop that ran
        self.server.stop()
        for leaf in jax.tree.leaves(self.params):
            leaf.delete()
        self.params = self.engine = self.server = None
        gc.collect()


# ---- correct -------------------------------------------------------------


def overlap_of(mine, theirs) -> float:
    """The share of the rows ``mine`` names (``-1``: none) that
    ``theirs`` names too; 0 where ``mine`` names none."""
    a = {int(r) for r in mine if r >= 0}
    b = {int(r) for r in theirs if r >= 0}
    return len(a & b) / len(a) if a else 0.0


def reference_gaps(seed: int, config: dict, samples: list, *,
                   control: str | None = None) -> dict:
    """The reference once over every sample ``(prompt, tokens,
    selected)``: one layer's float32 weights at a time, every layer
    over the whole sequence. A sequence is padded to the
    configuration's fixed length so every seed finds the same programs
    compiled, and its hidden state waits on the host between layers (a
    layer's attention holds 5 GB of a sequence's queries, keys, values
    and maps beside 3.3 GB of weights). ``served_gap``: the widest gap
    by which a served token's logit lies below the reference's best.
    ``overlap``: the least share, over requests and layers, of the rows
    the program ``selected`` for its last step that the reference
    selects at that position. With ``control`` (a lower precision)
    also ``control_gap``: at the same positions, the gap of the token
    that precision puts first; ``control_overlap``: that precision's
    selection against the reference's; and ``served_control_overlap``:
    the program's selection against that precision's (high where
    ``control`` is the configuration's own precision, the witness).
    Every overlap also BY LAYER (``*_by_layer``: the least over the
    requests): rounding that compounds reads near 1 in layer 0 and
    falls with depth, a fault in the selection reads low from layer 0."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import glm_dsa_ref as ref

    sizes, cfg = model_sizes(config), ref_cfg(config)
    pad = config["correct"]
    seqs = [list(p) + list(t[:-1]) for p, t, _ in samples]
    T = max([len(q) for q in seqs] + [int(pad.get("pad_len", 0)), 1])
    G = max([len(t) for _, t, _ in samples] + [int(pad.get("pad_new", 0)), 1])
    n_layers = sizes["depth"]

    def hidden(precision: str, top):
        """-> for each sample, (the last layer's output at the compared
        positions ``[G, d]``, the rows its last position selects in
        each layer ``[layers, K]``)."""
        embed = jax.jit(ref.embed)
        xs, picked = [], [[] for _ in samples]
        for seq in seqs:
            toks = np.zeros((T,), np.int32)
            toks[: len(seq)] = seq
            xs.append(np.asarray(embed(top["embed_tokens"],
                                       jnp.asarray(toks))))
        programs: dict = {}
        for i in range(n_layers):
            dense = glm_dsa_weights.is_dense(sizes, i)
            if dense not in programs:  # one program a kind of layer
                programs[dense] = jax.jit(
                    lambda x, p, at, n, dense=dense: ref.layer(
                        x, p, 0, cfg, precision, at, dense=dense, rows=n))
            p = glm_dsa_weights.as_float32(
                glm_dsa_weights.make_layer(seed, sizes, i))
            for r, seq in enumerate(seqs):
                n = len(seq)
                x, sel = programs[dense](
                    jnp.asarray(xs[r]), p, jnp.asarray([n - 1], jnp.int32),
                    jnp.int32(n))
                xs[r] = np.asarray(x)
                picked[r].append(np.asarray(sel[0]))
            for leaf in jax.tree.leaves(p):
                leaf.delete()
        out = []
        for r, (prompt, tokens, _) in enumerate(samples):
            at = np.zeros((G,), np.int64)
            at[: len(tokens)] = len(prompt) - 1 + np.arange(len(tokens))
            out.append((jnp.asarray(xs[r][at]), np.stack(picked[r])))
        return out

    head = {}

    def logits_rows(precision: str, top):
        if precision not in head:
            head[precision] = jax.jit(lambda xg, n, w: ref.head(
                xg, n, w, cfg, precision))
        for xg, picked in hidden(precision, top):
            yield head[precision](xg, top["norm"], top["lm_head"]), picked

    @jax.jit
    def served_gap_of(out, targets, mask):
        best = out.max(-1)
        chosen = jnp.take_along_axis(out, targets[..., None], -1)[..., 0]
        gap = jnp.where(mask, best - chosen, 0.0)
        return gap.max(), gap.sum(), (gap > 0).sum()

    @jax.jit
    def control_gap_of(out, low, mask):
        first = low.argmax(-1)
        chosen = jnp.take_along_axis(out, first[..., None], -1)[..., 0]
        flips = (mask & (first != out.argmax(-1))).sum()
        return jnp.where(mask, out.max(-1) - chosen, 0.0).max(), flips

    n_tokens = sum(len(t) for _, t, _ in samples)
    res = {"served_gap": float("nan"), "overlap": 0.0, "control_gap": 0.0,
           "control_overlap": 1.0, "tokens": n_tokens, "control_flips": 0,
           "served_flips": 0, "served_mean_gap": 0.0, "per_request": [],
           "overlap_mean": 0.0, "overlap_per_request": [],
           "overlap_by_layer": [], "overlap_mean_by_layer": [],
           "control_overlap_by_layer": [], "served_control_overlap": 1.0,
           "served_control_overlap_by_layer": [], "rows_selected": []}
    if not samples:
        return res
    top = glm_dsa_weights.as_float32(glm_dsa_weights.make_top(seed, sizes))
    sound = list(logits_rows("float32", top))
    low = list(logits_rows(control, top)) if control else None
    per_row, overlaps, g_sum, g_n, c_gap, c_flips = [], [], 0.0, 0, 0.0, 0
    c_overlaps, sc_overlaps = [], []
    for r, ((_, tokens, selected), (out, picked)) in enumerate(
            zip(samples, sound)):
        n = len(tokens)
        targets = np.zeros((G,), np.int32)
        targets[:n] = tokens
        m = jnp.asarray(np.arange(G) < n)
        g, s, k = served_gap_of(out, jnp.asarray(targets), m)
        per_row.append(float(g))
        g_sum, g_n = g_sum + float(s), g_n + int(k)
        overlaps.append([
            overlap_of(selected[i], picked[i]) if selected else 0.0
            for i in range(n_layers)])
        res["rows_selected"].append(
            [int((np.asarray(selected[i]) >= 0).sum()) if selected else 0
             for i in range(n_layers)])
        if low is not None:
            cg, fl = control_gap_of(out, low[r][0], m)
            c_gap, c_flips = max(c_gap, float(cg)), c_flips + int(fl)
            c_overlaps.append([overlap_of(low[r][1][i], picked[i])
                               for i in range(n_layers)])
            sc_overlaps.append([
                overlap_of(selected[i], low[r][1][i]) if selected else 0.0
                for i in range(n_layers)])
    for leaf in jax.tree.leaves(top):
        leaf.delete()
    flat = [v for row in overlaps for v in row]
    by_layer = lambda rows: [min(col) for col in zip(*rows)]
    res.update(
        served_gap=max(per_row), per_request=per_row, served_flips=g_n,
        served_mean_gap=g_sum / max(1, n_tokens), control_gap=c_gap,
        control_flips=c_flips, overlap=min(flat),
        overlap_mean=sum(flat) / len(flat),
        overlap_per_request=[min(row) for row in overlaps],
        overlap_by_layer=by_layer(overlaps),
        overlap_mean_by_layer=[sum(col) / len(col)
                               for col in zip(*overlaps)],
        control_overlap_by_layer=by_layer(c_overlaps),
        served_control_overlap_by_layer=by_layer(sc_overlaps))
    if c_overlaps:
        res.update(
            control_overlap=min(res["control_overlap_by_layer"]),
            served_control_overlap=min(
                res["served_control_overlap_by_layer"]))
    return res


def _checks(gaps: dict, limits: dict, n_requests: int) -> list:
    return [
        Check("served_logit_gap", gaps["served_gap"],
              limits["served_logit_gap"],
              f"widest gap of a served token's logit below the "
              f"reference's best, {gaps['tokens']} tokens of "
              f"{n_requests} requests"),
        # a floor: the check passes where value <= limit
        Check("selection_overlap_shortfall", 1.0 - gaps["overlap"],
              1.0 - limits["selection_overlap"],
              f"1 - the least share of the rows the program selected "
              f"that the reference selects too (floor "
              f"{limits['selection_overlap']}), over {n_requests} "
              f"requests' last steps and the layers"),
    ]


def control(cell, seed: int, out_dir: str) -> dict:
    """The control of ``correct``: at each position of the prompts and
    tokens a run of this seed served (written by that run into its
    output directory), the gap of the token that the next precision
    below the configuration's puts first, and that precision's
    selection against the reference's. Has to fail one of the limits."""
    config = cell.config
    with open(os.path.join(out_dir, f"checked_seed{seed}.json")) as f:
        samples = [(s["prompt"], s["tokens"], s.get("selected"))
                   for s in json.load(f)]
    prec = config["precision"]["control"]
    gaps = reference_gaps(seed, config, samples, control=prec)
    limits = config["correct"]["limits"]
    ok = (gaps["control_gap"] <= limits["served_logit_gap"]
          and gaps["control_overlap"] >= limits["selection_overlap"])
    return {"seed": seed, "precision": prec, **gaps, "limits": limits,
            "correct": ok}


def readings(cell, seeds: list, ctx) -> list[dict]:
    """Limit-setting: for each seed, serve a burst of two requests a
    lane (so that the checked ones ran in reused lanes beside decoding
    ones), then the sound reading and the control's on what was
    served."""
    config, traffic = cell.config, cell.traffic
    rows = []
    for seed in seeds:
        served = Served(config, seed)
        n = 2 * served.slots
        requests = cell.generator().generate(
            dict(traffic, burst=n, rate_rps=1e-6, lead_s=0.0),
            seed=seed, vocab_size=served.sizes["vocab_size"], seconds=0.0,
        )[:n]
        load = Load(served, requests)
        load.start()
        time.sleep(0.5)
        load.stop()
        load.join()
        checked = pick_checked(load.snapshot(),
                               int(traffic["checked_requests"]), seed)
        samples = [(r.prompt, r.tokens, served.selected.get(tuple(r.prompt)))
                   for r in checked]
        served.stop_and_free()
        gaps = reference_gaps(seed, config, samples,
                              control=config["precision"]["control"])
        row = {"seed": seed,
               "sound": {"served_logit_gap": gaps["served_gap"],
                         "selection_overlap": gaps["overlap"]},
               "control": {"served_logit_gap": gaps["control_gap"],
                           "selection_overlap": gaps["control_overlap"]}}
        emit("reading", {**row, "detail": gaps})
        rows.append(row)
    return rows


# ---- one run --------------------------------------------------------------


def run(cell, args, ctx) -> Run:
    config, traffic = cell.config, cell.traffic
    split = {"backend_up_s": ctx.backend_up_s}
    served = Served(config, args.seed)
    if ctx.break_path:
        ctx.break_path(served)
    split.update(weights_s=served.weights_s, engine_s=served.engine_s,
                 warmup_s=served.warmup_s)
    requests = cell.generator().generate(
        traffic, seed=args.seed, vocab_size=served.sizes["vocab_size"],
        seconds=args.seconds + (float(traffic.get("trace_s", 0))
                                if args.trace else 0.0),
    )
    load = Load(served, requests)
    t = time.perf_counter()
    clock0 = load.start()
    split["hand_over_s"] = clock0 - t
    lead = float(traffic["lead_s"])
    time.sleep(max(0.0, clock0 + lead - time.perf_counter()))
    split["lead_traffic_s"] = time.perf_counter() - clock0
    split.update(ctx.ledger.snapshot())
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx.t0
    programs_before = ctx.ledger.programs
    counts_before = dict(served.engine.compile_counts())

    # ---- the measured window --------------------------------------------
    block_s = float(traffic["block_s"])
    blocks: list[Block] = []
    trace_dir = os.path.join(ctx.out_dir, "trace")
    traced_counts = None
    if args.trace:
        with btrace.record(trace_dir, ctx.spans):
            # counters over the sampling only: the profiler's own start
            # and stop take seconds in which the engine keeps running
            before = served.counts()
            blocks += sample_blocks(served, float(traffic["trace_s"]),
                                    block_s, traced=True)
            traced_counts = (before, served.counts())
    timed_before = served.counts()
    timed = sample_blocks(served, args.seconds, block_s)
    timed_counts = (timed_before, served.counts())
    blocks += timed
    w0, w1 = timed[0].start, timed[-1].end
    load.stop()
    status_counts = dict(served.engine.status_counts)
    reject_counts = dict(served.engine.reject_counts)
    compile_counts = dict(served.engine.compile_counts())
    peak = ctx.memory_peak()
    compiles_in_window = ctx.ledger.programs - programs_before
    gc.unfreeze()
    selected = served.selected
    scope_maps = None
    if args.trace:
        # after the window: the compiled programs' text, for the
        # readers that attribute device time to the program's scopes
        from benchmarks.layer_metrics import _gd_common

        texts = getattr(served.engine, "program_hlo", dict)()
        scope_maps = _gd_common.scope_maps(texts) or None
    served.stop_and_free()
    load.join()
    records = load.snapshot()

    due_in = [r for r in requests if w0 <= clock0 + r.due_s < w1]
    by_index = {r.index: r for r in records}
    sent_in = [by_index[i] for i, r in enumerate(requests)
               if w0 <= clock0 + r.due_s < w1 and i in by_index]
    failed = [
        r for r in sent_in
        if not (r.http == 200 and r.status == "complete")
        and not (r.http == 503 and r.done >= w1)  # our own stop
    ]
    finished = [r for r in records if w0 <= r.done <= w1]
    wrong_length = [
        r for r in finished if r.http == 200 and r.status == "complete"
        and len(r.tokens) != requests[r.index].max_new_tokens
    ]
    tpots = [t for t in map(tpot_ms, finished) if t is not None]
    tpots_engine = [t for t in map(tpot_engine_ms, finished)
                    if t is not None]
    late = max((r.sent - r.due for r in records), default=0.0)

    run_ = Run(cell=cell)
    run_.blocks = blocks
    run_.window = describe(timed, "tokens/s")
    run_.window.update(
        finished_requests=len(finished), tpot_samples=len(tpots),
        generator_max_late_s=late,
        tpot_client_p50_ms=statistics.median(tpots) if tpots else None,
        tpot_engine_p50_ms=statistics.median(tpots_engine)
        if tpots_engine else None,
        queue_depth_start=timed[0].extra["queue_depth"],
        queue_depth_end=timed[-1].extra["queue_depth"],
    )
    run_.attempted = len(due_in)
    run_.failed = len(failed)
    run_.end_to_end = {
        "serve_tokens_per_s": window_quotient(timed),
        "setup_s": setup_s,
    }
    run_.setup_split = split
    run_.spans = ctx.spans
    run_.counters = {
        "glm_dsa_slots": served.slots,
        "compile_counts": compile_counts,
        "compiles_in_window": compiles_in_window,
        "requests_by_status": status_counts,
        "rejects": reject_counts,
        "sizes": served.sizes,
        "prefill_chunk": int(config["engine"]["prefill_chunk"]),
        "glm_dsa_counts_timed": timed_counts,
        "glm_dsa_counts_traced": traced_counts,
        "scope_maps": scope_maps,
    }
    run_.device = {"memory_peak_bytes": peak}
    if args.trace:
        run_.trace = btrace.load(trace_dir)

    # ---- correct: after the window, the program's state freed -----------
    t = time.perf_counter()
    checked = pick_checked(
        [r for r in records if r.done <= w1],
        int(traffic["checked_requests"]), args.seed,
    )
    samples = [(r.prompt, r.tokens, selected.get(tuple(r.prompt)))
               for r in checked]
    with open(os.path.join(ctx.out_dir,
                           f"checked_seed{args.seed}.json"), "w") as f:
        json.dump([{"prompt": p, "tokens": t, "selected": s}
                   for p, t, s in samples], f)
    gaps = reference_gaps(args.seed, config, samples)
    run_.checks = _checks(gaps, config["correct"]["limits"], len(checked)) + [
        Check("compiles_in_window",
              float(compiles_in_window
                    + (compile_counts != counts_before)), 0.0,
              "programs compiled or loaded inside the measured window"),
        Check("failed_requests", float(len(failed) + len(wrong_length)),
              0.0,
              "requests due in the window that were refused, errored or "
              "timed out, or answered with another length than asked"),
    ]
    run_.notes["reference_s"] = time.perf_counter() - t
    run_.notes["gaps"] = gaps
    emit("reference_s", {"seconds": run_.notes["reference_s"], **gaps})
    return run_
