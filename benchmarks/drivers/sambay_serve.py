"""Driver for ``kind: sambay_serve`` cells: the SambaY decoder
(``ddp_tpu/models/sambay.py``: Mamba-1, windowed and full differential
attention, and a cross-decoder of gated memory units and
cross-attention) served one token a step by the program's ``LMServer``
over a ``ServeEngine`` and entered by ``submit_and_wait``, exactly as
``drivers/serve.py`` enters the GPT-2 block. Load (the hybrid driver's
160 connections, here with the time-zero burst queued in the generator's
order before the engine loop starts), the block sampler, the choice of checked requests and the window
arithmetic are those drivers', imported; the model, its weights, its
reference and its counters are this one's.

The rate is the accepted whole-window quotient (``window_quotient``:
every token of the window over its wall time), reported as
``serve_tokens_per_s`` under the bound that metric has.

``correct`` is decided on what the timed path produced. After the
window, the program's state freed, ``checked_requests`` finished
requests (the longest among them) go through the plain reference
(``reference/sambay_ref.py``) ONCE, layer by layer with each layer's
float32 copy of the stored weights remade from the seed: every layer at
every position of the whole sequence (prompt and served tokens), from a
zero state by a sequential scan, under explicit masks.
``served_logit_gap`` is the widest gap by which a served token's
reference logit lies below the reference's best at that position, over
every generated position. Those requests were prefilled in two to four
chunks with a padded last bucket and WITHOUT their cross-decoder, in
lanes whose rings other requests filled before them, their rings
wrapped, while the other lanes decoded: a ring row destroyed by
padding or by an idle lane, a chunk that overwrote rows before reading
them, a state not reset, a read-out taken from the wrong position
shows here.

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

from benchmarks.drivers import granite_serve
from benchmarks.drivers.serve import (
    pick_checked,
    sample_blocks,
    tpot_engine_ms,
    tpot_ms,
)
from benchmarks.harness import sambay_weights
from benchmarks.harness import trace as btrace
from benchmarks.harness.result import Check, Run, emit
from benchmarks.harness.window import Block, describe, window_quotient

__all__ = ["Load", "Served", "control", "readings", "run", "sample_blocks",
           "tpot_engine_ms", "window_quotient"]

BLOCK = "sambay"
KINDS = ("mamba", "window", "full", "gmu", "cross")
# How long the hand-over of the time-zero burst waits for a request to
# be accepted before it hands over the next (a refused one never is).
HAND_OVER_S = 1.0


class Load(granite_serve.Load):
    """The hybrid driver's open-loop load of 160 connections, with the
    requests DUE AT TIME ZERO in the server's queue, in the generator's
    order, when its engine loop starts. The traffic is ONE realisation
    (``order_seed``), but handed to 128 threads at once the burst races
    for the server's lock: which 64 requests take the lanes first, and
    with it what every later window holds, then differs from run to
    run, and sets of six spread by 0.7-0.8% in ``serve_tokens_per_s``
    whatever the mix (PERF.md section 6). So ``start`` hands the burst
    over one request at a time, each through ``submit_and_wait`` on a
    connection of its own as every other request is, the next once the
    engine has counted the one before (``accepted_total``); nothing
    contends for the lock then, because ``Served`` has not started the
    server yet: ``start`` does, and time zero is that moment. Every
    later request goes the imported way, at its due time."""

    def __init__(self, served, requests: list):
        self.burst = [r for r in requests if r.due_s <= 0.0]
        assert requests[: len(self.burst)] == self.burst
        super().__init__(served, requests[len(self.burst):])

    def start(self) -> float:
        engine = self.served.engine
        # a sweep's later rates find the loop running: it would starve
        # a hand-over, and a knee does not turn on the order
        ordered = not self.served.started
        due = time.perf_counter()
        for i, r in enumerate(self.burst):
            seen = engine.accepted_total
            give_up = time.perf_counter() + HAND_OVER_S
            self._pool.submit(granite_serve.Load._one, self, i, r, due)
            while (ordered and engine.accepted_total == seen
                   and time.perf_counter() < give_up):
                time.sleep(0.0002)
        self.served.start_server()
        return super().start()

    def _one(self, i, r, due):
        """The dispatcher's requests: those after the burst."""
        super()._one(len(self.burst) + i, r, due)


def layer_table(config: dict) -> list[str]:
    """The layer kinds from ``num_hidden_layers`` (``mb_per_layer`` 2:
    Mamba on every second layer of the self-decoder): the rule the
    configuration's ``assumed.layer_table`` states."""
    n = int(config["num_hidden_layers"])
    if int(config["mb_per_layer"]) != 2 or n % 2:
        raise ValueError("the driver serves the published table: "
                         "mb_per_layer 2 over an even depth")
    half = n // 2
    return [
        ("window" if i % 2 else "mamba") if i <= half
        else "full" if i == half + 1
        else ("cross" if i % 2 else "gmu")
        for i in range(n)
    ]


def model_sizes(config: dict) -> dict:
    """The published keys (and the assumed Mamba-1 sizes) under the
    names the harness's weight and operation counts take."""
    d, H = int(config["hidden_size"]), int(config["num_attention_heads"])
    m = config["assumed"]["mamba_sizes"]
    return dict(
        vocab_size=int(config["vocab_size"]), d_model=d,
        depth=int(config["num_hidden_layers"]),
        layer_types=layer_table(config),
        num_heads=H, num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=d // H,
        mamba_d_inner=int(m["d_inner"]), mamba_d_state=int(m["d_state"]),
        mamba_d_conv=int(m["d_conv"]), mamba_dt_rank=int(m["dt_rank"]),
        mlp_intermediate=int(config["intermediate_size"]),
        sliding_window=int(config["sliding_window"]),
    )


def ref_cfg(config: dict) -> dict:
    """What the reference reads, under the config's own keys."""
    s = model_sizes(config)
    keys = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "hidden_size", "sliding_window",
            "layer_norm_eps")
    return {**{k: config[k] for k in keys},
            "mamba_dt_rank": s["mamba_dt_rank"],
            "mamba_d_state": s["mamba_d_state"]}


def lm_spec(config: dict):
    from ddp_tpu.models.lm import LMSpec

    s = model_sizes(config)
    if not config["tie_word_embeddings"] or config.get("mlp_bias") or \
            config.get("lm_head_bias"):
        raise ValueError("the driver serves the published model: a tied "
                         "head, no bias in the MLP or on the head")
    return LMSpec(
        vocab_size=s["vocab_size"],
        total_len=int(config["engine"]["cache_length"]),
        d_model=s["d_model"], depth=s["depth"], num_heads=s["num_heads"],
        num_kv_heads=s["num_kv_heads"], head_dim=s["head_dim"], block=BLOCK,
        layer_types=tuple(s["layer_types"]),
        mamba_d_inner=s["mamba_d_inner"], mamba_d_state=s["mamba_d_state"],
        mamba_d_conv=s["mamba_d_conv"], mamba_dt_rank=s["mamba_dt_rank"],
        mlp_intermediate=s["mlp_intermediate"],
        sliding_window=s["sliding_window"],
        layer_norm_eps=float(config["layer_norm_eps"]),
        tie_embeddings=True, position_embedding="nope",
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
        self.params = sambay_weights.make_params(seed, s)
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
        self.submit = self.server.submit_and_wait

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
        return {**e.recurrent_stats(), "steps": int(e._steps),
                "kv_rows_attended_total": int(e.kv_rows_attended_total)}

    def stop_and_free(self):
        import jax

        self.start_server()  # ``stop`` waits for a loop that ran
        self.server.stop()
        for leaf in jax.tree.leaves(self.params):
            leaf.delete()
        self.params = self.engine = self.server = self.submit = None
        gc.collect()


# ---- correct -------------------------------------------------------------


def reference_gaps(seed: int, config: dict, samples: list, *,
                   control: str | None = None) -> dict:
    """The reference once over every sample ``(prompt, tokens)``, one
    layer's float32 weights at a time, every layer at every position.
    Rows and length are padded to the configuration's fixed sizes so
    every seed finds the same programs compiled; a layer runs a row at
    a time (a row's 40 attention maps are 1.5 GB at 3,072 positions).
    ``served_gap``: the widest gap by which a served token's logit lies
    below the reference's best. With ``control`` (a lower precision)
    also ``control_gap``: at the same positions, the gap of the token
    that precision puts first."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import sambay_ref as ref

    sizes, cfg = model_sizes(config), ref_cfg(config)
    pad = config["correct"]
    R = max(int(pad.get("pad_rows", 0)), len(samples), 1)
    seqs = [list(p) + list(t[:-1]) for p, t in samples]
    T = max([len(q) for q in seqs] + [int(pad.get("pad_len", 0)), 1])
    G = max([len(t) for _, t in samples] + [int(pad.get("pad_new", 0)), 1])
    toks = np.zeros((R, T), np.int32)
    at = np.zeros((R, G), np.int32)  # positions whose logits are compared
    targets = np.zeros((R, G), np.int32)
    mask = np.zeros((R, G), bool)
    for r, ((prompt, tokens), seq) in enumerate(zip(samples, seqs)):
        toks[r, : len(seq)] = seq
        n = len(tokens)
        # position len(prompt) - 1 + j predicts served token j
        at[r, :n] = len(prompt) - 1 + np.arange(n)
        targets[r, :n] = tokens
        mask[r, :n] = True

    def hidden(precision: str, top):
        """The last layer's output at the compared positions,
        ``[R, G, d]``."""
        xs = [jax.jit(ref.embed)(top["embed_tokens"],
                                 jnp.asarray(toks[r:r + 1]))
              for r in range(R)]
        carries = [{} for _ in range(R)]
        programs: dict = {}
        for i, kind in enumerate(sizes["layer_types"]):
            # one program a kind of layer: the index is data
            if kind not in programs:
                programs[kind] = jax.jit(
                    lambda x, c, p, i, kind=kind: ref.layer(
                        x, c, p, i, cfg, precision, kind=kind))
            p = sambay_weights.as_float32(
                sambay_weights.make_layer(seed, sizes, i))
            for r in range(R):
                xs[r], carries[r] = programs[kind](
                    xs[r], carries[r], p, jnp.int32(i))
            jax.block_until_ready(xs)
            for leaf in jax.tree.leaves(p):
                leaf.delete()
        return [x[0][jnp.asarray(at[r])] for r, x in enumerate(xs)]

    def logits_rows(precision: str, top):
        head = jax.jit(lambda xg, n, w: ref.head(xg, n, w, cfg, precision))
        for xg in hidden(precision, top):
            yield head(xg, top["final_layernorm"], top["embed_tokens"])

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

    n_tokens = int(mask.sum())
    res = {"served_gap": float("nan"), "control_gap": 0.0,
           "tokens": n_tokens, "control_flips": 0, "served_flips": 0,
           "served_mean_gap": 0.0, "per_request": []}
    if not samples:
        return res
    top = sambay_weights.as_float32(sambay_weights.make_top(seed, sizes))
    low_rows = iter(logits_rows(control, top)) if control else None
    per_row, g_sum, g_n, c_gap, c_flips = [], 0.0, 0, 0.0, 0
    for r, out in enumerate(logits_rows("float32", top)):
        m = jnp.asarray(mask[r])
        g, s, n = served_gap_of(out, jnp.asarray(targets[r]), m)
        per_row.append(float(g))
        g_sum, g_n = g_sum + float(s), g_n + int(n)
        if low_rows is not None:
            cg, fl = control_gap_of(out, next(low_rows), m)
            c_gap, c_flips = max(c_gap, float(cg)), c_flips + int(fl)
    for leaf in jax.tree.leaves(top):
        leaf.delete()
    per_row = per_row[: len(samples)]
    res.update(served_gap=max(per_row), per_request=per_row,
               served_flips=g_n, served_mean_gap=g_sum / max(1, n_tokens),
               control_gap=c_gap, control_flips=c_flips)
    return res


def control(cell, seed: int, out_dir: str) -> dict:
    """The control of ``correct``: at each position of the prompts and
    tokens a run of this seed served (written by that run into its
    output directory), the gap of the token that the next precision
    below the configuration's puts first. Has to pass the limit."""
    config = cell.config
    with open(os.path.join(out_dir, f"checked_seed{seed}.json")) as f:
        samples = [(s["prompt"], s["tokens"]) for s in json.load(f)]
    prec = config["precision"]["control"]
    gaps = reference_gaps(seed, config, samples, control=prec)
    limit = config["correct"]["limits"]["served_logit_gap"]
    return {"seed": seed, "precision": prec, **gaps,
            "limit": limit, "correct": gaps["control_gap"] <= limit}


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
        served.stop_and_free()
        gaps = reference_gaps(
            seed, config, [(r.prompt, r.tokens) for r in checked],
            control=config["precision"]["control"])
        row = {"seed": seed,
               "sound": {"served_logit_gap": gaps["served_gap"]},
               "control": {"served_logit_gap": gaps["control_gap"]}}
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
        "sambay_slots": served.slots,
        "compile_counts": compile_counts,
        "compiles_in_window": compiles_in_window,
        "requests_by_status": status_counts,
        "rejects": reject_counts,
        "sizes": served.sizes,
        "prefill_chunk": int(config["engine"]["prefill_chunk"]),
        "sambay_counts_timed": timed_counts,
        "sambay_counts_traced": traced_counts,
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
    samples = [(r.prompt, r.tokens) for r in checked]
    with open(os.path.join(ctx.out_dir,
                           f"checked_seed{args.seed}.json"), "w") as f:
        json.dump([{"prompt": p, "tokens": t} for p, t in samples], f)
    gaps = reference_gaps(args.seed, config, samples)
    limits = config["correct"]["limits"]
    run_.checks = [
        Check("served_logit_gap", gaps["served_gap"],
              limits["served_logit_gap"],
              f"widest gap of a served token's logit below the "
              f"reference's best, {gaps['tokens']} tokens of "
              f"{len(checked)} requests"),
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
