"""Driver for ``kind: sdar_serve`` cells: a model that generates a block
of positions at a time by masked diffusion (``ddp_tpu/models/sdar.py``),
served by the program's ``LMServer`` over a ``ServeEngine`` and entered
by ``submit_and_wait``, exactly as ``drivers/serve.py`` enters a
one-token model. Load, the block sampler and the window arithmetic are
that driver's, imported.

What differs is what a step is, and so what ``correct`` compares. A
step is one forward over every lane's block; it yields no token of its
own, and a block's tokens are COMMITTED together by the forward over
the clean block. The token counter the sampler polls
(``tokens_emitted_total``) counts committed tokens, never forward
positions, and never more than a request asked for.

The rate is the accepted whole-window quotient (``window_quotient``:
every committed token of the window over its wall time), reported as
``serve_tokens_per_s`` under the bound that metric has. Requests of one
length admitted together would keep their lanes in step for ever; the
configuration's ``engine`` group spaces admissions (``admit_every``) so
that the lanes spread over a request's length during the lead (PERF.md
section 6).

``correct`` is decided on what the timed path produced. Every request
asks the engine to record its forwards (``record_blocks``: the block's
first position, its tokens and its mask as each forward saw them).
After the window, the program's state freed, the plain reference
(``reference/sdar_ref.py``) is handed, for ``checked_requests``
finished requests, the recorded inputs of every unmasking forward of
``correct.blocks_per_request`` blocks spread evenly from the first to
the last: the whole sequence up to the block's end, recomputed from
scratch under the block-causal mask, layer by layer with each layer's
float32 copy of the bfloat16 weights remade from the seed. Logits are
compared, not tokens: ``served_logit_gap`` is how far the token the
program put at the position it unmasked lies below the reference's best
there. Printed beside it and held to no limit: ``unmask_conf_gap``, how
far the reference's confidence (log of its best token's probability) at
the position the program unmasked lies below its highest among the
masked positions (at seeded random weights a block's masked positions
hold nearly equal confidences, so no precision can be told by it).

The control (``check_controls.py``) puts the reference computed with
float8 operands in the program's place on the same recorded inputs; it
has to come out NOT correct.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import time

import numpy as np

from benchmarks.drivers.serve import (
    Load,
    pick_checked,
    sample_blocks,
    tpot_engine_ms,
    tpot_ms,
)
from benchmarks.harness import sdar_weights
from benchmarks.harness import trace as btrace
from benchmarks.harness.result import Check, Run, emit
from benchmarks.harness.window import Block, describe, window_quotient

__all__ = ["Load", "Served", "control", "readings", "run", "sample_blocks",
           "tpot_engine_ms", "window_quotient"]


def model_sizes(config: dict) -> dict:
    """The published keys under the names the harness's weight and
    operation counts take."""
    return dict(
        vocab_size=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        depth=int(config["num_hidden_layers"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        num_experts=int(config["num_experts"]),
        moe_intermediate=int(config["moe_intermediate_size"]),
    )


def ref_cfg(config: dict) -> dict:
    # ``generation``: what the config does not give and ``assumed`` sets
    s, g = model_sizes(config), config["generation"]
    return dict(
        num_heads=s["num_heads"], num_kv_heads=s["num_kv_heads"],
        head_dim=s["head_dim"], top_k=int(config["num_experts_per_tok"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        block_length=int(g["block_length"]),
        denoise_steps=int(g["denoise_steps"]),
        mask_token_id=int(g["mask_token_id"]), unmask=g["unmask"],
        unmask_threshold=float(g["unmask_threshold"]),
    )


def lm_spec(config: dict):
    from ddp_tpu.models.lm import LMSpec

    s, c = model_sizes(config), ref_cfg(config)
    return LMSpec(
        vocab_size=s["vocab_size"],
        total_len=int(config["engine"]["cache_length"]),
        d_model=s["d_model"], depth=s["depth"], num_heads=s["num_heads"],
        num_kv_heads=s["num_kv_heads"], head_dim=s["head_dim"],
        num_experts=s["num_experts"], moe_top_k=c["top_k"],
        moe_normalize_gates=c["norm_topk_prob"],
        moe_intermediate=s["moe_intermediate"], block="qwen3_moe",
        rope_theta=c["rope_theta"], rms_eps=c["rms_eps"],
        block_length=c["block_length"], denoise_steps=c["denoise_steps"],
        mask_token_id=c["mask_token_id"], unmask=c["unmask"],
        unmask_threshold=c["unmask_threshold"],
    )


class Served:
    """The server, built the way ``scripts/serve.py`` builds it, over
    the benchmark's seeded weights. ``submit`` asks every request to
    record its forwards and keeps them by prompt (``Load``'s records
    hold the answer's tokens only)."""

    def __init__(self, config: dict, seed: int):
        import jax

        from ddp_tpu.serve.engine import ServeEngine
        from ddp_tpu.serve.server import LMServer

        self.spec = lm_spec(config)
        s = model_sizes(config)
        # The generator draws ids below the mask token's.
        self.sizes = dict(s, model_vocab_size=s["vocab_size"],
                          vocab_size=self.spec.mask_token_id)
        t = time.perf_counter()
        self.params = sdar_weights.make_params(seed, s)
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
        self.server = LMServer(self.engine, port=0).start()
        self.recorded: dict = {}

    def submit(self, body: dict):
        http, payload = self.server.submit_and_wait(
            {**body, "record_blocks": True}
        )
        forwards = payload.pop("block_inputs", None)
        if forwards is not None:
            self.recorded[tuple(body["prompt_tokens"])] = forwards
        return http, payload

    def tokens_total(self) -> int:
        return int(self.engine.tokens_emitted_total)

    def gauges(self) -> dict:
        """Unlocked reads of plain host-side state."""
        e = self.engine
        return {"active": int(e.active),
                "queue_depth": int(e.scheduler.depth)}

    def stop_and_free(self):
        import jax

        self.server.stop()
        for leaf in jax.tree.leaves(self.params):
            leaf.delete()
        self.params = self.engine = self.server = None
        gc.collect()


# ---- correct -------------------------------------------------------------


def pick_blocks(forwards: list, k: int) -> list[dict]:
    """A request's recorded forwards ``[(pos, tokens, mask), ...]`` ->
    ``k`` of its blocks spread evenly from the first to the last, each
    ``{"pos", "forwards"}`` with the block's forwards in order (the
    last one clean)."""
    by_pos: dict = {}
    for pos, toks, mask in forwards:
        by_pos.setdefault(int(pos), []).append((list(toks), list(mask)))
    order = sorted(by_pos)
    at = np.linspace(0, len(order) - 1, min(k, len(order))).round()
    return [{"pos": order[i], "forwards": by_pos[order[i]]}
            for i in sorted({int(i) for i in at})]


def decisions(samples: list, block_length: int) -> dict:
    """Every unmasking forward of the picked blocks, as arrays over
    rows: the sequence the forward saw (prompt, committed answer, the
    block as it stood), the block's start, its mask, the positions the
    program unmasked and the tokens it put there (read off the next
    forward's inputs)."""
    B = block_length
    seqs, starts, masks, taken, tokens = [], [], [], [], []
    for s in samples:
        context = list(s["prompt"]) + list(s["tokens"])
        for blk in s["blocks"]:
            fw = blk["forwards"]
            for (toks, mask), (nxt, nmask) in zip(fw, fw[1:]):
                m, nm = np.array(mask, bool), np.array(nmask, bool)
                if not m.any():
                    continue
                seqs.append(context[: blk["pos"]] + list(toks))
                starts.append(blk["pos"])
                masks.append(m)
                taken.append(m & ~nm)
                tokens.append(np.array(nxt, np.int64))
    n = len(seqs)
    T = max((len(q) for q in seqs), default=B)
    T += -T % B
    padded = np.zeros((n, T), np.int32)
    for i, q in enumerate(seqs):
        padded[i, : len(q)] = q
    return {"tokens": padded, "starts": np.array(starts, np.int32),
            "mask": np.array(masks, bool).reshape(n, B),
            "taken": np.array(taken, bool).reshape(n, B),
            "chosen": np.array(tokens, np.int64).reshape(n, B)}


def reference_logits(seed: int, config: dict, rows: dict, *,
                     precision: str = "float32", pad_rows: int = 0,
                     pad_len: int = 0):
    """The reference's logits ``[n, B, V]`` at each row's block: the
    full forward of each row's sequence, layer by layer, one layer's
    float32 weights at a time, ``pad_rows`` rows at a time (a row's
    attention scores are 19 MB a layer at 384 positions). Rows and
    length are padded to fixed sizes so every seed finds the same
    programs compiled."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import sdar_ref as ref

    sizes, cfg = model_sizes(config), ref_cfg(config)
    B = cfg["block_length"]
    tokens, starts = rows["tokens"], rows["starts"]
    n, T = tokens.shape
    R = pad_rows or n
    N, L = -(-n // R) * R, max(T, pad_len)
    toks = np.zeros((N, L), np.int32)
    toks[:n, :T] = tokens
    at = np.zeros((N,), np.int32)
    at[:n] = starts
    top = sdar_weights.as_float32(sdar_weights.make_top(seed, sizes))
    x = jax.jit(ref.embed)(top["embed_tokens"], jnp.asarray(toks))
    layer = jax.jit(lambda x, p: ref.layer(x, p, cfg, precision))
    for i in range(sizes["depth"]):
        p = sdar_weights.as_float32(sdar_weights.make_layer(seed, sizes, i))
        x = jnp.concatenate([layer(x[i:i + R], p)
                             for i in range(0, N, R)])
        jax.block_until_ready(x)
        for leaf in jax.tree.leaves(p):
            leaf.delete()
    cols = jnp.asarray(at)[:, None] + jnp.arange(B)[None, :]
    xb = x[jnp.arange(N)[:, None], cols]  # [N, B, d]
    logits = jax.jit(lambda xb, norm, w: ref.head(xb, norm, w, cfg, precision))(
        xb, top["norm"], top["lm_head"]
    )
    jax.block_until_ready(logits)
    for leaf in jax.tree.leaves(top):
        leaf.delete()
    return logits[:n]


def gaps_of(ref_logits, mask, taken, chosen) -> dict:
    """The two numbers ``correct`` compares, from the reference's
    logits ``[n, B, V]`` and a decider's choices (the program's, or the
    control's): which positions it unmasked and with which tokens."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce(logits, mask, taken, chosen):
        best = logits.max(-1)
        at = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
        logit_gap = jnp.where(taken, best - at, 0.0)
        conf = jax.nn.log_softmax(logits, axis=-1).max(-1)
        top = jnp.where(mask, conf, -jnp.inf).max(-1)
        low = jnp.where(taken, conf, jnp.inf).min(-1)
        conf_gap = jnp.where(taken.any(-1), top - low, 0.0)
        return (logit_gap.max(), logit_gap.sum(), (logit_gap > 0).sum(),
                conf_gap.max(), conf_gap.sum(), (conf_gap > 0).sum())

    if not len(mask):
        nan = float("nan")
        return {"served_logit_gap": nan, "unmask_conf_gap": nan,
                "decisions": 0}
    lg, lsum, lflips, cg, csum, cflips = (
        float(v) for v in reduce(ref_logits, jnp.asarray(mask),
                                 jnp.asarray(taken), jnp.asarray(chosen))
    )
    n_taken = max(1, int(np.sum(taken)))
    return {"served_logit_gap": lg, "unmask_conf_gap": cg,
            "decisions": int(len(mask)), "positions": int(np.sum(taken)),
            "token_flips": int(lflips), "position_flips": int(cflips),
            "mean_logit_gap": lsum / n_taken,
            "mean_conf_gap": csum / max(1, len(mask))}


def decide(logits, rows: dict, cfg: dict) -> tuple:
    """What a decider computing ``logits`` ``[n, B, V]`` would unmask in
    each row, by the configuration's strategy -> (taken, chosen)."""
    from benchmarks.reference import sdar_ref as ref

    taken = np.zeros_like(rows["mask"])
    chosen = np.zeros(rows["mask"].shape, np.int64)
    for i in range(len(taken)):
        toks, _, took = ref.unmask(
            logits[i], np.zeros(cfg["block_length"], np.int64),
            rows["mask"][i], cfg,
        )
        taken[i, took] = True
        chosen[i] = toks
    return taken, chosen


def sound_and_control(seed: int, config: dict, samples: list, *,
                      with_control: bool) -> dict:
    cfg = ref_cfg(config)
    rows = decisions(samples, cfg["block_length"])
    pad = config["correct"]
    kw = dict(pad_rows=int(pad.get("pad_rows", 0)),
              pad_len=int(pad.get("pad_len", 0)))
    logits = reference_logits(seed, config, rows, **kw)
    out = {"sound": gaps_of(logits, rows["mask"], rows["taken"],
                            rows["chosen"])}
    if with_control:
        prec = config["precision"]["control"]
        low = reference_logits(seed, config, rows, precision=prec, **kw)
        out["control"] = gaps_of(logits, rows["mask"],
                                 *decide(low, rows, cfg))
    return out


def _samples(checked: list, recorded: dict, config: dict) -> list:
    k = int(config["correct"]["blocks_per_request"])
    return [
        {"prompt": r.prompt, "tokens": r.tokens,
         "blocks": pick_blocks(recorded[tuple(r.prompt)], k)}
        for r in checked if tuple(r.prompt) in recorded
    ]


def _within(gaps: dict, limits: dict) -> bool:
    return all(np.isfinite(gaps[k]) and gaps[k] <= limit
               for k, limit in limits.items())


def control(cell, seed: int, out_dir: str) -> dict:
    """The control of ``correct``: on the block inputs a run of this
    seed recorded (written by that run into its output directory), the
    choices of the reference computed in the next precision below the
    configuration's. Has to pass one of the limits at least."""
    config = cell.config
    with open(os.path.join(out_dir, f"checked_seed{seed}.json")) as f:
        samples = json.load(f)
    res = sound_and_control(seed, config, samples, with_control=True)
    limits = config["correct"]["limits"]
    return {"seed": seed, "precision": config["precision"]["control"],
            **{f"control_{k}": v for k, v in res["control"].items()},
            **{f"sound_{k}": v for k, v in res["sound"].items()},
            "limits": limits, "correct": _within(res["control"], limits)}


def readings(cell, seeds: list, ctx) -> list[dict]:
    """Limit-setting: for each seed, serve a short burst (one request a
    lane and the checked ones among them), then the sound readings and
    the control's on what was recorded."""
    config, traffic = cell.config, cell.traffic
    rows = []
    for seed in seeds:
        served = Served(config, seed)
        requests = cell.generator().generate(
            dict(traffic, burst=served.slots, rate_rps=1e-6, lead_s=0.0),
            seed=seed, vocab_size=served.sizes["vocab_size"], seconds=0.0,
        )[: served.slots]
        load = Load(served, requests)
        load.start()
        time.sleep(0.5)
        load.stop()
        load.join()
        checked = pick_checked(load.snapshot(),
                               int(traffic["checked_requests"]), seed)
        samples = _samples(checked, served.recorded, config)
        served.stop_and_free()
        res = sound_and_control(seed, config, samples, with_control=True)
        keys = ("served_logit_gap", "unmask_conf_gap")
        row = {"seed": seed,
               "sound": {k: res["sound"][k] for k in keys},
               "control": {k: res["control"][k] for k in keys}}
        emit("reading", {**row, "detail": res})
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
    clock0 = load.start()
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
    stats = served.engine.block_stats
    traced_counts = None
    if args.trace:
        with btrace.record(trace_dir, ctx.spans):
            # counters over the sampling only: the profiler's own start
            # and stop take seconds in which the engine keeps running
            before = stats()
            blocks += sample_blocks(served, float(traffic["trace_s"]),
                                    block_s, traced=True)
            traced_counts = (before, stats())
    timed_before = stats()
    timed = sample_blocks(served, args.seconds, block_s)
    timed_counts = (timed_before, stats())
    blocks += timed
    w0, w1 = timed[0].start, timed[-1].end
    load.stop()
    status_counts = dict(served.engine.status_counts)
    reject_counts = dict(served.engine.reject_counts)
    compile_counts = dict(served.engine.compile_counts())
    peak = ctx.memory_peak()
    compiles_in_window = ctx.ledger.programs - programs_before
    gc.unfreeze()
    recorded = served.recorded
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
        "block_slots": served.slots,
        "compile_counts": compile_counts,
        "compiles_in_window": compiles_in_window,
        "requests_by_status": status_counts,
        "rejects": reject_counts,
        "sizes": model_sizes(config),
        "top_k": int(config["num_experts_per_tok"]),
        "block_counts_timed": timed_counts,
        "block_counts_traced": traced_counts,
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
    samples = _samples(checked, recorded, config)
    with open(os.path.join(ctx.out_dir,
                           f"checked_seed{args.seed}.json"), "w") as f:
        json.dump(samples, f)
    gaps = sound_and_control(args.seed, config, samples,
                             with_control=False)["sound"]
    limits = config["correct"]["limits"]
    run_.checks = [
        Check("served_logit_gap", gaps["served_logit_gap"],
              limits["served_logit_gap"],
              "widest gap of an unmasked position's served token below "
              f"the reference's best there, {gaps['decisions']} unmasking "
              f"forwards of {len(samples)} requests"),
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
