"""Driver for ``kind: serve`` cells: the program's ``LMServer`` over a
``ServeEngine``, entered by ``submit_and_wait`` (what ``POST /generate``
calls) from generator threads in the run's own process.

Load is open loop at the rate fixed in the traffic file: each request is
handed to a worker thread when it is due, whatever the earlier ones are
doing, as independent clients would. Submitters race for the server's
lock (the engine loop holds it for every whole step and takes it again
at once), so the order in which requests enter the queue, and with it
the prefill a window holds, differs a little from run to run of one
schedule: on the chip the rate spreads by 3% for that reason. Handing
requests over strictly in order was tried and is worse: a lone
submitter waits 14-39 s for that lock and the lanes run empty (PERF.md).

Tokens completed are the engine's ``tokens_emitted_total`` counter
(what ``/stats`` reports as ``tokens_total``). It is read WITHOUT the
server's lock, which a reader waits many seconds for (the first run on
the chip saw ``/stats`` wait 13 s). A poller watches the counter every
2 ms; each change is the end of an engine step, and a block runs from
one such change to the first one ``block_s`` seconds or more later, so
a block holds whole steps and its own wall time. The end-to-end rate is
the whole-window quotient: all tokens over first block's start to last
block's end. Blocks with prefill run at 40-70 tokens/s and blocks
without at 78, so the blocks' median swings with the mix; it is printed
beside the quotient and kept as a per-layer diagnostic.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from benchmarks.harness import trace as btrace
from benchmarks.harness import weights
from benchmarks.harness.result import Check, Run, emit
from benchmarks.harness.window import Block, describe, window_quotient


def model_sizes(config: dict) -> dict:
    return dict(
        vocab_size=int(config["vocab_size"]),
        seq_len=int(config["n_positions"]),
        d_model=int(config["n_embd"]), depth=int(config["n_layer"]),
    )


@dataclass
class Record:
    index: int
    due: float  # host clock
    sent: float
    done: float
    http: int
    status: str
    prompt: list
    tokens: list
    ttft_s: float | None
    decode_tokens_per_s: float | None = None


class Served:
    """The server, built the way ``scripts/serve.py`` builds it, over
    the benchmark's seeded weights. ``submit`` exists so a test can
    break the timed path."""

    def __init__(self, config: dict, seed: int):
        from ddp_tpu.models.lm import LMSpec
        from ddp_tpu.serve.engine import ServeEngine
        from ddp_tpu.serve.server import LMServer

        self.sizes = s = model_sizes(config)
        self.spec = LMSpec(
            vocab_size=s["vocab_size"], total_len=s["seq_len"],
            d_model=s["d_model"], depth=s["depth"],
            num_heads=int(config["n_head"]),
        )
        t = time.perf_counter()
        self.params = weights.make_params(seed, s)
        import jax

        jax.block_until_ready(self.params)
        self.weights_s = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = ServeEngine(self.spec, self.params, **config["engine"])
        self.engine_s = time.perf_counter() - t
        t = time.perf_counter()
        self.engine.warmup()
        self.warmup_s = time.perf_counter() - t
        self.slots = self.engine.num_slots
        self.server = LMServer(self.engine, port=0).start()
        self.submit = self.server.submit_and_wait

    def tokens_total(self) -> int:
        return int(self.engine.tokens_emitted_total)

    def gauges(self) -> dict:
        """Unlocked reads of plain host-side state."""
        e = self.engine
        return {"active": int(e.active),
                "queue_depth": int(e.scheduler.depth)}

    def stop_and_free(self):
        """Stop the server (waiting requests come back 503), then free
        the device: weights, cache, the engine itself."""
        self.server.stop()
        for leaf in _leaves(self.params):
            leaf.delete()
        self.params = self.engine = self.server = self.submit = None
        gc.collect()


def _leaves(tree):
    import jax

    return jax.tree.leaves(tree)


class Load:
    """Open-loop load: one dispatcher thread sleeps to each request's
    due time and hands it to a small pool; each worker blocks in
    ``submit`` like an HTTP handler thread would."""

    def __init__(self, served: Served, requests: list, workers: int = 96):
        self.served = served
        self.requests = requests
        self.records: list[Record] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._pool = ThreadPoolExecutor(max_workers=workers,
                                        thread_name_prefix="bench-load")
        self._thread = threading.Thread(target=self._dispatch,
                                        name="bench-dispatch", daemon=True)
        self.clock0 = 0.0

    def start(self) -> float:
        self.clock0 = time.perf_counter()
        self._thread.start()
        return self.clock0

    def _dispatch(self):
        for i, r in enumerate(self.requests):
            due = self.clock0 + r.due_s
            while not self._stop.is_set():
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                self._stop.wait(min(wait, 0.05))
            if self._stop.is_set():
                return
            self._pool.submit(self._one, i, r, due)

    def _one(self, i, r, due):
        sent = time.perf_counter()
        http, payload = self.served.submit({
            "prompt_tokens": r.prompt,
            "max_new_tokens": r.max_new_tokens,
            "temperature": 0.0,
        })
        done = time.perf_counter()
        rec = Record(
            index=i, due=due, sent=sent, done=done, http=int(http),
            status=str(payload.get("status", payload.get("error", ""))),
            prompt=r.prompt, tokens=list(payload.get("tokens", [])),
            ttft_s=payload.get("ttft_s"),
            decode_tokens_per_s=payload.get("decode_tokens_per_s"),
        )
        with self._lock:
            self.records.append(rec)

    def stop(self):
        self._stop.set()
        self._thread.join()

    def join(self):
        self._pool.shutdown(wait=True)

    def snapshot(self) -> list[Record]:
        with self._lock:
            return list(self.records)


POLL_S = 0.002
IDLE_GRACE_S = 0.5


def sample_blocks(served: Served, seconds: float, block_s: float,
                  traced: bool = False) -> list[Block]:
    """Watch the token counter until ``seconds`` have passed: one Block
    from a step's end to the first step's end ``block_s`` or more
    later (or, where the engine is idle and no step ends, to
    ``IDLE_GRACE_S`` past that). ``steps`` counts the counter's
    changes, which is the engine's steps while every step emits a
    token."""
    out: list[Block] = []
    tok = served.tokens_total()
    t_start = time.perf_counter()
    # Open the first block on a step's end too (if one comes).
    while (served.tokens_total() == tok
           and time.perf_counter() - t_start < IDLE_GRACE_S):
        time.sleep(POLL_S)
    tok = served.tokens_total()
    t_start = t0 = time.perf_counter()
    tok_start, steps = tok, 0
    while True:
        time.sleep(POLL_S)
        cur, now = served.tokens_total(), time.perf_counter()
        changed = cur != tok
        if changed:
            tok, steps = cur, steps + 1
        age = now - t_start
        if (changed and age >= block_s) or age >= block_s + IDLE_GRACE_S:
            out.append(Block(t_start, now, work=tok - tok_start,
                             steps=steps, traced=traced,
                             extra=served.gauges()))
            t_start, tok_start, steps = now, tok, 0
            if now - t0 >= seconds:
                return out


def tpot_engine_ms(rec: Record) -> float | None:
    """The engine's own time per output token for one request, from the
    answer's ``decode_tokens_per_s`` (decode seconds over tokens after
    the first, on the engine's clock)."""
    if rec.http != 200 or not rec.decode_tokens_per_s:
        return None
    return 1e3 / rec.decode_tokens_per_s


def tpot_ms(rec: Record) -> float | None:
    """Time per output token as the client sees it: from the first
    token (the server's ``ttft_s`` after the send) to the answer in
    hand, over the tokens after the first."""
    n = len(rec.tokens)
    if rec.http != 200 or n < 2 or rec.ttft_s is None:
        return None
    return (rec.done - rec.sent - rec.ttft_s) / (n - 1) * 1e3


# ---- correct -------------------------------------------------------------


def pick_checked(records: list[Record], k: int, seed: int) -> list[Record]:
    """A sample of finished requests, drawn from the seed, with the
    longest in it."""
    ok = [r for r in records if r.http == 200 and r.status == "complete"
          and len(r.tokens) >= 1]
    if not ok:
        return []
    ok.sort(key=lambda r: r.index)
    longest = max(ok, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(seed: int, config: dict, samples: list,
                   *, control: str | None = None) -> dict:
    """Run the plain reference once over each prompt with its served
    tokens. ``served_gap``: the widest gap by which a served token's
    logit lies below the reference's best. With ``control`` (a lower
    precision) also ``control_gap``: at the same positions, the gap of
    the token that precision puts first. Layer by layer, so only one
    block's activations live beside the weights."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gpt2_ref as ref

    sizes = model_sizes(config)
    heads, depth = int(config["n_head"]), sizes["depth"]
    pad_to = int(config["correct"].get("pad_to", 256))
    params = weights.make_params(seed, sizes)
    embed = jax.jit(ref.embed)
    head = jax.jit(ref.head, static_argnames="precision")
    block = jax.jit(ref.block, static_argnames=("num_heads", "precision"))

    def logits(tokens, precision):
        x = embed(params, tokens)
        for i in range(1, depth + 1):
            x = block(x, params[f"block{i}"], num_heads=heads,
                      precision=precision)
        return head(params, x, precision=precision)[0]

    # Shapes depend on the padded length only, so every seed finds
    # these programs in the cache.
    @jax.jit
    def served_gap_of(out, targets, mask):
        best = out.max(-1)
        at = jnp.take_along_axis(out, targets[:, None], -1)[:, 0]
        gap = jnp.where(mask, best - at, 0.0)
        return gap.max(), gap.sum(), (gap > 0).sum()

    @jax.jit
    def control_gap_of(out, low, mask):
        first = low.argmax(-1)
        at = jnp.take_along_axis(out, first[:, None], -1)[:, 0]
        flips = (mask & (first != out.argmax(-1))).sum()
        return jnp.where(mask, out.max(-1) - at, 0.0).max(), flips

    served_gap, control_gap, n_tokens, flips = 0.0, 0.0, 0, 0
    served_sum, served_flips = 0.0, 0
    per_request = []
    for prompt, tokens in samples:
        seq = list(prompt) + list(tokens[:-1])
        L, lo = len(seq), len(prompt) - 1
        Lp = L + (-L % pad_to)
        toks = jnp.asarray([seq + [0] * (Lp - L)], jnp.int32)
        # position j (lo <= j < L) predicts the served token j - lo
        targets = np.zeros(Lp, np.int32)
        targets[lo:L] = tokens
        mask = np.zeros(Lp, bool)
        mask[lo:L] = True
        out = logits(toks, "float32")
        g, g_sum, g_n = served_gap_of(out, targets, mask)
        g = float(g)
        per_request.append(g)
        served_gap = max(served_gap, g)
        served_sum += float(g_sum)
        served_flips += int(g_n)
        n_tokens += len(tokens)
        if control:
            cg, fl = control_gap_of(out, logits(toks, control), mask)
            control_gap = max(control_gap, float(cg))
            flips += int(fl)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    return {"served_gap": served_gap, "control_gap": control_gap,
            "tokens": n_tokens, "control_flips": flips,
            "served_flips": served_flips,
            "served_mean_gap": served_sum / max(1, n_tokens),
            "per_request": per_request}


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


# ---- one run --------------------------------------------------------------


def run(cell, args, ctx) -> Run:
    import jax

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
    if args.trace:
        with btrace.record(trace_dir, ctx.spans):
            blocks += sample_blocks(served, float(traffic["trace_s"]),
                                    block_s, traced=True)
    timed = sample_blocks(served, args.seconds, block_s)
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

    due_in = [r for r in requests
              if w0 <= clock0 + r.due_s < w1]
    by_index = {r.index: r for r in records}
    sent_in = [by_index[i] for i, r in enumerate(requests)
               if w0 <= clock0 + r.due_s < w1 and i in by_index]
    failed = [
        r for r in sent_in
        if not (r.http == 200 and r.status == "complete")
        and not (r.http == 503 and r.done >= w1)  # our own stop
    ]
    finished = [r for r in records if w0 <= r.done <= w1]
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
        "slots": served.slots,
        "compile_counts": compile_counts,
        "compiles_in_window": compiles_in_window,
        "requests_by_status": status_counts,
        "rejects": reject_counts,
        "sizes": served.sizes,
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
        Check("served_logit_gap", gaps["served_gap"] if checked
              else float("nan"), limits["served_logit_gap"],
              f"widest gap of a served token's logit below the "
              f"reference's best, {gaps['tokens']} tokens of "
              f"{len(checked)} requests"),
        Check("compiles_in_window",
              float(compiles_in_window
                    + (compile_counts != counts_before)), 0.0,
              "programs compiled or loaded inside the measured window"),
        Check("failed_requests", float(len(failed)), 0.0,
              "requests due in the window that were refused, errored "
              "or timed out"),
    ]
    run_.notes["reference_s"] = time.perf_counter() - t
    run_.notes["checked_tokens"] = gaps["tokens"]
    emit("reference_s", {"seconds": run_.notes["reference_s"],
                         "per_request_gap": gaps["per_request"]})
    return run_
