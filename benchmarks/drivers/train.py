"""Driver for ``kind: train`` cells: the program's ``Trainer``, built the
normal way, driven through the same hot loop as ``Trainer._train_epoch``
(loader fetch, ``trainer.train_step``, at most ``MAX_INFLIGHT_STEPS`` in
flight), with the benchmark's weights and token rows put in its place.

One object — the compiled step with its state — is built in set-up,
driven from the seed through its first (checked) steps, and handed to
the window. No eval, no checkpoint and no metrics flush is ever called.
"""

from __future__ import annotations

import gc
import itertools
import os
import statistics
import time
from collections import deque

import numpy as np

from benchmarks.harness import trace as btrace
from benchmarks.harness import weights
from benchmarks.harness.result import Check, Run, emit
from benchmarks.harness.window import Block, describe, window_quotient

ADAM_B1 = 0.9  # optax.adam's default, which the program uses


def model_sizes(config: dict) -> dict:
    tc = config["train_config"]
    return dict(
        vocab_size=int(tc["vocab_size"]), seq_len=int(tc["seq_len"]),
        d_model=int(tc["model_dim"]), depth=int(tc["model_depth"]),
    )


# ---- small jitted readers of the program's state ------------------------


def _leaf_norms(tree):
    import jax.numpy as jnp

    return {
        p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for p, x in weights.flatten(tree).items()
    }


def _delta_norms(params, key):
    """Per-leaf norm of (params - the seeded initial weights); the
    initial leaves are regenerated inside the program, fused into the
    reduction, so no second copy of the model is ever held."""
    import jax.numpy as jnp

    return {
        p: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - weights.leaf(key, p, x.shape)
        )))
        for p, x in weights.flatten(params).items()
    }


def _adam_mu(opt_state):
    for part in opt_state if isinstance(opt_state, tuple) else (opt_state,):
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam first moment in the optimizer state")


# ---- the reference's side ------------------------------------------------


def reference_readings(seed: int, config: dict, batches: list,
                       *, precision: str = "float32") -> dict:
    """The plain reference through the same first steps: per-step loss,
    per-leaf norm of the first gradient, per-leaf norm of the
    parameters' change after the last step."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gpt2_ref as ref

    sizes = model_sizes(config)
    tc = config["train_config"]
    heads, depth = int(tc["num_heads"]), sizes["depth"]
    row_block = int(config["correct"].get("row_block", 1))
    adam = ref.AdamRef(float(tc["lr"]))
    key = weights.seed_key(seed)

    @jax.jit
    def loss_grad_norms(params, toks):
        loss, g = ref.loss_and_grad_rows(
            params, toks, num_heads=heads, depth=depth,
            precision=precision, row_block=row_block,
        )
        return loss, g, _leaf_norms(g)

    update = jax.jit(adam.update, donate_argnums=(0, 2))

    params = weights.make_params(seed, sizes)
    opt = jax.jit(adam.init)(params)
    losses, gnorms = [], None
    for i, toks in enumerate(batches):
        loss, g, norms = loss_grad_norms(params, jnp.asarray(toks))
        if i == 0:  # the first gradient is the one compared
            gnorms = {p: float(v) for p, v in norms.items()}
        losses.append(float(loss))
        params, opt = update(params, g, opt)
    dn = jax.jit(_delta_norms)(params, key)
    dnorms = {p: float(v) for p, v in dn.items()}
    for leaf in jax.tree.leaves((params, opt)):
        leaf.delete()
    return {"losses": losses, "grad_norms": gnorms, "delta_norms": dnorms}


def worst_leaf_gap(prog: dict, ref: dict) -> tuple[float, str]:
    """max over leaves of | ||prog|| - ||ref|| | over the larger of the
    reference's norm of that leaf and of the median leaf."""
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for p, r in ref.items():
        gap = abs(prog[p] - r) / max(r, floor, 1e-30)
        if gap != gap:  # NaN: nothing compares, so it decides
            return float("nan"), p
        if gap > worst:
            worst, where = gap, p
    return float(worst), where


def compare(prog: dict, ref: dict, limits: dict) -> list[Check]:
    loss_rel = max(
        abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])
    )
    g, g_at = worst_leaf_gap(prog["grad_norms"], ref["grad_norms"])
    d, d_at = worst_leaf_gap(prog["delta_norms"], ref["delta_norms"])
    return [
        Check("loss_rel", loss_rel, limits["loss_rel"],
              "worst |loss - ref| / ref over the checked steps: "
              f"{prog['losses']} vs {ref['losses']}"),
        Check("grad_leaf_rel", g, limits["grad_leaf_rel"],
              f"first gradient's norm, worst leaf ({g_at})"),
        Check("delta_leaf_rel", d, limits["delta_leaf_rel"],
              f"parameters' change after the checked steps, worst leaf "
              f"({d_at})"),
    ]


def control(cell, seed: int, out_dir: str) -> dict:
    """The control of ``correct``: the reference put in the program's
    place, computed in the precision below the one the configuration
    states, through the same comparison. Has to come out not correct.
    Needs no program: the rows are the cell's own from the seed."""
    config = cell.config
    tc = config["train_config"]
    sizes = model_sizes(config)
    gb = int(tc["batch_size"]) * cell.chips
    rows = cell.generator().generate(
        cell.traffic, seed=seed, vocab_size=sizes["vocab_size"],
        seq_len=sizes["seq_len"], global_batch=gb,
    )
    n = int(cell.traffic["checked_steps"])
    batches = [rows[i * gb:(i + 1) * gb] for i in range(n)]
    sound = reference_readings(seed, config, batches)
    low = reference_readings(
        seed, config, batches, precision=config["precision"]["control"]
    )
    checks = compare(low, sound, config["correct"]["limits"])
    return {"seed": seed, "precision": config["precision"]["control"],
            "checks": [c.to_json() for c in checks],
            "correct": all(c.ok for c in checks)}


def readings(cell, seeds: list, ctx) -> list:
    """Sound runs and controls over many seeds in ONE set-up, for
    setting limits: the program's first checked steps from each seed
    (new weights, zeroed optimizer state and new rows through the same
    compiled step), the reference, and the control, each compared with
    the reference."""
    import jax
    import jax.numpy as jnp

    h = Harnessed(cell, seeds[0], cell.chips, ctx.on_tpu, ctx.out_dir,
                  ctx.spans)
    tr = h.trainer
    n = int(cell.traffic["checked_steps"])
    limits = cell.config["correct"]["limits"]
    p_shard = jax.tree.map(lambda x: x.sharding, tr.state.params)
    o_shard = jax.tree.map(lambda x: x.sharding, tr.state.opt_state)
    o_shape = jax.eval_shape(lambda: tr.state.opt_state)
    zeros = jax.jit(
        lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), o_shape),
        out_shardings=o_shard,
    )
    out = []
    for k, seed in enumerate(seeds):
        if k:
            tr.state = tr.state._replace(
                params=weights.make_params(seed, h.sizes,
                                           out_shardings=p_shard),
                opt_state=zeros(),
            )
            h.load_rows(cell, seed)
        prog, batches = h.checked_steps(n, seed)
        # The reference needs the room; the next seed rebuilds these.
        for leaf in jax.tree.leaves((tr.state.params, tr.state.opt_state)):
            leaf.delete()
        sound = reference_readings(seed, cell.config, batches)
        low = reference_readings(
            seed, cell.config, batches,
            precision=cell.config["precision"]["control"])
        row = {
            "seed": seed,
            "sound": {c.name: c.value for c in compare(prog, sound, limits)},
            "control": {c.name: c.value
                        for c in compare(low, sound, limits)},
        }
        emit("readings", row)
        out.append(row)
    return out


# ---- the program's side --------------------------------------------------


class Harnessed:
    """The Trainer with the benchmark's weights and rows, and the hot
    loop. ``step_fn`` exists so a test can break the timed path."""

    def __init__(self, cell, seed: int, chips: int, on_tpu: bool,
                 out_dir: str, spans):
        import jax

        t = time.perf_counter()
        from ddp_tpu.train.config import TrainConfig
        from ddp_tpu.train.trainer import Trainer

        self.spans = spans
        self.split = {"imports_s": time.perf_counter() - t}
        t = time.perf_counter()
        tc = dict(cell.config["train_config"])
        self.sizes = model_sizes(cell.config)
        global_batch = int(tc["batch_size"]) * chips
        cfg = TrainConfig(
            **tc, seed=int(seed) & 0x7FFFFFFF, num_devices=chips,
            backend="tpu" if on_tpu else "cpu",
            synthetic_data=True, synthetic_size=global_batch,
            eval_every=0,
            checkpoint_dir=os.path.join(out_dir, "never_written"),
        )
        self.trainer = tr = Trainer(cfg)
        jax.block_until_ready(tr.state.params)
        self.split["trainer_s"] = time.perf_counter() - t
        t = time.perf_counter()
        shard = jax.tree.map(lambda x: x.sharding, tr.state.params)
        tr.state = tr.state._replace(params=weights.make_params(
            seed, self.sizes, out_shardings=shard
        ))
        jax.block_until_ready(tr.state.params)
        self.split["weights_s"] = time.perf_counter() - t
        self.tokens_per_step_per_chip = (
            int(tc["batch_size"]) * self.sizes["seq_len"]
        )
        self.inflight: deque = deque()
        self.losses: list = []
        self.load_rows(cell, seed)
        self.step_fn = lambda state, toks, lbls: tr.train_step(
            state, toks, lbls
        )

    def load_rows(self, cell, seed: int) -> None:
        """The benchmark's rows from the seed, behind the program's own
        ``ShardedLoader``, in shuffled epochs without end."""
        from ddp_tpu.data.loader import ShardedLoader

        tr = self.trainer
        rows = cell.generator().generate(
            cell.traffic, seed=seed, vocab_size=self.sizes["vocab_size"],
            seq_len=self.sizes["seq_len"],
            global_batch=tr.global_batch_size,
        )
        tr.loader = ShardedLoader(
            rows, np.zeros(len(rows), np.int32), tr.mesh,
            tr.global_batch_size, shuffle=tr.config.shuffle,
            seed=int(seed) & 0x7FFFFFFF,
        )
        self.feed = itertools.chain.from_iterable(
            tr.loader.epoch(e) for e in itertools.count()
        )
        self.losses.clear()

    def one_step(self, keep_tokens: list | None = None):
        import jax

        tr = self.trainer
        with self.spans.span("bench.loader_fetch"):
            batch = next(self.feed)
        if keep_tokens is not None:
            keep_tokens.append(np.asarray(batch.images))
        with self.spans.span("bench.dispatch"):
            tr.state, m = self.step_fn(tr.state, batch.images, batch.labels)
        self.losses.append(m.loss)
        self.inflight.append(m.loss)
        if len(self.inflight) > tr.MAX_INFLIGHT_STEPS:
            jax.block_until_ready(self.inflight.popleft())

    def fence(self):
        import jax

        with self.spans.span("bench.fence"):
            jax.block_until_ready(self.losses[-1])
            self.inflight.clear()

    def checked_steps(self, n: int, seed: int) -> tuple[dict, list]:
        """The first ``n`` steps, through ``one_step``: what the
        reference will be held against, and the rows they saw."""
        import jax

        batches: list = []
        gnorms = None
        for i in range(n):
            self.one_step(keep_tokens=batches)
            if i == 0:
                mu = _adam_mu(self.trainer.state.opt_state)
                gn = jax.jit(_leaf_norms)(mu)
                gnorms = {
                    p: float(v) / (1.0 - ADAM_B1) for p, v in gn.items()
                }
        self.fence()
        dn = jax.jit(_delta_norms)(
            self.trainer.state.params, weights.seed_key(seed)
        )
        prog = {
            "losses": [float(x) for x in self.losses[:n]],
            "grad_norms": gnorms,
            "delta_norms": {p: float(v) for p, v in dn.items()},
        }
        return prog, batches

    def free(self):
        import jax

        for leaf in jax.tree.leaves(self.trainer.state):
            leaf.delete()
        self.trainer.loader.close()


def run(cell, args, ctx) -> Run:
    import jax

    traffic = cell.traffic
    split = {"backend_up_s": ctx.backend_up_s}
    t = time.perf_counter()
    h = Harnessed(cell, args.seed, cell.chips, ctx.on_tpu, ctx.out_dir,
                  ctx.spans)
    if ctx.break_path:
        ctx.break_path(h)
    split["state_s"] = time.perf_counter() - t
    split.update(h.split)

    t = time.perf_counter()
    n_checked = int(traffic["checked_steps"])
    prog, batches = h.checked_steps(n_checked, args.seed)
    split["first_steps_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(int(traffic["warm_steps"])):
        h.one_step()
    h.fence()
    split["warm_steps_s"] = time.perf_counter() - t
    split.update(ctx.ledger.snapshot())
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx.t0
    programs_before = ctx.ledger.programs
    n_setup_steps = len(h.losses)

    # ---- the measured window --------------------------------------------
    block_steps = int(traffic["block_steps"])
    work = block_steps * h.tokens_per_step_per_chip
    trace_blocks = int(traffic.get("trace_blocks", 1)) if args.trace else 0
    h.spans.enabled = bool(args.trace)
    h.spans.reset()
    blocks: list[Block] = []
    trace_dir = os.path.join(ctx.out_dir, "trace")

    def one_block(traced: bool):
        b0 = time.perf_counter()
        for _ in range(block_steps):
            h.one_step()
        h.fence()
        blocks.append(Block(b0, time.perf_counter(), work,
                            steps=block_steps, traced=traced))

    if trace_blocks:
        with btrace.record(trace_dir, h.spans):
            for _ in range(trace_blocks):
                one_block(True)
    start = time.perf_counter()
    while True:
        one_block(False)
        if blocks[-1].end - start >= args.seconds:
            break
    timed = [b for b in blocks if not b.traced]
    # Each block starts where the last ended, so the quotient — the
    # end-to-end reading — is all the window's work over all its wall
    # time, whatever ran between two blocks included.
    for prev, nxt in zip(timed, timed[1:]):
        nxt.start = prev.end

    losses = np.asarray(jax.device_get(h.losses[n_setup_steps:]))
    peak = ctx.memory_peak()
    compiles_in_window = ctx.ledger.programs - programs_before
    gc.unfreeze()

    run_ = Run(cell=cell)
    run_.blocks = blocks
    run_.window = describe(timed, "tokens/s/chip")
    run_.attempted = int(losses.size)
    run_.failed = int((~np.isfinite(losses)).sum())
    run_.end_to_end = {
        "train_tokens_per_s_per_chip": window_quotient(timed),
        "setup_s": setup_s,
    }
    run_.setup_split = split
    run_.spans = h.spans
    run_.counters = {
        "steps": int(losses.size),
        "timed_steps": block_steps * len(timed),
        "tokens_per_step_per_chip": h.tokens_per_step_per_chip,
        "compiles_in_window": compiles_in_window,
        "sizes": h.sizes,
        "chips": cell.chips,
    }
    run_.device = {"memory_peak_bytes": peak}
    if trace_blocks:
        run_.trace = btrace.load(trace_dir)
        run_.counters["traced_steps"] = block_steps * trace_blocks

    # ---- correct: outside set-up and window, program's state freed ------
    t = time.perf_counter()
    h.free()
    del h
    gc.collect()
    ref = reference_readings(args.seed, cell.config, batches)
    run_.checks = compare(prog, ref, cell.config["correct"]["limits"])
    run_.checks.append(Check(
        "compiles_in_window", float(compiles_in_window), 0.0,
        "programs compiled or loaded inside the measured window",
    ))
    run_.notes["reference_s"] = time.perf_counter() - t
    emit("reference_s", {"seconds": run_.notes["reference_s"]})
    return run_
