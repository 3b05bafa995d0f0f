#!/usr/bin/env python
"""One-screen triage report from a metrics JSONL stream.

    python scripts/health_report.py metrics.jsonl

Summarizes what the first five minutes of an incident actually need:
step-time p50/p99 and the input-wait fraction (is it the data
pipeline?), MFU and goodput (is the chip earning its keep?), the
grad-norm trajectory (was it diverging before it died?), anomaly
sentry events, NaN provenance, and recompile counts (shape leak?).
Reads only the JSONL the trainer always writes — works on a live
file mid-run, a dead run's tail, or a finished run.

Output is deterministic for a given input (golden-pinned in
tests/test_health.py), so it is also greppable from cron/CI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddp_tpu.utils.metrics import StatSummary  # noqa: E402


def _fmt(v, nd: int = 4) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def load_records(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                # A live (or killed) run's last line can be partial;
                # the report must still answer from the rest.
                continue
    return records


def build_report(records: list[dict]) -> str:
    steps = [r for r in records if r.get("kind") == "step"]
    epochs = [r for r in records if r.get("kind") == "epoch"]
    health = [r for r in records if r.get("kind") == "health"]
    finals = [r for r in records if r.get("kind") == "final"]

    lines = ["ddp_tpu run health report", "=" * 25]

    losses = [r["loss"] for r in steps if r.get("loss") is not None]
    lines.append(
        f"steps logged  : {len(steps)} (epochs {len(epochs)})"
    )
    if losses:
        lines.append(
            f"loss          : first {_fmt(losses[0])} -> last "
            f"{_fmt(losses[-1])} (min {_fmt(min(losses))})"
        )
        nulls = sum(1 for r in steps if r.get("loss") is None)
        if nulls:
            lines.append(
                f"                {nulls} step record(s) with "
                "non-finite loss (serialized null)"
            )
    gnorms = [
        (r.get("step"), r["grad_norm"])
        for r in steps
        if r.get("grad_norm") is not None
    ]
    if gnorms:
        peak_step, peak = max(gnorms, key=lambda t: t[1])
        lines.append(
            f"grad norm     : first {_fmt(gnorms[0][1])} -> last "
            f"{_fmt(gnorms[-1][1])} (max {_fmt(peak)} @ step {peak_step})"
        )

    # Step wall time from the attribution fields (--trace_dir runs);
    # falls back to per-epoch seconds/batches when untraced.
    times = StatSummary()
    wait = StatSummary()
    for r in steps:
        if "compute_s" in r:
            wall = (
                r.get("input_wait_s", 0.0)
                + r.get("dispatch_s", 0.0)
                + r["compute_s"]
            )
            times.add(wall)
            if wall > 0:
                wait.add(r.get("input_wait_s", 0.0) / wall)
    if times.count:
        frac = wait.snapshot().get("mean")
        lines.append(
            f"step time     : p50 {_fmt(times.percentile(50), 4)}s  "
            f"p99 {_fmt(times.percentile(99), 4)}s  "
            f"(input-wait {_fmt(100.0 * frac, 1)}%)"
        )
    elif epochs:
        per = [
            e["seconds"] / e["batches"]
            for e in epochs
            if e.get("batches")
        ]
        if per:
            lines.append(
                f"step time     : ~{_fmt(sum(per) / len(per), 4)}s "
                "mean (epoch-level; re-run with --trace_dir for "
                "per-step attribution)"
            )

    mfus = [e["mfu"] for e in epochs if e.get("mfu") is not None]
    if mfus:
        lines.append(f"mfu           : last {_fmt(mfus[-1], 6)}")
    # Prefer the final record's full goodput snapshot (it carries the
    # restart count); fall back to the latest epoch fraction mid-run.
    final_gp = finals[-1].get("goodput") if finals else None
    if isinstance(final_gp, dict):
        lines.append(
            f"goodput       : {_fmt(final_gp.get('goodput'), 6)} "
            f"({_fmt(final_gp.get('restarts'))} restart(s))"
        )
    else:
        epoch_gps = [
            e["goodput"] for e in epochs if e.get("goodput") is not None
        ]
        if epoch_gps:
            lines.append(f"goodput       : {_fmt(epoch_gps[-1], 6)}")

    # Restart/fallback triage (the fault-tolerance layer): how many
    # times the run was relaunched, and whether auto-resume ever had
    # to quarantine a corrupt checkpoint and fall back to an earlier
    # epoch ("fallback" records from the trainer's restore path).
    fallbacks = [r for r in records if r.get("kind") == "fallback"]
    restart_n = (
        final_gp.get("restarts") if isinstance(final_gp, dict) else None
    )
    if restart_n or fallbacks:
        lines.append(
            f"restarts      : {restart_n or 0} restart(s), "
            f"{len(fallbacks)} checkpoint fallback(s)"
        )
        if fallbacks:
            fb = fallbacks[-1]
            lines.append(
                f"                last fallback: epoch "
                f"{_fmt(fb.get('epoch'))} quarantined -> resumed "
                f"epoch {_fmt(fb.get('resumed_epoch'))}"
            )

    # Elastic triage (world resize): one run_start record per
    # generation carries the live world; the trajectory plus the
    # goodput sidecar's downtime split answers "did we shrink, and
    # what did the reshapes cost vs the plain crashes". Absent on
    # pre-elastic streams (no run_start records), so their reports
    # stay byte-identical.
    run_starts = [r for r in records if r.get("kind") == "run_start"]
    if run_starts:
        worlds = [
            r.get("data_shards") or r.get("world_size")
            for r in run_starts
        ]
        worlds = [int(w) for w in worlds if w]
        traj = " -> ".join(str(w) for w in worlds) if worlds else "?"
        n_resize = sum(
            1 for a, b in zip(worlds, worlds[1:]) if a != b
        )
        line = (
            f"elastic       : {len(run_starts)} generation(s), "
            f"world {traj}"
        )
        if n_resize:
            line += f" ({n_resize} resize(s))"
        if isinstance(final_gp, dict) and (
            "resize_downtime_s" in final_gp
            or "restart_downtime_s" in final_gp
        ):
            line += (
                f"; downtime resize "
                f"{_fmt(final_gp.get('resize_downtime_s', 0.0), 1)}s / "
                f"restart "
                f"{_fmt(final_gp.get('restart_downtime_s', 0.0), 1)}s"
            )
        lines.append(line)

    recompiles = sum(e.get("recompiles", 0) for e in epochs)
    if any("recompiles" in e for e in epochs):
        lines.append(f"recompiles    : {recompiles}")

    # Compiled-program triage (--xprof streams): what the run paid in
    # XLA builds — and, when a compile was a RE-compile, the culprit
    # label and shape-diff. Only printed when the stream carries
    # "compile" records, so pre-xprof reports stay byte-identical.
    compiles = [r for r in records if r.get("kind") == "compile"]
    if compiles:
        total = sum(r.get("compile_time_s") or 0.0 for r in compiles)
        by_label: dict[str, int] = {}
        for c in compiles:
            lbl = c.get("label") or "?"
            by_label[lbl] = by_label.get(lbl, 0) + 1
        detail = ", ".join(
            f"{k}: {v}" for k, v in sorted(by_label.items())
        )
        lines.append(
            f"compiles      : {len(compiles)} ({detail}), "
            f"{_fmt(total, 2)}s total"
        )
        recompiled = [c for c in compiles if c.get("shape_diff")]
        if recompiled:
            last = recompiled[-1]
            lines.append(
                f"                last recompile: {last.get('label')} "
                f"[{last.get('shape_diff')}] "
                f"{_fmt(last.get('compile_time_s'), 2)}s"
            )

    # Device-memory triage (--xprof): the high-water across the run
    # and the latest headroom (absent off-TPU — no honest limit).
    # STREAM order, not steps-then-epochs concatenation: a run that
    # finished epoch N and then OOM'd mid-epoch N+1 has its freshest
    # (lowest) headroom in step records written AFTER the epoch-N
    # record, and the "latest" value must be the last one written.
    hbm = [
        r
        for r in records
        if r.get("kind") in ("step", "epoch")
        and r.get("hbm_high_water_bytes") is not None
    ]
    if hbm:
        high = max(int(r["hbm_high_water_bytes"]) for r in hbm)
        line = f"hbm           : high-water {high:,} bytes"
        fracs = [
            r["hbm_headroom_frac"]
            for r in hbm
            if r.get("hbm_headroom_frac") is not None
        ]
        if fracs:
            line += f" (headroom {_fmt(100.0 * fracs[-1], 1)}%)"
        lines.append(line)

    # Collective-payload estimate (the ddp/zero update strategies
    # stamp it — parallel/zero.py): only printed when present, so
    # pre-zero streams keep their golden output byte-identical. The
    # hierarchical zero step additionally stamps the per-fabric split
    # (comm_bytes_ici/dcn) — rendered inline when present, so flat
    # streams (and every existing golden) stay byte-identical too.
    comm = [
        r
        for r in steps + epochs
        if r.get("comm_bytes") is not None
    ]
    if comm:
        last = comm[-1]
        line = f"comm/step     : {last['comm_bytes']:,} bytes (estimate"
        if last.get("comm_bytes_dcn") is not None:
            line += (
                f"; ici {last.get('comm_bytes_ici', 0):,} / "
                f"dcn {last['comm_bytes_dcn']:,}"
            )
        lines.append(line + ")")

    # Serve triage (ISSUE 11): user-facing latency percentiles, queue
    # wait, SLO burn and speculative acceptance — only when the stream
    # carries serve records (scripts/serve.py --metrics_file), so
    # pre-existing trainer streams stay byte-identical.
    serve_reqs = [r for r in records if r.get("kind") == "serve_request"]
    serve_steps = [r for r in records if r.get("kind") == "serve_step"]
    slo_breaches = [r for r in records if r.get("kind") == "slo_breach"]
    if serve_reqs or serve_steps or slo_breaches:
        by_status: dict[str, int] = {}
        ttft, tpot, queue = StatSummary(), StatSummary(), StatSummary()
        acc = StatSummary()
        for r in serve_reqs:
            s = r.get("status", "?")
            by_status[s] = by_status.get(s, 0) + 1
            for summ, key in (
                (ttft, "ttft_s"), (tpot, "tpot_s"), (queue, "queue_s"),
                (acc, "spec_acceptance"),
            ):
                if r.get(key) is not None:
                    summ.add(r[key])
        detail = ", ".join(
            f"{k}: {v}" for k, v in sorted(by_status.items())
        )
        steps_note = (
            f", {len(serve_steps)} engine step(s)" if serve_steps else ""
        )
        lines.append(
            f"serve         : {len(serve_reqs)} request(s)"
            + (f" ({detail})" if detail else "")
            + steps_note
        )
        for label, summ in (
            ("serve ttft", ttft), ("serve tpot", tpot),
            ("serve queue", queue),
        ):
            if summ.count:
                lines.append(
                    f"{label:<14}: p50 {_fmt(summ.percentile(50), 4)}s  "
                    f"p99 {_fmt(summ.percentile(99), 4)}s"
                )
        if acc.count:
            lines.append(
                f"spec accept   : mean "
                f"{_fmt(acc.snapshot().get('mean'), 4)} over "
                f"{acc.count} request(s)"
            )
        # Paged-KV page/prefix triage (PR 12): only when the stream
        # carries paged serve_step fields — fixed-lane streams (and
        # every pre-paging golden) stay byte-identical.
        paged_steps = [
            r for r in serve_steps if r.get("pages_free") is not None
        ]
        if paged_steps:
            last = paged_steps[-1]
            hits = sum(
                1 for r in serve_reqs if r.get("prefix_hit_tokens")
            )
            misses = sum(
                1
                for r in serve_reqs
                if r.get("prefix_hit_tokens") == 0
            )
            lines.append(
                f"pages         : free {_fmt(last.get('pages_free'))}"
                f", resident {_fmt(last.get('pages_resident'))}"
                f", shared {_fmt(last.get('pages_shared'))}; prefix "
                f"hit rate {_fmt(last.get('prefix_hit_rate'), 4)} "
                f"({hits} hit / {misses} miss)"
            )
        if slo_breaches:
            last = slo_breaches[-1]
            lines.append(
                f"slo           : {len(slo_breaches)} breach event(s), "
                f"last {last.get('objective')} burn "
                f"{_fmt(last.get('burn_rate_fast'), 1)} (fast) / "
                f"{_fmt(last.get('burn_rate_slow'), 1)} (slow)"
            )

    # Fleet triage (serve/fleet.py): the router/manager poll records
    # carry cumulative counters, so the LAST one is the fleet's
    # current shape — replicas up/draining/dead, breakers shedding,
    # replay/hedge accounting, restarts. Gated on record presence:
    # trainer and single-replica serve streams (and every existing
    # golden) stay byte-identical.
    fleet_polls = [r for r in records if r.get("kind") == "fleet_poll"]
    if fleet_polls:
        f = fleet_polls[-1]
        lines.append(
            f"fleet         : {f.get('replicas_healthy', 0)}/"
            f"{f.get('replicas', 0)} healthy"
            f", {f.get('replicas_draining', 0)} draining"
            f", {f.get('replicas_dead', 0)} dead"
            f"; breakers open {f.get('breaker_open', 0)} "
            f"({f.get('breaker_opens_total', 0)} lifetime)"
        )
        lines.append(
            f"fleet traffic : {f.get('dispatched_total', 0)} dispatched"
            f", {f.get('replays_total', 0)} replayed"
            f", hedges {f.get('hedge_wins_total', 0)}/"
            f"{f.get('hedges_total', 0)} won"
            f"; restarts {f.get('restarts_total', 0)}"
            f", rolling {f.get('rolling_restarts_total', 0)}"
        )
        # Disaggregation triage (PR 16): the migration counters ride
        # the poll record only when the router runs role-aware or
        # directory dispatch — classic fleet streams (and their
        # goldens) carry no key and print no line.
        if "migrations_total" in f or "directory_pulls_total" in f:
            pulls = f.get("directory_pulls_total", 0)
            hits = f.get("directory_pull_hits_total", 0)
            ms = f.get("migration_seconds") or {}
            lines.append(
                f"fleet disagg  : {f.get('migrations_total', 0)} "
                f"migration(s) ({f.get('pages_migrated_total', 0)} "
                f"pages, {f.get('migration_failures_total', 0)} "
                f"failed)"
                f", prefill handoffs {f.get('prefill_handoffs_total', 0)}"
                f"; directory pulls {hits}/{pulls} hit"
                + (
                    f"; migrate p50 {_fmt(ms.get('p50'), 4)}s"
                    f" p95 {_fmt(ms.get('p95'), 4)}s"
                    if ms.get("count")
                    else ""
                )
            )

    # Lifecycle triage (PR 20): one serve_reload record per /reload
    # attempt — swaps, named rejections, rollbacks, and the version
    # the engine last landed on. Gated on record presence so trainer
    # and pre-lifecycle serve streams (and their goldens) stay
    # byte-identical.
    reloads = [r for r in records if r.get("kind") == "serve_reload"]
    if reloads:
        swapped = [r for r in reloads if r.get("outcome") == "swapped"]
        rejected = [r for r in reloads if r.get("outcome") == "rejected"]
        rolled = [r for r in reloads if r.get("rolled_back")]
        line = (
            f"lifecycle     : {len(swapped)}/{len(reloads)} "
            f"reload(s) swapped"
            f", {len(rejected)} rejected"
            f", {len(rolled)} rolled back"
        )
        reasons = sorted({r.get("reason") for r in rejected if r.get("reason")})
        if reasons:
            line += f" ({', '.join(reasons)})"
        if swapped and swapped[-1].get("model_version"):
            line += f"; now {swapped[-1]['model_version']}"
        lines.append(line)

    # Fleet-trace triage (PR 19): trace_merge.py --metrics_file stamps
    # one cumulative fleet_trace record per merge, so the LAST one is
    # the freshest fleet reconstruction — requests stitched across
    # router + replica trace dirs, how many survived causal
    # validation, and which hop is the tail's bottleneck. Gated on
    # record presence so every existing golden stays byte-identical.
    fleet_traces = [r for r in records if r.get("kind") == "fleet_trace"]
    if fleet_traces:
        ft = fleet_traces[-1]
        n = ft.get("requests", 0)
        ok = ft.get("causal_ok", 0)
        frac = (ok / n) if n else 0.0
        line = (
            f"fleet trace   : {n} request(s) reconstructed, "
            f"{ok} causal-ok ({_fmt(100.0 * frac, 1)}%)"
            f"; hedged {ft.get('hedged', 0)}"
            f", migrated {ft.get('migrated', 0)}"
        )
        if ft.get("worst_hop"):
            line += (
                f"; worst hop {ft['worst_hop']} "
                f"p99 {_fmt(ft.get('worst_hop_p99_s'), 4)}s"
            )
        lines.append(line)

    # MPMD pipeline triage (parallel/mpmd.py): stage-tagged step
    # records plus the supervisor's mpmd_run/mpmd_restart stamps.
    # Gated on those markers, so SPMD trainer and serve streams (and
    # every existing golden) stay byte-identical.
    mpmd_steps = [r for r in steps if r.get("stage") is not None]
    mpmd_runs = [r for r in records if r.get("kind") == "mpmd_run"]
    mpmd_restarts = [
        r for r in records if r.get("kind") == "mpmd_restart"
    ]
    if mpmd_steps or mpmd_runs or mpmd_restarts:
        stage_ids = sorted({int(r["stage"]) for r in mpmd_steps})
        if mpmd_runs and mpmd_runs[-1].get("stages"):
            n_stages = int(mpmd_runs[-1]["stages"])
        else:
            n_stages = len(stage_ids)
        lead = [
            r
            for r in mpmd_steps
            if stage_ids
            and r.get("stage") == stage_ids[0]
            and r.get("loss") is not None
        ]
        lead.sort(key=lambda r: r.get("step", 0))
        traj = (
            f"loss {_fmt(lead[0]['loss'])} -> {_fmt(lead[-1]['loss'])}"
            if lead
            else "loss ?"
        )
        bubbles = [
            r["bubble_s"] / r["wall_s"]
            for r in mpmd_steps
            if r.get("bubble_s") is not None and r.get("wall_s")
        ]
        bub = (
            f", bubble {_fmt(100.0 * sum(bubbles) / len(bubbles), 1)}%"
            if bubbles
            else ""
        )
        n_restarts = (
            mpmd_runs[-1].get("restarts")
            if mpmd_runs and mpmd_runs[-1].get("restarts") is not None
            else len(mpmd_restarts)
        )
        lines.append(
            f"mpmd          : {n_stages} stage(s), {traj}"
            f"{bub}, {n_restarts} restart(s)"
        )

    sentry = [h for h in health if h.get("detector") != "nonfinite"]
    if sentry:
        by_det: dict[str, int] = {}
        for h in sentry:
            d = h.get("detector", "?")
            by_det[d] = by_det.get(d, 0) + 1
        detail = ", ".join(f"{k}: {v}" for k, v in sorted(by_det.items()))
        lines.append(f"anomalies     : {len(sentry)} ({detail})")
    else:
        lines.append("anomalies     : none")

    nonfinite = [h for h in health if h.get("detector") == "nonfinite"]
    if nonfinite:
        first = nonfinite[0]
        layer = first.get("layer") or "<loss only>"
        lines.append(
            f"nonfinite     : layer {layer} at step {first.get('step')}"
        )
    else:
        lines.append("nonfinite     : none")

    if finals:
        f = finals[-1]
        tail = (
            f"accuracy {_fmt(f.get('accuracy'))}  "
            f"loss {_fmt(f.get('loss'))}"
        )
        if f.get("perplexity") is not None:
            tail += f"  perplexity {_fmt(f.get('perplexity'))}"
        lines.append(f"final         : {tail}")
    else:
        lines.append("final         : (run not finished)")

    return "\n".join(lines) + "\n"


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("metrics_file", help="JSONL stream from --metrics_file")
    args = p.parse_args()
    records = load_records(args.metrics_file)
    if not records:
        raise SystemExit(f"{args.metrics_file}: no readable records")
    sys.stdout.write(build_report(records))


if __name__ == "__main__":
    main()
