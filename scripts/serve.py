#!/usr/bin/env python
"""Serving CLI: load the latest LM checkpoint and answer traffic.

    python scripts/serve.py --checkpoint_dir ./checkpoints --port 8000
    curl -s localhost:8000/generate -d \
        '{"prompt_tokens": [1, 2, 3], "max_new_tokens": 32}'

Restores the checkpoint template-free (train/checkpoint.py
``restore_for_inference`` — no optimizer construction), recovers the
architecture from the parameter shapes plus the ``lm_spec.json``
sidecar the trainer writes (num_heads, MoE routing config), and
stands up the continuous-batching engine (ddp_tpu.serve) behind a
stdlib HTTP frontend. ``--metrics_file`` streams serve_step /
serve_request JSONL records through utils/metrics.MetricsWriter.

``--init_demo`` skips the checkpoint and serves a randomly
initialized model — a frontend/ops smoke path that needs no training
run (and no checkpoint libraries) at all.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--checkpoint_dir", default="./checkpoints")
    p.add_argument("--epoch", type=int, default=None, help="default: latest")
    p.add_argument(
        "--num_heads", type=int, default=4,
        help="fallback when the checkpoint has no lm_spec.json sidecar",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument(
        "--slots", type=int, default=4,
        help="decode batch lanes (static — the serving batch shape)",
    )
    p.add_argument(
        "--prefill_len", type=int, default=None,
        help="max admissible prompt length (default total_len/2)",
    )
    p.add_argument(
        "--prefill_chunk", type=int, default=None,
        help="chunked-prefill width (rounded to a power of two; "
        "default min(pow2(prefill_len), 64)) — prompts are ingested "
        "in chunks co-scheduled with decode steps",
    )
    p.add_argument(
        "--min_bucket", type=int, default=None,
        help="smallest power-of-two bucket for the final partial "
        "chunk (default min(8, prefill_chunk); clamped so the "
        "smallest bucket always fits total_len - prefill_len + 1) — "
        "short prompts pay bucket-sized compute, not "
        "prefill_len-sized",
    )
    p.add_argument(
        "--step_token_budget", type=int, default=None,
        help="max prefill-chunk tokens + decode tokens dispatched "
        "per engine step (default prefill_chunk + slots)",
    )
    p.add_argument(
        "--admit_every", type=int, default=0,
        help="while any lane runs, bind at most one queued request "
        "to a lane every N engine steps (0: refill every free lane at "
        "once). Spreads lanes that would otherwise stay in step for "
        "ever under requests of one length; set a little under "
        "(steps a request takes) / slots",
    )
    p.add_argument(
        "--no_warmup", action="store_true",
        help="skip eager compilation of the engine program set "
        "(first requests then pay the XLA compiles)",
    )
    p.add_argument("--max_queue", type=int, default=64)
    p.add_argument("--metrics_file", default=None)
    p.add_argument(
        "--trace_dir", default=None,
        help="span-trace prefill/refill/decode (ddp_tpu.obs): serves "
        "the live tail at /statusz and exports a Perfetto "
        "trace_event JSON here on shutdown",
    )
    p.add_argument(
        "--trace_ring_events", type=int, default=65536,
        help="bounded trace memory: keep the last N events",
    )
    p.add_argument(
        "--trace_rank", type=int, default=0,
        help="tracer process id: names the exported file "
        "(trace_rank{N}.trace.json) and scopes span pairing in a "
        "merged fleet document — the fleet manager assigns each "
        "replica a distinct rank so scripts/trace_merge.py never "
        "cross-pairs two replicas' spans under one trace id",
    )
    p.add_argument(
        "--drain_timeout", type=float, default=30.0,
        help="SIGTERM graceful drain: stop admitting (503 + "
        "Retry-After), let running lanes finish up to this many "
        "seconds, then exit cleanly",
    )
    p.add_argument(
        "--reqtrace", action="store_true",
        help="per-request distributed tracing (ddp_tpu.obs.reqtrace): "
        "every request gets a 64-bit trace id at admission, its "
        "lifecycle (admit -> queue -> prefill chunks -> spec rounds "
        "-> decode -> retire) is reconstructable at /requestz?id=... "
        "and exported as Perfetto async spans under --trace_dir; "
        "completions carry a .trace digest",
    )
    p.add_argument(
        "--slo", default=None, metavar="SPEC",
        help="declarative serving objectives evaluated live over "
        "rolling 5m/1h windows with burn-rate alerting, e.g. "
        "'ttft_p99<0.5s,tpot_p50<80ms,availability>0.999' — state on "
        "/statusz, ddp_tpu_slo_* gauges on /metricsz, breach events "
        "into the metrics stream and the flight recorder",
    )
    p.add_argument(
        "--flight_dir", default=None,
        help="flight-recorder directory (ddp_tpu.obs.recorder): SLO "
        "breach events ride the bounded ring and the dump lands here "
        "on shutdown (flight_rank0.json)",
    )
    p.add_argument(
        "--sanitize", action="store_true",
        help="arm jax.transfer_guard('disallow') around the decode "
        "dispatch: any implicit host transfer in the hot loop raises "
        "instead of silently stalling (the runtime half of "
        "scripts/lint.py; docs/ANALYSIS.md)",
    )
    p.add_argument(
        "--xprof", action="store_true",
        help="compiled-program introspection (ddp_tpu.obs.xprof): the "
        "engine's program set dispatches through a compile ledger "
        "(XLA FLOPs/memory per executable), /metricsz gains compile "
        "and HBM gauges, and /stats carries the full ledger",
    )
    p.add_argument(
        "--decode_attn", default="auto",
        choices=["auto", "flash", "reference"],
        help="single-query decode attention (ops/decode.py): 'flash' "
        "is the Pallas flash-decode kernel (compiled Mosaic on TPU, "
        "interpreter elsewhere), 'reference' the bit-identical jnp "
        "path; 'auto' picks flash on TPU only",
    )
    p.add_argument(
        "--kv_dtype", default="fp32", choices=["fp32", "int8"],
        help="KV-cache storage: 'int8' quantizes on write (per-head "
        "scales, dequantize at the compute site) — cache HBM per "
        "slot drops ~2.7x, so a chip fits more --slots",
    )
    p.add_argument(
        "--page_size", type=int, default=0,
        help="paged KV + radix prefix cache (serve/pages.py): KV "
        "lives in a pool of this-many-token pages and prompts "
        "sharing a prefix prefill it once and fork the pages "
        "copy-free (power of two dividing total_len; 0 = the "
        "fixed-lane cache)",
    )
    p.add_argument(
        "--kv_pages", type=int, default=None,
        help="page-pool size for --page_size (default: slots x "
        "total_len/page_size + 1 scratch — capacity-neutral vs "
        "fixed lanes; smaller pools lean on prefix sharing, "
        "admission waits on free pages)",
    )
    p.add_argument(
        "--spec_tokens", type=int, default=0,
        help="speculative decoding: draft-propose this many greedy "
        "tokens per lane per round, verified in ONE target step "
        "(0 = off; needs --draft_checkpoint_dir, or --init_demo "
        "which synthesizes a smaller draft)",
    )
    p.add_argument(
        "--draft_checkpoint_dir", default=None,
        help="checkpoint of the DRAFT LM for --spec_tokens (its own "
        "lm_spec.json sidecar; must share vocab and total_len with "
        "the target)",
    )
    p.add_argument(
        "--role", default=None,
        choices=["prefill", "decode", "hybrid"],
        help="disaggregated-serving role (docs/SERVING.md): 'prefill' "
        "replicas take long prompts and ship the prefilled KV pages "
        "to a decode replica over POST /pages; 'decode' replicas "
        "receive pages and run the steady decode batch; 'hybrid' "
        "(and the default, no role at all) is the classic co-located "
        "engine. The role is advertised on /healthz + /statusz for "
        "the fleet router — the engine itself is identical; the "
        "ROUTER enforces who gets which traffic",
    )
    p.add_argument(
        "--model", action="append", default=None, metavar="NAME=DIR",
        help="register an EXTRA named model from its own checkpoint "
        "dir (repeatable): requests carrying model=NAME route to its "
        "own engine — own scheduler, slots and pages, so per-model "
        "accounting is structural. POST /reload with model=NAME "
        "hot-swaps it independently of the default model",
    )
    p.add_argument(
        "--streaming_restore", action="store_true",
        help="layer-streamed startup (serve/lifecycle.py): restore "
        "the checkpoint on a background thread in residency order "
        "while the main thread compiles the program set — admission "
        "opens once the embedding + first --stream_layers blocks are "
        "resident (requests queue), the full tree installs through "
        "the hot-swap path when the deep layers land. Cold = restore "
        "THEN warmup; streaming = max(restore, warmup)",
    )
    p.add_argument(
        "--stream_layers", type=int, default=1,
        help="--streaming_restore admission gate: open the front "
        "door once the embedding + this many leading blocks are "
        "resident",
    )
    p.add_argument(
        "--init_demo", action="store_true",
        help="serve a freshly initialized tiny LM (no checkpoint)",
    )
    p.add_argument(
        "--vocab_size", type=int, default=256,
        help="--init_demo model vocabulary",
    )
    p.add_argument(
        "--seq_len", type=int, default=128,
        help="--init_demo model context length",
    )
    args = p.parse_args()

    from ddp_tpu.models.lm import LMSpec, init_lm
    from ddp_tpu.obs.startup import startup_line
    from ddp_tpu.obs.tracer import Tracer, get_tracer
    from ddp_tpu.obs.xprof import Xprof
    from ddp_tpu.runtime.dist import enable_compile_cache
    from ddp_tpu.serve.engine import ServeEngine
    from ddp_tpu.serve.server import LMServer
    from ddp_tpu.utils.metrics import MetricsWriter

    # Before the first compile: a restart then reloads the engine's
    # program set instead of rebuilding it.
    compile_cache = enable_compile_cache()

    # Streaming restore (lifecycle PR): epoch + spec come from
    # checkpoint METADATA (no tensor read), the weights stream in on a
    # background thread while warmup compiles over same-shaped init
    # params, and the real tree installs through the hot-swap path.
    streaming = None
    model_version = None
    t_weights = time.perf_counter()  # → ``startup.weights``, below
    if args.init_demo:
        spec = LMSpec(
            vocab_size=args.vocab_size, total_len=args.seq_len,
            num_heads=args.num_heads,
        )
        params = init_lm(spec, seed=0)
        epoch = -1
    elif args.streaming_restore:
        from ddp_tpu.serve.lifecycle import StreamingRestore

        try:
            streaming = StreamingRestore(
                args.checkpoint_dir,
                epoch=args.epoch,
                first_blocks=args.stream_layers,
                num_heads_fallback=args.num_heads,
            )
        except (FileNotFoundError, ValueError, KeyError) as e:
            raise SystemExit(
                f"checkpoint in {args.checkpoint_dir}: {e}"
            )
        spec = streaming.spec
        epoch = streaming.epoch
        model_version = streaming.version
        # Shape-true zeros, not a random init: warmup only needs the
        # shapes, and the real weights are already streaming in.
        params = streaming.placeholder_params()
        streaming.start()
    else:
        from ddp_tpu.serve.lifecycle import model_version_token
        from ddp_tpu.train.checkpoint import (
            CheckpointManager,
            derive_spec_with_sidecar,
        )

        mgr = CheckpointManager(args.checkpoint_dir)
        params, _, epoch = mgr.restore_for_inference(args.epoch)
        mgr.close()
        try:
            spec = derive_spec_with_sidecar(
                args.checkpoint_dir, params,
                num_heads_fallback=args.num_heads,
            )
        except ValueError as e:
            raise SystemExit(
                f"checkpoint in {args.checkpoint_dir}: {e}"
            )
        model_version = model_version_token(args.checkpoint_dir, epoch)

    # Speculative decoding's draft model: a real (smaller) checkpoint
    # with its own lm_spec.json, or — under --init_demo — a freshly
    # initialized half-width sibling so the demo/CI path exercises
    # the draft/verify machinery with no training run at all.
    draft_spec = draft_params = None
    if args.spec_tokens:
        if args.draft_checkpoint_dir:
            from ddp_tpu.train.checkpoint import (
                CheckpointManager,
                derive_spec_with_sidecar,
            )

            dmgr = CheckpointManager(args.draft_checkpoint_dir)
            draft_params, _, _ = dmgr.restore_for_inference(None)
            dmgr.close()
            try:
                draft_spec = derive_spec_with_sidecar(
                    args.draft_checkpoint_dir, draft_params,
                    num_heads_fallback=args.num_heads,
                )
            except ValueError as e:
                raise SystemExit(
                    f"draft checkpoint in {args.draft_checkpoint_dir}: "
                    f"{e}"
                )
        elif args.init_demo:
            draft_spec = spec._replace(
                d_model=max(16, spec.d_model // 2),
                depth=max(1, spec.depth // 2),
            )
            draft_params = init_lm(draft_spec, seed=1)
        else:
            raise SystemExit(
                "--spec_tokens needs --draft_checkpoint_dir (or "
                "--init_demo, which synthesizes a draft)"
            )

    metrics = MetricsWriter(args.metrics_file)
    tracer = Tracer(
        enabled=bool(args.trace_dir),
        ring_events=args.trace_ring_events,
        process_id=args.trace_rank,
        # One account of the process's start: the imports and compiles
        # kept in the process-global tracer, this one's phases with them.
        kept_with=get_tracer(),
    )
    # Parameters (and the draft's) restored or initialised.
    tracer.phase_complete(
        "startup.weights", t_weights, time.perf_counter() - t_weights
    )
    # SLO engine + flight recorder (ISSUE 11): objectives evaluated
    # live inside the serving process; breach events land in the
    # metrics stream and the recorder ring (dumped on shutdown so a
    # post-mortem sees them even when nobody scraped /metricsz).
    from ddp_tpu.obs.recorder import FlightRecorder, build_info, snapshot_env
    from ddp_tpu.obs.slo import SLOEngine, parse_model_slos

    # ``--slo`` may carry per-model groups ("clauses;name:clauses"):
    # each registered model gets its OWN SLOEngine over its own
    # engine's observations. The bare single-group form parses to
    # {None: spec} — pre-lifecycle behavior, byte-identical.
    try:
        model_slos = parse_model_slos(args.slo) if args.slo else {}
    except ValueError as e:
        raise SystemExit(f"--slo: {e}")
    for name in model_slos:
        if name is not None and name not in {
            m.partition("=")[0] for m in (args.model or [])
        }:
            raise SystemExit(
                f"--slo names model {name!r} but no --model "
                f"{name}=DIR registers it"
            )
    slo = (
        SLOEngine(model_slos[None]) if model_slos.get(None) else None
    )
    recorder = FlightRecorder(args.flight_dir)
    recorder.set_context(
        build_info=build_info(), env=snapshot_env(),
        slo=args.slo, role="serve",
    )
    engine = ServeEngine(
        spec,
        params,
        slots=args.slots,
        prefill_len=args.prefill_len,
        prefill_chunk=args.prefill_chunk,
        min_bucket=args.min_bucket,
        step_token_budget=args.step_token_budget,
        admit_every=args.admit_every,
        max_queue=args.max_queue,
        metrics=metrics,
        tracer=tracer,
        sanitize=args.sanitize,
        xprof=Xprof(enabled=args.xprof),
        decode_attn=args.decode_attn,
        kv_dtype=args.kv_dtype,
        page_size=args.page_size,
        kv_pages=args.kv_pages,
        draft_spec=draft_spec,
        draft_params=draft_params,
        spec_tokens=args.spec_tokens,
        reqtrace=args.reqtrace,
        slo=slo,
        recorder=recorder,
        model_version=model_version,
    )
    if streaming is not None:
        # No lane may bind to init weights: admission stays paused
        # (requests queue) until the streamed tree installs below.
        engine.pause_admission()
    if not args.no_warmup:
        # Compile the bounded program set (one chunk program per
        # bucket width + decode) before the first request arrives:
        # first-request TTFT is then a decode step, not an XLA build.
        # Under --streaming_restore this is exactly the work the
        # restore I/O overlaps.
        engine.warmup()
    # Extra named models (--model NAME=DIR): each an independent
    # engine over its own restored checkpoint — own scheduler, slots
    # and page pool; ``model=NAME`` requests route to it.
    models = {}
    for entry in args.model or []:
        name, _, mdir = entry.partition("=")
        if not name or not mdir:
            raise SystemExit(f"--model wants NAME=DIR, got {entry!r}")
        if name in models:
            raise SystemExit(f"--model {name!r} registered twice")
        from ddp_tpu.serve.lifecycle import model_version_token
        from ddp_tpu.train.checkpoint import (
            CheckpointManager,
            derive_spec_with_sidecar,
        )

        mmgr = CheckpointManager(mdir)
        mparams, _, mepoch = mmgr.restore_for_inference(None)
        mmgr.close()
        try:
            mspec = derive_spec_with_sidecar(
                mdir, mparams, num_heads_fallback=args.num_heads
            )
        except ValueError as e:
            raise SystemExit(f"--model {name}: checkpoint in {mdir}: {e}")
        models[name] = ServeEngine(
            mspec,
            mparams,
            slots=args.slots,
            admit_every=args.admit_every,
            max_queue=args.max_queue,
            metrics=metrics,
            kv_dtype=args.kv_dtype,
            page_size=args.page_size,
            kv_pages=args.kv_pages,
            slo=(
                SLOEngine(model_slos[name])
                if model_slos.get(name)
                else None
            ),
            model_version=model_version_token(mdir, mepoch),
        )
        if not args.no_warmup:
            models[name].warmup()
    # Graceful drain on SIGTERM (the preemption signal): the handler
    # only sets an event; the main thread wakes, stops admitting
    # (503 + Retry-After), waits for running lanes up to
    # --drain_timeout, and exits through the normal telemetry-flush
    # path below. Installed before serving so a reclaim racing
    # startup still drains.
    import signal
    import threading

    stop_event = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop_event.set())
    try:
        with LMServer(
            engine, host=args.host, port=args.port, role=args.role,
            models=models,
        ) as server:
            if streaming is not None:
                # The front door opens at the ADMISSION milestone —
                # embedding + first --stream_layers blocks resident —
                # not at full residency; queued requests dispatch the
                # moment the full tree installs below.
                streaming.wait_admission()
            print(
                json.dumps(
                    {
                        "serving": server.url,
                        # Scrape target: Prometheus text exposition of
                        # the live engine counters (obs/promtext.py).
                        "metricsz": server.url + "/metricsz",
                        "epoch": epoch,
                        "slots": engine.num_slots,
                        "prefill_len": engine.prefill_len,
                        "prefill_chunk": engine.prefill_chunk,
                        "buckets": engine.buckets,
                        "step_token_budget": engine.step_token_budget,
                        "total_len": spec.total_len,
                        "vocab_size": spec.vocab_size,
                        "compile_counts": engine.compile_counts(),
                        "decode_attn": engine.decode_attn,
                        # compiled Mosaic, the Pallas interpreter, or
                        # plain XLA: picked from the platform, so said.
                        "decode_kernel": engine.decode_kernel,
                        # chunked prefill is always the dense masked
                        # einsum (models/generate.prefill_chunk)
                        "prefill_attn": "dense",
                        "kv_dtype": engine.kv_dtype,
                        "cache_bytes_per_slot":
                            engine.cache_bytes_per_slot(),
                        "spec_tokens": engine.spec_tokens,
                        **(
                            {"paged": engine.page_stats()}
                            if engine.paged
                            else {}
                        ),
                        "build_info": build_info(),
                        "compile_cache": compile_cache,
                        **({"role": args.role} if args.role else {}),
                        "reqtrace": bool(args.reqtrace),
                        **({"slo": args.slo} if args.slo else {}),
                        **(
                            {"model_version": model_version}
                            if model_version
                            else {}
                        ),
                        **(
                            {"models": sorted(models)} if models else {}
                        ),
                        **(
                            {
                                "streaming_restore": {
                                    "admission_ready_s":
                                        streaming.admission_ready_s,
                                    "admission_group":
                                        streaming.admission_group,
                                }
                            }
                            if streaming is not None
                            else {}
                        ),
                    }
                ),
                flush=True,
            )
            # The socket listens: what the process did on its way here.
            print(json.dumps({"startup": startup_line(tracer)}), flush=True)
            if streaming is not None:
                # Full residency → install through the hot-swap path
                # (same barrier, same validation) and open the lanes.
                # A failed stream is fatal — serving init weights is
                # never an option.
                full = streaming.wait(timeout=600.0)
                with server._lock:
                    engine.install_params(
                        full, model_version=streaming.version
                    )
                    engine.resume_admission()
                print(
                    json.dumps(
                        {
                            "streamed": True,
                            "admission_ready_s":
                                streaming.admission_ready_s,
                            "complete_s": streaming.complete_s,
                        }
                    ),
                    flush=True,
                )
            try:
                stop_event.wait()  # serve until SIGTERM (or ctrl-C)
            except KeyboardInterrupt:
                pass
            if stop_event.is_set():
                drained = server.drain(args.drain_timeout)
                print(
                    json.dumps(
                        {
                            "draining": True,
                            "drained": drained,
                            "drain_timeout": args.drain_timeout,
                        }
                    ),
                    flush=True,
                )
    finally:
        # Short sessions must keep their telemetry tail: the span
        # trace exports on the way out (crash-safe tmp+rename) and
        # the JSONL stream is flushed/closed explicitly rather than
        # trusting interpreter teardown ordering. An unwritable
        # trace_dir must not turn a clean shutdown into a crash (or
        # skip the metrics close below).
        if args.trace_dir:
            try:
                # Any request spans whose retire fell outside a traced
                # window (or that never emitted) ride the export too.
                engine.emit_request_spans()
                path = tracer.export_to_dir(args.trace_dir)
                print(json.dumps({"trace": path}), flush=True)
            except OSError as e:
                print(
                    json.dumps({"trace_error": str(e)}),
                    file=sys.stderr, flush=True,
                )
        # The flight recorder's ring (SLO breach events included)
        # lands on disk even for a clean exit — a breach that paged
        # nobody must still be findable post-hoc. dump() never raises.
        dump = recorder.dump("shutdown")
        if dump:
            print(json.dumps({"flight": dump}), flush=True)
        metrics.close()


if __name__ == "__main__":
    main()
