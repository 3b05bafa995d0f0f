#!/usr/bin/env python
"""Fleet serving CLI: N supervised replicas behind the health-gated
router (ddp_tpu.serve.fleet; docs/SERVING.md "Fleet serving").

    python scripts/fleet.py --replicas 3 --port 8100 \
        -- --init_demo --slots 2 --page_size 16
    curl -s localhost:8100/generate -d \
        '{"prompt_tokens": [1, 2, 3], "max_new_tokens": 16}'

Everything after ``--`` is forwarded VERBATIM to every replica's
``scripts/serve.py`` (same checkpoint, same engine knobs — each
replica gets its own ``--port``). The frontend exposes:

  POST /generate   routed with prefix affinity + least-loaded spill,
                   bounded retry, optional hedging (--hedge_after),
                   per-replica circuit breakers; responses carry a
                   ``router`` digest (replica, attempts, replays,
                   hedge outcome, fleet trace id)
  GET  /healthz    fleet liveness (>= 1 dispatchable replica)
  GET  /statusz    router + manager state, plus the live
                   obs/aggregate.py fleet view scraped from members
  GET  /metricsz   linted ddp_tpu_fleet_* gauges
  POST /rollz      rolling restart: drain -> wait -> restart ->
                   re-admit, one replica at a time, zero dropped

``--roles prefill,decode,decode`` splits the fleet into a
disaggregated prefill/decode topology and ``--directory`` turns on the
fleet-global prefix tier — both ride the replicas' POST /pages
transfer plane (docs/SERVING.md "Disaggregated serving").

``--chaos "kill:replica1@request8"`` arms fleet drills
(runtime/chaos.py grammar) fired on the router's dispatch counter.
SIGTERM drains the FLEET: the frontend stops admitting (503 +
Retry-After), replicas drain their lanes, then everything exits.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8100)
    p.add_argument(
        "--workdir", default="/tmp/ddp_tpu_fleet",
        help="per-replica logs land here (replicaN.log)",
    )
    p.add_argument(
        "--max_restarts", type=int, default=2,
        help="per-replica restart budget (PR-5 semantics: classified "
        "exit, capped exponential backoff)",
    )
    p.add_argument("--restart_backoff", type=float, default=0.5)
    p.add_argument(
        "--poll_interval", type=float, default=0.25,
        help="supervision cadence: /healthz probes, breaker half-open "
        "probes, process liveness",
    )
    p.add_argument(
        "--hedge_after", type=float, default=None,
        help="tail-latency hedging: duplicate a request still "
        "unanswered after this many seconds to a second replica — "
        "first completion wins, the loser is cancelled (off by "
        "default)",
    )
    p.add_argument(
        "--retry_max", type=int, default=3,
        help="re-dispatch budget per request (connection-level "
        "failures; jittered exponential backoff between tries)",
    )
    p.add_argument("--retry_backoff", type=float, default=0.05)
    p.add_argument(
        "--breaker_threshold", type=int, default=3,
        help="consecutive failures that open a replica's circuit "
        "breaker (a refused connection opens it immediately)",
    )
    p.add_argument(
        "--breaker_cooldown", type=float, default=2.0,
        help="open -> half-open probe interval",
    )
    p.add_argument(
        "--affinity_page", type=int, default=16,
        help="prefix-affinity granularity: the prompt's leading "
        "page-aligned tokens hash to a preferred replica so its "
        "radix prefix cache stays warm (0 = least-loaded only; "
        "match the replicas' --page_size)",
    )
    p.add_argument(
        "--roles", default=None,
        help="comma-separated per-replica roles (prefill|decode|"
        "hybrid), e.g. 'prefill,decode,decode' — must name every "
        "replica. Long prompts prefill on the prefill tier, then the "
        "KV pages migrate to a decode replica over POST /pages "
        "(docs/SERVING.md 'Disaggregated serving'). Default: every "
        "replica hybrid, identical to the classic fleet",
    )
    p.add_argument(
        "--prefill_cutoff", type=int, default=64,
        help="disagg length classifier: prompts with at least this "
        "many page-aligned tokens go to the prefill tier (only "
        "meaningful with --roles)",
    )
    p.add_argument(
        "--directory", action="store_true",
        help="fleet-global prefix tier: the router remembers which "
        "replica owns each leading-page prefix and has a missing "
        "replica PULL those pages over /pages instead of "
        "re-prefilling (generalizes prefix affinity across churn)",
    )
    p.add_argument(
        "--migration_timeout", type=float, default=10.0,
        help="budget for one page migration (export + push); on "
        "expiry the router skips the migration and the target "
        "prefills locally — never a torn page set",
    )
    p.add_argument(
        "--chaos", default=None,
        help="fleet drills, e.g. 'kill:replica1@request8,"
        "stall:replica0@request4:2.5s' — fired on the router's "
        "dispatch counter (runtime/chaos.py grammar)",
    )
    p.add_argument(
        "--metrics_file", default=None,
        help="fleet_poll JSONL records (scripts/health_report.py "
        "prints the fleet triage lines from them)",
    )
    p.add_argument(
        "--trace_dir", default=None,
        help="fleet-wide distributed tracing: the router records a "
        "span per dispatch/retry/hedge/migration hop and exports to "
        "TRACE_DIR/router on drain; every replica runs with "
        "--trace_dir TRACE_DIR/replicaN --reqtrace so "
        "scripts/trace_merge.py can stitch one causal timeline per "
        "request across the fleet (docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--drain_timeout", type=float, default=30.0,
        help="SIGTERM: stop admitting at the frontend, then give "
        "replicas this long to finish lanes before the kill",
    )
    p.add_argument(
        "serve_args", nargs=argparse.REMAINDER,
        help="everything after -- goes verbatim to every replica's "
        "scripts/serve.py",
    )
    args = p.parse_args()
    serve_args = list(args.serve_args)
    if serve_args and serve_args[0] == "--":
        serve_args = serve_args[1:]
    if any(a in ("--port", "--host") for a in serve_args):
        raise SystemExit(
            "replica --port/--host are manager-assigned; drop them "
            "from the forwarded serve args"
        )

    from ddp_tpu.serve.fleet import (
        ROLE_HYBRID,
        ROLES,
        FleetChaos,
        FleetServer,
        ReplicaManager,
        Router,
        RouterConfig,
    )
    from ddp_tpu.utils.metrics import MetricsWriter

    roles = None
    if args.roles:
        roles = [r.strip() for r in args.roles.split(",")]
        bad = [r for r in roles if r not in ROLES]
        if bad:
            raise SystemExit(
                f"unknown role(s) {bad}; pick from {list(ROLES)}"
            )
        if len(roles) != args.replicas:
            raise SystemExit(
                f"--roles names {len(roles)} replicas but "
                f"--replicas is {args.replicas}"
            )
    metrics = MetricsWriter(args.metrics_file)
    manager = ReplicaManager(
        args.replicas,
        serve_args,
        workdir=args.workdir,
        max_restarts=args.max_restarts,
        restart_backoff=args.restart_backoff,
        poll_interval=args.poll_interval,
        metrics=metrics,
        roles=roles,
        trace_dir=args.trace_dir,
    )
    tracer = None
    if args.trace_dir:
        from ddp_tpu.obs.tracer import Tracer

        tracer = Tracer(enabled=True)
    config = RouterConfig(
        retry_max=args.retry_max,
        retry_backoff_s=args.retry_backoff,
        hedge_after_s=args.hedge_after,
        affinity_page=args.affinity_page,
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown,
        trace_seed=int.from_bytes(os.urandom(8), "little"),
        disagg=bool(roles) and any(r != ROLE_HYBRID for r in roles),
        prefill_cutoff_tokens=args.prefill_cutoff,
        directory=args.directory,
        migration_timeout_s=args.migration_timeout,
    )
    stop_event = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop_event.set())
    chaos = FleetChaos(args.chaos, manager) if args.chaos else None
    try:
        manager.start()
        router = manager.attach_router(
            Router(
                manager.replicas,
                config,
                on_dispatch=chaos.on_dispatch if chaos else None,
                tracer=tracer,
            )
        )
        healthy = manager.wait_healthy()
        with FleetServer(
            manager, router, host=args.host, port=args.port,
            chaos=chaos,
        ) as server:
            print(
                json.dumps(
                    {
                        "fleet": server.url,
                        "metricsz": server.url + "/metricsz",
                        "replicas": [
                            r.url for r in manager.replicas
                        ],
                        "all_healthy": healthy,
                        "hedge_after": args.hedge_after,
                        "affinity_page": args.affinity_page,
                        **({"roles": roles} if roles else {}),
                        **(
                            {"directory": True}
                            if args.directory else {}
                        ),
                        **(
                            {"chaos": args.chaos} if args.chaos else {}
                        ),
                        **(
                            {"trace_dir": args.trace_dir}
                            if args.trace_dir else {}
                        ),
                    }
                ),
                flush=True,
            )
            try:
                stop_event.wait()
            except KeyboardInterrupt:
                pass
            # Fleet-wide drain: frontend first (new admissions get
            # 503 + Retry-After), then the members finish their lanes
            # inside manager.stop(drain_timeout) below.
            server.begin_drain()
            print(
                json.dumps({"draining": True}), flush=True
            )
    finally:
        manager.stop(drain_timeout=args.drain_timeout)
        # Router trace exports after the members stop so the drain's
        # final hop spans (503s, cancelled hedges) are in the file;
        # an unwritable dir must not mask the metrics close below.
        if tracer is not None:
            try:
                path = tracer.export_to_dir(
                    os.path.join(args.trace_dir, "router")
                )
                print(json.dumps({"router_trace": path}), flush=True)
            except OSError as e:
                print(
                    json.dumps({"router_trace_error": str(e)}),
                    file=sys.stderr, flush=True,
                )
        metrics.close()


if __name__ == "__main__":
    main()
