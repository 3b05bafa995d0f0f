"""Stdlib-HTTP frontend for the serve engine.

One background thread drives ``ServeEngine.step()`` whenever work is
pending; HTTP handler threads only change the engine under the same
lock (the engine is deliberately single-threaded — slots and cache are
one device program's state). ``/stats`` and ``/metricsz`` only READ
plain host-side counters and summaries and do so without that lock:
the loop holds it for every whole step, and a scrape must not wait
behind the decode loop. No web framework: ``http.server`` is in
every container this repo targets, and the API is three routes:

  POST /generate   {"prompt_tokens": [...], "max_new_tokens": N,
                    "temperature"?, "top_p"?, "seed"?, "timeout"?,
                    "model"?: registered model name (multi-model
                    serving; absent → the default model)}
                   → 200 {"rid", "status", "tokens", "ttft_s", ...}
                   → 429 {"error": "queue_full"} + ``Retry-After``
                     (the measured queue-drain ETA) on backpressure
                   → 400 {"error": "prompt_too_long" | ...} on
                     permanently-invalid requests
                   → 400 on malformed bodies
                   → 503 {"error": "draining"} + ``Retry-After``
                     while the server drains for shutdown
  GET  /healthz    → 200 {"ok": true, "slots": S, ...} (liveness)
  GET  /stats      → 200 engine.stats() (TTFT/throughput summaries,
                    compile counts — the static-shape invariant is an
                    OBSERVABLE, not a comment)
  GET  /statusz    → 200 {"ok", "stats", "trace", "startup",
                    "build_info"} —
                    stats (including mergeable summary states, SLO
                    state when --slo is set, and build provenance)
                    plus the live span-trace tail (``.trace`` is a
                    loadable Perfetto traceEvents document) and the
                    engine's goodput snapshot (ddp_tpu.obs); what the
                    fleet aggregator (scripts/obs_aggregate.py)
                    scrapes
  GET  /metricsz   → 200 Prometheus text exposition of the live
                    counters/summaries (TTFT/TPOT/queue-wait,
                    occupancy, rejects, SLO burn gauges, build info,
                    goodput — obs/promtext.py), so runs are
                    scrapeable without parsing JSONL
  POST /reload     {"checkpoint_dir": D, "epoch"?, "model"?,
                    "drain_timeout"?} — verified atomic hot-swap
                    (serve/lifecycle.py): verify the incoming
                    checkpoint (manifest CRCs + spec) BEFORE touching
                    device state, restore host-side while the old
                    model keeps serving, drain lanes to a barrier,
                    swap under the lock, roll back on any failure.
                   → 200 {"reloaded", "model_version", ...}
                   → 409 {"error": "manifest_missing" |
                     "crc_mismatch" | "spec_skew", "detail"} — named
                     rejections, old model untouched
                   → 500 load_failed / swap_failed (rolled_back)
                   → 503 swap_drain_timeout (lanes never retired)
  GET  /requestz?id=RID|0xTRACEID
                   → 200 one request's full lifecycle timeline
                    (admit → queue → prefill chunks → spec rounds →
                    decode → retire; obs/reqtrace.py); without ?id=,
                    the recently retired requests. 404 on unknown
                    ids; requires the engine's request tracing
                    (scripts/serve.py --reqtrace)

The handler blocks until its request completes (simple request/
response serving); queue position and slot availability decide
latency. Backpressure is visible: an admission rejection returns
immediately with the scheduler's reason.
"""

from __future__ import annotations

import time

_IMPORT_T0 = time.perf_counter()  # → ``startup.import``, at the last line

import json
import math
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ddp_tpu.obs.tracer import imported
from ddp_tpu.serve.engine import ServeEngine
from ddp_tpu.serve.scheduler import QUEUE_FULL

# Engine-loop idle poll; the loop burns no CPU when no work is queued.
_IDLE_SLEEP_S = 0.002


class LMServer:
    """Engine + driver thread + ThreadingHTTPServer, lifecycle-managed.

    ``port=0`` binds an ephemeral port (tests); ``server.port`` is the
    bound one. Use as a context manager or call ``start()``/``stop()``.
    """

    def __init__(
        self,
        engine: ServeEngine,
        *,
        host: str = "127.0.0.1",
        port: int = 0,
        drain_retry_after: float = 5.0,
        role: Optional[str] = None,
        models: Optional[dict] = None,
    ):
        # Disaggregated-serving role (PR 16): "prefill" | "decode" |
        # "hybrid" advertised on /healthz and /statusz so the fleet
        # router and aggregator can group by tier. None (the default,
        # and the only value single-replica setups ever see) keeps
        # every surface byte-identical to the pre-disagg server.
        self.role = role
        self.engine = engine
        # The engine's tracer (the process-global one unless the engine
        # was handed another): ``server.request`` lands beside the
        # engine's own spans.
        self.tracer = engine.tracer
        # Multi-model serving (lifecycle tentpole): extra NAMED
        # engines, each with its own scheduler/slots/pages — per-model
        # accounting by construction. ``model=`` in a /generate body
        # routes here; absent routes to the default engine. Empty
        # (every pre-lifecycle setup) keeps all surfaces byte-
        # identical: no ``models`` key anywhere.
        self.models: dict = dict(models or {})
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._draining = threading.Event()
        # Advertised in the 503 Retry-After header while draining: a
        # well-behaved client re-resolves (the replacement process) and
        # retries after this many seconds.
        self.drain_retry_after = float(drain_retry_after)
        self._engine_error: Optional[str] = None
        # One reload at a time (non-blocking: a second POST /reload
        # while one runs answers 409 reload_in_progress rather than
        # queueing swaps).
        self._reload_lock = threading.Lock()
        handler = _make_handler(self)
        self._httpd = ThreadingHTTPServer((host, port), handler)
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._threads: list[threading.Thread] = []

    # ---- lifecycle --------------------------------------------------

    def start(self) -> "LMServer":
        for name, target in (
            ("serve-engine", self._engine_loop),
            ("serve-http", self._httpd.serve_forever),
        ):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        for t in self._threads:
            t.join(timeout=5)

    # ---- graceful drain ---------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def begin_drain(self) -> None:
        """Stop ADMITTING (new POSTs get 503 + Retry-After) while
        already-running lanes keep decoding to completion. Idempotent;
        visible on /healthz, /statusz and as the ``_draining`` gauge
        on /metricsz."""
        self._draining.set()

    def drain(self, timeout: float = 30.0, *, poll: float = 0.01) -> bool:
        """``begin_drain`` + wait for in-flight work to finish.

        The SIGTERM shutdown path (scripts/serve.py): a preempted
        serving process answers its running requests instead of
        killing them, bounded by ``timeout`` (a preemption grace
        window is finite). Returns True when the engine went idle,
        False when the timeout expired with lanes still running —
        either way the caller should exit afterwards.
        """
        self.begin_drain()
        deadline = time.monotonic() + max(0.0, timeout)
        while time.monotonic() < deadline:
            with self._lock:
                idle = not any(e.pending for e in self._engines())
            if idle or self._engine_error is not None:
                return True
            time.sleep(poll)
        with self._lock:
            return not any(e.pending for e in self._engines())

    def __enter__(self) -> "LMServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # ---- engine driving ---------------------------------------------

    def _engines(self):
        """Every engine this server drives: default first, then the
        named multi-model extras in registration order."""
        return [self.engine, *self.models.values()]

    def _engine_loop(self) -> None:
        # An exception escaping step() (device OOM, runtime error) must
        # not kill this daemon thread SILENTLY: waiters would poll
        # forever and /healthz would keep answering ok. Record it, flip
        # health, and fail in-flight requests fast instead. Multi-model
        # engines step round-robin under the one lock — the device is
        # one program's state at a time either way.
        try:
            while not self._stop.is_set():
                busy = False
                for eng in self._engines():
                    with self._lock:
                        if eng.pending:
                            busy = True
                            eng.step()
                if not busy:
                    time.sleep(_IDLE_SLEEP_S)
        except Exception as e:  # noqa: BLE001 — terminal, reported
            self._engine_error = f"{type(e).__name__}: {e}"

    def submit_and_wait(
        self, body: dict, *, poll: float = 0.002
    ) -> tuple[int, dict]:
        """The POST /generate implementation → (http_status, payload).

        Also the in-process frontend: callers embedding the engine
        (tests, bench) can use it without a socket.
        """
        try:
            prompt = list(body["prompt_tokens"])
            max_new = int(body["max_new_tokens"])
            temperature = float(body.get("temperature", 0.0))
            top_p = float(body.get("top_p", 1.0))
            seed = int(body.get("seed", 0))
            timeout = float(body["timeout"]) if "timeout" in body else None
            # Fleet trace context + staged hop seconds (ISSUE 19):
            # optional, router-injected; a malformed context is the
            # engine's orphan-counting problem, never a 400.
            trace = body.get("trace")
            hops = body.get("hops")
            if hops is not None and not isinstance(hops, dict):
                hops = None
        except (KeyError, TypeError, ValueError):
            return 400, {
                "error": "body needs prompt_tokens (list[int]) and "
                "max_new_tokens (int); temperature/top_p/seed/timeout "
                "must be numeric"
            }
        # Multi-model routing: a named model goes to its own engine
        # (own scheduler/slots/pages); absent → the default engine. An
        # unknown name is a permanent client error, and the answer
        # lists what IS registered — misrouting to the default model
        # would silently serve the wrong weights.
        model = body.get("model")
        if model is not None:
            if model not in self.models:
                return 400, {
                    "error": "unknown_model",
                    "model": model,
                    "models": sorted(self.models),
                }
            engine = self.models[model]
        else:
            engine = self.engine
        if self._engine_error is not None:
            return 500, {"error": f"engine failed: {self._engine_error}"}
        if self._draining.is_set():
            # Draining: admitted work finishes, new work goes to the
            # replacement process. retry_after_s rides the JSON too so
            # in-process callers (no HTTP headers) see it.
            return 503, {
                "error": "draining",
                "retry_after_s": self.drain_retry_after,
            }
        # The engine loop holds the lock for every whole step and takes
        # it again at once, so this wait can be long, and it is in none
        # of the engine's own numbers (``ttft_s`` and ``queue_s`` start
        # at ``submit``). Each request sums its own waits and reports
        # them once, at hand-back, as ``server.request``.
        t_call = time.perf_counter()
        with self._lock:
            lock_wait_s = time.perf_counter() - t_call
            adm = engine.submit(
                prompt,
                max_new,
                temperature=temperature,
                top_p=top_p,
                seed=seed,
                timeout=timeout,
                trace=trace,
                hops=hops,
                model=model,
                record_blocks=bool(body.get("record_blocks", False)),
                record_selection=bool(body.get("record_selection", False)),
            )
        if not adm.accepted:
            # Only queue_full is transient (retry-after-backoff
            # semantics); the validation reasons are permanent client
            # errors — a 429 would invite retry loops on requests that
            # can never be served.
            if adm.reason == QUEUE_FULL:
                # Backpressure carries WHEN to come back, like the
                # drain path's 503 does: the queue-drain-rate ETA
                # (bounded to a sane header range), falling back to
                # the static drain hint before any retire window
                # exists. In the JSON too, for in-process callers.
                with self._lock:
                    eta = engine.queue_drain_eta_s()
                retry_after = (
                    min(60.0, max(1.0, eta))
                    if eta is not None
                    else self.drain_retry_after
                )
                return 429, {
                    "error": adm.reason,
                    "retry_after_s": round(retry_after, 2),
                }
            return 400, {"error": adm.reason}
        rid = adm.request.rid
        poll_wait_s = 0.0
        while True:
            t_poll = time.perf_counter()
            with self._lock:
                poll_wait_s += time.perf_counter() - t_poll
                done = engine.pop_result(rid)
                if done is not None:
                    # Finish (the engine's clock) to the answer in this
                    # thread's hand. Fed under the lock: StatSummary is
                    # not thread-safe.
                    pickup_s = max(0.0, engine.clock() - done.finished)
                    engine.lock_wait.add(lock_wait_s)
                    engine.pickup.add(pickup_s)
            if done is not None:
                self.tracer.complete(
                    "server.request", t_call,
                    time.perf_counter() - t_call,
                    nums=(rid, lock_wait_s, pickup_s, poll_wait_s),
                )
                break
            if self._engine_error is not None:
                return 500, {"error": f"engine failed: {self._engine_error}"}
            if self._stop.is_set():
                return 503, {"error": "server stopping"}
            time.sleep(poll)
        return 200, {
            "rid": done.rid,
            "status": done.status,
            "prompt_tokens": done.prompt,
            "tokens": done.tokens,
            # null for requests that never produced a token (queue
            # timeout / rejected at refill) — not a fake queue-wait.
            "ttft_s": round(done.ttft, 4) if done.ttft is not None
            else None,
            "decode_tokens_per_s": round(done.decode_tokens_per_s, 2),
            # Paged engines only (absent otherwise): how many prompt
            # tokens were served from cached pages — the signal a
            # disaggregated fleet's migration really landed (PR 16).
            **(
                {"prefix_hit_tokens": done.prefix_hit_tokens}
                if done.prefix_hit_tokens is not None
                else {}
            ),
            # A model that generates by blocks, asked with
            # ``record_blocks``: every forward's (pos, tokens, mask).
            **(
                {"block_inputs": done.block_inputs}
                if done.block_inputs is not None
                else {}
            ),
            # A model that selects its keys, asked with
            # ``record_selection``: the last token's step's rows.
            **(
                {"selected_rows": done.selected_rows}
                if done.selected_rows is not None
                else {}
            ),
            # Which model version served this request (absent on
            # engines that never loaded a versioned checkpoint — the
            # pre-lifecycle payload is byte-identical). The swap
            # drills read it to prove zero requests ever saw a torn
            # model.
            **(
                {"model_version": engine.model_version}
                if engine.model_version is not None
                else {}
            ),
            # Adoption echo (ISSUE 19): present ONLY when the request
            # carried a VALID inbound trace context — the router reads
            # it to count propagated-vs-orphaned. Requests without a
            # context (every pre-fleet-tracing client) see the exact
            # pre-PR payload.
            **(self._trace_echo(body.get("trace"))),
        }

    @staticmethod
    def _trace_echo(trace) -> dict:
        from ddp_tpu.obs.reqtrace import (
            format_trace_id,
            parse_trace_context,
        )

        ctx = parse_trace_context(trace) if trace is not None else None
        return (
            {"trace_id": format_trace_id(ctx[0])} if ctx else {}
        )

    # ---- verified atomic hot-swap (serve/lifecycle.py) --------------

    def reload_model(
        self, body: dict, *, poll: float = 0.005
    ) -> tuple[int, dict]:
        """The POST /reload implementation → (http_status, payload).

        verify → load → drain-to-barrier → swap → (rollback), with the
        old model serving until the instant of the swap and again
        after any failure — a reload can be slow, but it can never be
        torn. Verification and the host-side restore run OUTSIDE the
        engine lock (requests keep flowing); only the final barrier +
        pointer swap hold it, and only once ``active == 0``.
        """
        directory = body.get("checkpoint_dir")
        if not isinstance(directory, str) or not directory:
            return 400, {"error": "body needs checkpoint_dir (str)"}
        try:
            epoch = (
                int(body["epoch"]) if body.get("epoch") is not None
                else None
            )
            drain_timeout = float(body.get("drain_timeout", 30.0))
        except (TypeError, ValueError):
            return 400, {"error": "epoch/drain_timeout must be numeric"}
        model = body.get("model")
        if model is not None:
            if model not in self.models:
                return 400, {
                    "error": "unknown_model",
                    "model": model,
                    "models": sorted(self.models),
                }
            engine = self.models[model]
        else:
            engine = self.engine
        if self._engine_error is not None:
            return 500, {"error": f"engine failed: {self._engine_error}"}
        if not self._reload_lock.acquire(blocking=False):
            return 409, {"error": "reload_in_progress"}
        try:
            return self._reload_locked(
                engine, directory, epoch, drain_timeout, poll
            )
        finally:
            self._reload_lock.release()

    def _reload_locked(
        self, engine, directory, epoch, drain_timeout, poll
    ) -> tuple[int, dict]:
        from ddp_tpu.serve import lifecycle as lc

        def record(outcome: str, **fields) -> None:
            engine.metrics.write(
                "serve_reload", outcome=outcome, directory=directory,
                **fields,
            )

        # Stage 1 — verify, host-side, before anything else: manifest
        # present, CRCs intact, spec exactly the serving spec. A
        # rejection names its reason and device state was never
        # touched.
        t0 = time.monotonic()
        try:
            target = lc.verify_reload_target(
                directory,
                epoch=epoch,
                current_spec=engine.spec,
                num_heads_fallback=engine.spec.num_heads,
            )
        except lc.ReloadRejected as e:
            record("rejected", reason=e.reason)
            return 409, {"error": e.reason, "detail": e.detail}
        verify_s = round(time.monotonic() - t0, 4)

        # Stage 2 — restore to host. The old model keeps serving; a
        # failed read here (I/O error, torn file the manifest missed)
        # aborts with nothing installed.
        t0 = time.monotonic()
        try:
            new_params = lc.load_reload_target(target)
        except Exception as e:  # noqa: BLE001 — named in the payload
            record("load_failed", model_version=target.version)
            return 500, {
                "error": "load_failed",
                "detail": f"{type(e).__name__}: {e}",
            }
        load_s = round(time.monotonic() - t0, 4)

        # Stage 3 — drain lanes to the barrier. Admission pauses (new
        # work queues, NOTHING is dropped) while bound lanes decode to
        # completion; the swap happens in the same lock hold that
        # observes active == 0, so no lane can bind in between.
        t_swap = time.monotonic()
        with self._lock:
            engine.pause_admission()
        deadline = time.monotonic() + max(0.0, drain_timeout)
        try:
            while True:
                with self._lock:
                    if engine.active == 0:
                        return self._swap_at_barrier(
                            engine, target, new_params, t_swap,
                            verify_s, load_s, record,
                        )
                if time.monotonic() > deadline:
                    record("drain_timeout", model_version=target.version)
                    return 503, {
                        "error": "swap_drain_timeout",
                        "detail": f"lanes still bound after "
                        f"{drain_timeout}s",
                    }
                time.sleep(poll)
        finally:
            # Whatever happened — swap, rollback, timeout — the front
            # door reopens; paused admission must never outlive the
            # reload that paused it.
            with self._lock:
                engine.resume_admission()

    def _swap_at_barrier(
        self, engine, target, new_params, t_swap, verify_s, load_s,
        record,
    ) -> tuple[int, dict]:
        """Install under the already-held barrier lock hold; roll back
        to the old references on ANY failure. Caller holds self._lock
        with ``engine.active == 0``."""
        previous = engine.model_version
        invalidate = previous != target.version
        old_params = engine.params
        try:
            engine.install_params(
                new_params,
                model_version=target.version,
                invalidate_prefix=invalidate,
            )
        except Exception as e:  # noqa: BLE001 — rolled back, reported
            engine.params = old_params
            engine.model_version = previous
            engine.rollbacks_total += 1
            record(
                "swap_failed",
                model_version=target.version,
                rolled_back=True,
            )
            return 500, {
                "error": "swap_failed",
                "rolled_back": True,
                "detail": f"{type(e).__name__}: {e}",
            }
        swap_s = round(time.monotonic() - t_swap, 4)
        record(
            "swapped",
            model_version=target.version,
            **({"previous_version": previous} if previous else {}),
            epoch=target.epoch,
            verify_s=verify_s,
            load_s=load_s,
            swap_s=swap_s,
            invalidated_prefix=invalidate,
        )
        return 200, {
            "reloaded": True,
            "model_version": target.version,
            "previous_version": previous,
            "epoch": target.epoch,
            "verify_s": verify_s,
            "load_s": load_s,
            "swap_s": swap_s,
            "invalidated_prefix": invalidate,
        }

    def snapshot(self, route: str) -> Optional[dict | str]:
        """Route → JSON-ready dict, Prometheus text (str), or None."""
        if route == "/healthz":
            with self._lock:
                return {
                    "ok": self._engine_error is None,
                    "slots": self.engine.num_slots,
                    "active": self.engine.active,
                    "queue_depth": self.engine.scheduler.depth,
                    "draining": self.draining,
                    **({"role": self.role} if self.role else {}),
                    # Serving model version (absent until a versioned
                    # checkpoint loads): what the fleet's poll loop
                    # reads so the router never routes a ``model=``
                    # request to a not-yet-swapped replica, and what
                    # the reload loop's convergence check compares.
                    **(
                        {"model_version": self.engine.model_version}
                        if self.engine.model_version is not None
                        else {}
                    ),
                    **(
                        {
                            "models": {
                                name: {
                                    **(
                                        {"model_version": e.model_version}
                                        if e.model_version is not None
                                        else {}
                                    ),
                                    "slots": e.num_slots,
                                    "active": e.active,
                                    "queue_depth": e.scheduler.depth,
                                }
                                for name, e in self.models.items()
                            }
                        }
                        if self.models
                        else {}
                    ),
                    **(
                        {"engine_error": self._engine_error}
                        if self._engine_error
                        else {}
                    ),
                }
        if route == "/stats":
            return self._stats_unlocked(include_ledger=True)
        if route == "/metricsz":
            # Prometheus text, not JSON, from the same stats()
            # snapshot /stats serves.
            from ddp_tpu.obs.promtext import render_serve

            return render_serve(
                self._stats_unlocked(),
                up=self._engine_error is None,
                draining=self.draining,
            )
        if route == "/statusz":
            # Live observability snapshot (ddp_tpu.obs): operational
            # stats + goodput (inside engine.stats()) plus the tail of
            # the span trace — the ``trace`` value is itself a valid
            # Chrome/Perfetto ``traceEvents`` document, so
            # ``curl .../statusz | jq .trace > t.json`` loads directly.
            # include_states=True: the latency summaries' mergeable
            # StatSummary states ride along so a fleet aggregator
            # (obs/aggregate.py) merges EXACTLY instead of averaging
            # percentiles.
            # What the process did before its first step: the kept
            # records, still here when the ring below has turned over
            # many times. They have a lock of their own: read outside
            # the one the engine loop steps under.
            startup = self.tracer.startup_snapshot()
            with self._lock:
                return {
                    "ok": self._engine_error is None,
                    "draining": self.draining,
                    **({"role": self.role} if self.role else {}),
                    "stats": self.engine.stats(include_states=True),
                    # Named multi-model engines, each with its own full
                    # stats block (absent when none are registered).
                    **(
                        {
                            "models": {
                                name: e.stats(include_states=True)
                                for name, e in self.models.items()
                            }
                        }
                        if self.models
                        else {}
                    ),
                    "trace": self.tracer.snapshot(limit=512),
                    "startup": startup,
                }
        return None

    def _stats_unlocked(self, **kw) -> dict:
        """``engine.stats()`` WITHOUT the lock the engine loop holds
        for every whole step (a locked read has waited 13 s on the
        chip). Reads only: counters, gauges and copies of bounded
        summaries, each a single atomic read under the interpreter
        lock, so a snapshot may straddle a step (one field a step
        newer than another) but never blocks or corrupts. A container
        that changes size mid-copy raises RuntimeError: read again."""
        for _ in range(8):
            try:
                return self.engine.stats(**kw)
            except RuntimeError:
                continue
        with self._lock:
            return self.engine.stats(**kw)

    # ---- disaggregated serving: the /pages transfer plane (PR 16) ---

    def pages_export(self, body: dict) -> tuple[int, "dict | bytes"]:
        """POST /pages/export: {"prompt_tokens": [...]} → the longest
        cached prefix of that prompt as one binary page frame
        (serve/disagg.py), or 404 when no full page of it is cached
        here (prefix_not_found — the puller falls back to a local
        prefill). 409 on non-paged engines: a fleet whose members
        disagree about paging is a config error worth naming."""
        try:
            prompt = [int(t) for t in body["prompt_tokens"]]
        except (KeyError, TypeError, ValueError):
            return 400, {"error": "body needs prompt_tokens (list[int])"}
        if not self.engine.paged:
            return 409, {"error": "not_paged"}
        # Optional fleet trace context: rides into the DPKV header so
        # the migration's install side sees the same trace id (absent
        # in the body → absent in the header → pre-PR wire bytes).
        trace = body.get("trace")
        if not isinstance(trace, str):
            trace = None
        with self._lock:
            buf = self.engine.export_prefix(prompt, trace=trace)
        if buf is None:
            return 404, {"error": "prefix_not_found"}
        return 200, buf

    def pages_install(self, raw: bytes) -> tuple[int, dict]:
        """POST /pages: one binary page frame → validate, adopt into
        the radix index, copy the missing pages into the pool
        (engine.install_prefix). A frame that fails validation gets a
        400 with the named reason and NOTHING is installed — the
        torn-page-set guarantee. A pool that cannot host the pages
        answers 409 pool_exhausted (the sender just skips the
        migration; the request replays from the prompt)."""
        from ddp_tpu.serve.disagg import PageWireError, decode_pages

        if self._engine_error is not None:
            return 500, {"error": f"engine failed: {self._engine_error}"}
        try:
            frame = decode_pages(raw)
        except PageWireError as e:
            return 400, {"error": e.reason, "detail": str(e)}
        try:
            with self._lock:
                res = self.engine.install_prefix(frame)
        except PageWireError as e:
            return 400, {"error": e.reason, "detail": str(e)}
        if res is None:
            return 409, {"error": "pool_exhausted"}
        # Echo the frame's trace context (gated on its presence, like
        # /generate's echo) so the pushing router can confirm the
        # context survived the DPKV round trip.
        return 200, {
            "installed": True,
            **res,
            **self._trace_echo(frame.trace),
        }

    def requestz(self, query: str) -> tuple[int, dict]:
        """GET /requestz[?id=...] → (status, payload): one request's
        reconstructed lifecycle timeline (obs/reqtrace.py), or the
        recently retired set when no id is given."""
        from urllib.parse import parse_qs

        if self.engine._reqtrace is None:
            return 404, {
                "error": "request tracing is off (scripts/serve.py "
                "--reqtrace, or ServeEngine(reqtrace=True))"
            }
        params = parse_qs(query or "")
        key = (params.get("id") or [None])[0]
        with self._lock:
            if key is None:
                return 200, {
                    "enabled": True,
                    "live": self.engine._reqtrace.live_count,
                    "recent": self.engine._reqtrace.recent(),
                }
            timeline = self.engine.request_timeline(key)
        if timeline is None:
            return 404, {
                "error": f"unknown request {key!r} (rid or 0x-prefixed "
                "trace id; retired timelines are retained up to the "
                "reqtrace_keep bound)"
            }
        return 200, timeline


def _make_handler(server: LMServer):
    class Handler(BaseHTTPRequestHandler):
        # Quiet: request logging goes through metrics, not stderr.
        def log_message(self, fmt, *args):  # noqa: D102
            pass

        def _send_text(
            self, status: int, text: str, ctype: str,
            headers: Optional[dict] = None,
        ) -> None:
            data = text.encode()
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def _send(
            self, status: int, payload: dict,
            headers: Optional[dict] = None,
        ) -> None:
            self._send_text(
                status, json.dumps(payload), "application/json", headers
            )

        def do_GET(self):  # noqa: N802
            route, _, query = self.path.partition("?")
            if route == "/requestz":
                status, payload = server.requestz(query)
                self._send(status, payload)
                return
            payload = server.snapshot(route)
            if payload is None:
                self._send(404, {"error": f"no route {self.path}"})
            elif isinstance(payload, str):
                # /metricsz: Prometheus text exposition, not JSON.
                from ddp_tpu.obs.promtext import CONTENT_TYPE

                self._send_text(200, payload, CONTENT_TYPE)
            else:
                # A dead engine must fail status-code liveness probes
                # (`curl -f /healthz`), not just flip a JSON field.
                status = 503 if payload.get("ok") is False else 200
                self._send(status, payload)

        def do_POST(self):  # noqa: N802
            if self.path == "/pages":
                # Binary page frame (serve/disagg.py), NOT JSON — the
                # payload is raw K/V bytes with its own header + CRC.
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n)
                status, payload = server.pages_install(raw)
                self._send(status, payload)
                return
            if self.path not in ("/generate", "/pages/export", "/reload"):
                self._send(404, {"error": f"no route {self.path}"})
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(body, dict):
                    raise ValueError("body must be a JSON object")
            except (ValueError, TypeError) as e:
                self._send(400, {"error": f"bad JSON body: {e}"})
                return
            if self.path == "/reload":
                status, payload = server.reload_model(body)
                self._send(status, payload)
                return
            if self.path == "/pages/export":
                status, payload = server.pages_export(body)
                if isinstance(payload, bytes):
                    self.send_response(status)
                    self.send_header(
                        "Content-Type", "application/octet-stream"
                    )
                    self.send_header(
                        "Content-Length", str(len(payload))
                    )
                    self.end_headers()
                    self.wfile.write(payload)
                else:
                    self._send(status, payload)
                return
            status, payload = server.submit_and_wait(body)
            headers = None
            if status == 503 and payload.get("error") == "draining":
                # RFC 9110 Retry-After: tells clients/load-balancers
                # when to come back (to the replacement process).
                headers = {
                    "Retry-After": str(int(server.drain_retry_after))
                }
            elif status == 429 and payload.get("retry_after_s"):
                # Backpressure 429s carry the queue-drain ETA the
                # engine measured — a client (or the fleet router)
                # backs off for as long as a seat will actually take
                # to free, instead of a blind constant.
                headers = {
                    "Retry-After": str(
                        max(1, math.ceil(payload["retry_after_s"]))
                    )
                }
            self._send(status, payload, headers)

    return Handler


imported(__name__, _IMPORT_T0)
