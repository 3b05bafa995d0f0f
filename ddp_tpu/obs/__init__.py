"""Observability subsystem: spans, attribution, goodput, run health.

Layers, all off by default and pinned always-cheap when off
(tests/test_obs.py + tests/test_health.py: disabled mode triggers no
jit compilation and no growing per-step allocations):

- ``tracer``  — in-process span tracer with a bounded ring buffer and
  crash-safe export to Perfetto/Chrome ``trace_event`` JSON;
- ``steptime`` — splits each training step into host-input-wait /
  dispatch / device-compute and flags recompiles via a process-wide
  XLA compile-event counter;
- ``goodput`` — per-model FLOPs estimators (CNN, ResNet, ViT, LM/MoE),
  MFU arithmetic against per-chip peak, and a restart-aware goodput
  accountant persisted in a sidecar next to the checkpoints;
- ``health`` — jit-fused per-layer-group gradient stats with NaN/Inf
  provenance (first offending layer path + step) and the one-step-
  behind HealthMonitor;
- ``sentry`` — rolling-window anomaly detectors (loss spike, grad
  explosion, straggler, recompile storm) with warn/checkpoint/halt
  actions;
- ``recorder`` — the flight recorder: a bounded ring of step records
  dumped crash-safely on exception, SIGTERM, and watchdog kill;
- ``promtext`` — Prometheus text exposition of the live counters,
  served at ``/metricsz`` (serve frontend + trainer metrics port),
  with a matching lint;
- ``xprof`` — compiled-program introspection: per-executable compile
  ledger (label, arg-shape signature, compile wall-time, XLA-measured
  FLOPs/bytes, memory breakdown, HLO collective payloads) plus the
  device-memory high-water/headroom sampler, cross-checking the
  analytic estimators and the zero strategy's hand-priced
  ``comm_bytes`` against what XLA actually built;
- ``reqtrace`` — per-request distributed tracing for the serve path:
  a 64-bit trace id at admission, lifecycle events (admit → queue →
  prefill chunks → spec rounds → decode → retire) hung off the
  engine's existing slot bookkeeping, exported as Perfetto async
  spans and reconstructable/causally-validated from merged traces;
- ``slo`` — declarative serving objectives
  (``ttft_p99<0.5s,availability>0.999``) evaluated over rolling
  windows with multi-window (5 m / 1 h) burn-rate alerting;
- ``aggregate`` — the multi-process telemetry aggregator: scrape N
  ``/statusz`` + ``/metricsz`` endpoints (or read per-rank metrics
  files offline), merge StatSummaries exactly, render one fleet view
  — the interface the multi-replica router will consume.

Wiring: ``--trace_dir`` / ``--health`` / ``--metrics_port`` on
train.py (train/trainer.py), the serve engine/server (spans +
``/statusz`` + ``/metricsz``), runtime/launch.py (per-rank trace
files, merged by scripts/trace_merge.py), and
scripts/health_report.py (JSONL → triage report).
docs/OBSERVABILITY.md has the full story.
"""

from ddp_tpu.obs.goodput import (
    GoodputAccountant,
    peak_flops_per_chip,
    train_flops_per_example,
)
from ddp_tpu.obs.health import (
    HealthMonitor,
    HealthStats,
    NonFiniteLossError,
    group_layout,
    health_stats,
)
from ddp_tpu.obs.promtext import (
    PromBuilder,
    render_serve,
    render_train,
    validate_promtext,
)
from ddp_tpu.obs.aggregate import (
    load_metrics_file,
    merge_fleet,
    render_fleet,
    scrape_endpoint,
)
from ddp_tpu.obs.recorder import FlightRecorder, build_info
from ddp_tpu.obs.reqtrace import (
    RequestTrace,
    RequestTracer,
    derive_trace_id,
    format_trace_id,
    reconstruct_requests,
    validate_request_timeline,
)
from ddp_tpu.obs.slo import Objective, SLOEngine, parse_slo
from ddp_tpu.obs.sentry import AnomalySentry, SentryConfig
from ddp_tpu.obs.steptime import CompileCounter, StepAttributor, StepTiming
from ddp_tpu.obs.tracer import (
    Tracer,
    get_tracer,
    install_from_env,
    validate_trace_file,
)
from ddp_tpu.obs.xprof import (
    DeviceMemorySampler,
    Xprof,
    parse_hlo_collectives,
    ring_collective_traffic,
)

__all__ = [
    "AnomalySentry",
    "CompileCounter",
    "DeviceMemorySampler",
    "FlightRecorder",
    "GoodputAccountant",
    "HealthMonitor",
    "HealthStats",
    "NonFiniteLossError",
    "Objective",
    "PromBuilder",
    "RequestTrace",
    "RequestTracer",
    "SLOEngine",
    "SentryConfig",
    "StepAttributor",
    "StepTiming",
    "Tracer",
    "Xprof",
    "build_info",
    "derive_trace_id",
    "format_trace_id",
    "get_tracer",
    "group_layout",
    "health_stats",
    "install_from_env",
    "load_metrics_file",
    "merge_fleet",
    "parse_hlo_collectives",
    "parse_slo",
    "peak_flops_per_chip",
    "reconstruct_requests",
    "render_fleet",
    "render_serve",
    "render_train",
    "ring_collective_traffic",
    "scrape_endpoint",
    "train_flops_per_example",
    "validate_promtext",
    "validate_request_timeline",
    "validate_trace_file",
]
