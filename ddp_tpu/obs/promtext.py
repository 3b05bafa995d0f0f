"""Prometheus text exposition for the live counters and summaries.

Runs are already observable via the metrics JSONL — but a scraper
should not have to tail and parse a file. This module renders the live
state (train health, serve TTFT/occupancy/rejects, goodput/MFU) in the
Prometheus text format (version 0.0.4), served at:

- ``GET /metricsz`` on the serve HTTP frontend (serve/server.py);
- ``GET /metricsz`` on the trainer's optional metrics port
  (``--metrics_port``; :class:`MetricsPort` below).

Zero dependencies: the format is lines of ``# HELP`` / ``# TYPE``
comments and ``name{label="v"} value`` samples. :func:`validate_promtext`
is the matching lint — metric/label name validity, quote escaping, no
duplicate samples, TYPE-before-samples — run by the smoke tier against
both expositions so a renderer regression fails tier-1 fast (the
trace-schema validator's sibling).

StatSummary snapshots render as Prometheus ``summary`` families:
``name{quantile="0.5"|"0.95"}``, ``name_sum``, ``name_count`` (plus
``name_min``/``name_max`` gauges — the snapshot carries exact
extremes, and dropping them would waste the only exact tail signal a
reservoir summary has).
"""

from __future__ import annotations

import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Optional

_METRIC_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)(\s+-?\d+)?$"
)
_LABEL_PAIR_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"'
)
_TYPES = ("counter", "gauge", "summary", "histogram", "untyped")

CONTENT_TYPE = "text/plain; version=0.0.4"


def _escape(value: str) -> str:
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_value(v: float) -> str:
    f = float(v)
    if f != f:
        return "NaN"
    if f in (float("inf"), float("-inf")):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class PromBuilder:
    """Accumulate samples per metric family, render once.

    Families keep insertion order; samples within a family keep theirs.
    ``add`` validates names eagerly (a bad series should fail where it
    was written, not at scrape time) and rejects duplicate
    (name, labelset) samples — the lint's rules, enforced at build.
    """

    def __init__(self):
        # name -> {"type": str, "help": str|None, "samples": [(labels, v)]}
        self._families: dict[str, dict] = {}
        self._seen: set = set()

    def add(
        self,
        name: str,
        value,
        *,
        labels: Optional[dict] = None,
        metric_type: str = "gauge",
        help: Optional[str] = None,
    ) -> "PromBuilder":
        if value is None:
            return self  # absent metric, not zero — same rule as MFU
        if not _METRIC_NAME_RE.match(name):
            raise ValueError(f"bad metric name {name!r}")
        if metric_type not in _TYPES:
            raise ValueError(f"bad metric type {metric_type!r}")
        labels = dict(labels or {})
        for k in labels:
            if not _LABEL_NAME_RE.match(k):
                raise ValueError(f"bad label name {k!r} on {name}")
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        if key in self._seen:
            raise ValueError(f"duplicate sample {name} {labels}")
        self._seen.add(key)
        fam = self._families.setdefault(
            name, {"type": metric_type, "help": help, "samples": []}
        )
        if fam["type"] != metric_type:
            raise ValueError(
                f"{name}: conflicting types {fam['type']} vs {metric_type}"
            )
        fam["samples"].append((labels, float(value)))
        return self

    def summary(
        self,
        name: str,
        snapshot: Optional[dict],
        *,
        labels: Optional[dict] = None,
        help: Optional[str] = None,
    ) -> "PromBuilder":
        """A StatSummary ``snapshot()`` → one summary family (+ exact
        min/max gauges). Empty snapshots render count 0 only."""
        snap = snapshot or {}
        count = int(snap.get("count", 0))
        base = dict(labels or {})
        self.add(
            f"{name}_count", count, labels=base,
            metric_type="counter", help=help,
        )
        if count == 0:
            return self
        # Prefer the exact running sum; mean×count is the fallback for
        # foreign snapshots and is NOT monotone under mean rounding.
        total = (
            float(snap["sum"])
            if "sum" in snap
            else float(snap["mean"]) * count
        )
        self.add(f"{name}_sum", total, labels=base, metric_type="counter")
        for q, field in (("0.5", "p50"), ("0.95", "p95")):
            if field in snap:
                self.add(
                    name, snap[field],
                    labels={**base, "quantile": q},
                    metric_type="summary",
                )
        for ext in ("min", "max"):
            if ext in snap:
                self.add(f"{name}_{ext}", snap[ext], labels=base)
        return self

    def render(self) -> str:
        lines: list[str] = []
        for name, fam in self._families.items():
            if fam["help"]:
                lines.append(f"# HELP {name} {fam['help']}")
            lines.append(f"# TYPE {name} {fam['type']}")
            for labels, value in fam["samples"]:
                if labels:
                    body = ",".join(
                        f'{k}="{_escape(v)}"'
                        for k, v in sorted(labels.items())
                    )
                    lines.append(f"{name}{{{body}}} {_fmt_value(value)}")
                else:
                    lines.append(f"{name} {_fmt_value(value)}")
        return "\n".join(lines) + "\n"


def validate_promtext(text: str) -> int:
    """Lint a text exposition; → sample count, raises ValueError.

    Checks the rules scrapers actually break on: name/label validity,
    quote escaping (labels must reconstruct exactly), float-parseable
    values, no duplicate (name, labelset) samples, at most one TYPE
    per family and declared before its samples, trailing newline.
    """
    if not text.endswith("\n"):
        raise ValueError("exposition must end with a newline")
    samples = 0
    seen: set = set()
    typed: dict[str, str] = {}
    sampled_names: set[str] = set()
    for n, line in enumerate(text.split("\n"), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] in ("TYPE", "HELP"):
                mname = parts[2]
                if not _METRIC_NAME_RE.match(mname):
                    raise ValueError(f"line {n}: bad metric name {mname!r}")
                if parts[1] == "TYPE":
                    if len(parts) < 4 or parts[3] not in _TYPES:
                        raise ValueError(f"line {n}: bad TYPE line")
                    if mname in typed:
                        raise ValueError(f"line {n}: duplicate TYPE {mname}")
                    if mname in sampled_names:
                        raise ValueError(
                            f"line {n}: TYPE {mname} after its samples"
                        )
                    typed[mname] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {n}: unparseable sample {line!r}")
        name, _, labelbody, value, _ts = m.groups()
        pairs: tuple = ()
        if labelbody:
            found = _LABEL_PAIR_RE.findall(labelbody)
            rebuilt = ",".join(f'{k}="{v}"' for k, v in found)
            if rebuilt != labelbody.rstrip(","):
                raise ValueError(f"line {n}: malformed labels {labelbody!r}")
            names = [k for k, _ in found]
            if len(set(names)) != len(names):
                raise ValueError(f"line {n}: repeated label name")
            pairs = tuple(sorted(found))
        if value not in ("NaN", "+Inf", "-Inf"):
            try:
                float(value)
            except ValueError:
                raise ValueError(f"line {n}: bad value {value!r}")
        # quantile/le label participates in dedup — identical full
        # labelsets are what scrapers reject.
        key = (name, pairs)
        if key in seen:
            raise ValueError(f"line {n}: duplicate sample {name} {pairs}")
        seen.add(key)
        # A summary's name_sum/name_count samples belong to family
        # `name`; approximate by exact-name tracking (enough to catch
        # TYPE-after-sample for the family head).
        sampled_names.add(name)
        samples += 1
    return samples


# ---- renderers -------------------------------------------------------


def _render_build_info(b: PromBuilder, bi: Optional[dict], name: str) -> None:
    """The ``ddp_tpu_build_info`` provenance gauge (value 1, identity
    in the labels — the Prometheus *_info idiom). Shared by both
    exporters so a fleet scrape spots version skew in one query;
    absent when the snapshot carries no block (pre-build-info
    streams stay byte-identical)."""
    if not bi:
        return
    b.add(
        name, 1,
        labels={k: str(v) for k, v in sorted(bi.items())},
        help="package/jax/backend provenance (value is always 1)",
    )


def _render_slo(b: PromBuilder, slo: Optional[dict]) -> None:
    """SLO gauges (obs/slo.py): target/current/burn-rate/breached per
    objective. Absent-key gated — an engine without --slo renders no
    ddp_tpu_slo_* series at all (the disabled-pin convention)."""
    if not slo:
        return
    for obj in slo.get("objectives") or []:
        labels = {"objective": obj["name"]}
        b.add(
            "ddp_tpu_slo_target", obj.get("target"), labels=labels,
            help="objective bound (seconds, or fraction for "
            "availability)",
        )
        b.add(
            "ddp_tpu_slo_current", obj.get("current"), labels=labels,
            help="fast-window SLI value (absent until observed)",
        )
        for window, key in (("fast", "burn_rate_fast"),
                            ("slow", "burn_rate_slow")):
            b.add(
                "ddp_tpu_slo_burn_rate", obj.get(key),
                labels={**labels, "window": window},
                help="error-budget burn rate (1.0 = budget consumed "
                "exactly)",
            )
        b.add(
            "ddp_tpu_slo_breached",
            1 if obj.get("breached") else 0,
            labels=labels,
            help="1 while the current windowed value violates the "
            "objective",
        )


def render_serve(
    stats: dict,
    *,
    up: Optional[bool] = None,
    draining: Optional[bool] = None,
) -> str:
    """ServeEngine.stats() → exposition (the /metricsz payload)."""
    b = PromBuilder()
    if up is not None:
        b.add(
            "ddp_tpu_serve_up", 1 if up else 0,
            help="1 while the engine loop is healthy",
        )
    if draining is not None:
        b.add(
            "ddp_tpu_serve_draining", 1 if draining else 0,
            help="1 while shutdown drain rejects new admissions",
        )
    b.add("ddp_tpu_serve_slots", stats.get("slots"), help="decode lanes")
    b.add(
        "ddp_tpu_serve_active_slots", stats.get("active"),
        help="lanes bound to a request",
    )
    slots = stats.get("slots") or 0
    if slots:
        b.add(
            "ddp_tpu_serve_slot_occupancy",
            (stats.get("active") or 0) / slots,
            help="active / slots",
        )
    b.add("ddp_tpu_serve_queue_depth", stats.get("queue_depth"))
    b.add(
        "ddp_tpu_serve_steps_total", stats.get("steps"),
        metric_type="counter", help="engine iterations",
    )
    for reason, count in sorted((stats.get("rejects") or {}).items()):
        b.add(
            "ddp_tpu_serve_rejects_total", count,
            labels={"reason": reason}, metric_type="counter",
        )
    for status, count in sorted(
        (stats.get("requests_by_status") or {}).items()
    ):
        b.add(
            "ddp_tpu_serve_requests_total", count,
            labels={"status": status}, metric_type="counter",
        )
    b.add(
        "ddp_tpu_serve_tokens_total", stats.get("tokens_total"),
        metric_type="counter",
        help="tokens scheduled across all requests (the aggregator's "
        "fleet tokens/s source)",
    )
    b.add(
        "ddp_tpu_serve_accepted_total", stats.get("accepted_total"),
        metric_type="counter",
        help="requests accepted into the queue",
    )
    b.add(
        "ddp_tpu_serve_kv_rows_attended_total",
        stats.get("kv_rows_attended_total"),
        metric_type="counter",
        help="cache rows the decoding lanes attended (pos + 1 a lane "
        "a decode step)",
    )
    b.add(
        "ddp_tpu_serve_kv_rows_lane_total",
        stats.get("kv_rows_lane_total"),
        metric_type="counter",
        help="cache rows the lanes hold (slots x total_len a decode "
        "step): attended over this is the share of lane bytes the "
        "banded read fetches",
    )
    # Block diffusion (models/sdar.py): present only on an engine whose
    # model generates by blocks.
    bd = stats.get("block_diffusion") or {}
    for key, help_ in (
        ("block_forwards_total", "lane-forwards of generating lanes"),
        ("blocks_committed_total", "blocks whose K/V rows were committed"),
        ("tokens_committed_total",
         "tokens committed, never more than a request asked for"),
        ("positions_unmasked_total", "block positions unmasked"),
    ):
        if key in bd:
            b.add(f"ddp_tpu_serve_{key}", bd[key],
                  metric_type="counter", help=help_)
    for key, help_ in (
        ("moe_tokens_routed_total",
         "rows the expert layers were handed (tokens x top_k x layers)"),
        ("moe_experts_hit_total", "experts that held a row, over layer calls"),
        ("moe_layer_calls_total", "expert-layer calls"),
    ):
        if key in bd:
            b.add(f"ddp_tpu_serve_{key}", bd[key],
                  metric_type="counter", help=help_)
    if "moe_expert_load_max" in bd:
        b.add("ddp_tpu_serve_moe_expert_load_max", bd["moe_expert_load_max"],
              help="the fullest expert's rows in the last step, mean "
              "over layers")
    # Recurrent lanes (models/granite_hybrid.py): present only on an
    # engine whose lanes hold state beside K/V rows.
    rs = stats.get("recurrent_state") or {}
    for key, kind, help_ in (
        ("ssm_lane_updates_total", "counter",
         "live lanes summed over decode steps: each is every recurrent "
         "layer's state read and written once"),
        ("ssm_prefill_tokens_total", "counter",
         "real (not padded) prompt positions through the chunked scan"),
        ("ssm_state_resets_total", "counter",
         "lanes whose state was reset at admission (first chunks)"),
        ("ssm_state_bytes_per_slot", "gauge",
         "recurrent state and convolution tail one lane holds"),
        ("kv_bytes_per_slot", "gauge",
         "K/V rows one lane holds, attention layers only"),
        ("kv_ring_rows_attended_total", "counter",
         "ring rows decode steps read: min(pos + 1, window) a live lane "
         "a windowed layer"),
        ("kv_shared_rows_attended_total", "counter",
         "shared rows decode steps read: pos + 1 a live lane a reading "
         "layer (the full layer and its cross-attention readers)"),
        ("prefill_self_positions_total", "counter",
         "real prompt positions through the layers that write a lane"),
        ("prefill_cross_positions_total", "counter",
         "prompt positions through the layers that only sample: one a "
         "request where prefill stops at the shared K/V layer"),
        ("kv_ring_bytes_per_slot", "gauge",
         "K/V rows one lane holds in its windowed layers' rings"),
        ("kv_shared_bytes_per_slot", "gauge",
         "K/V rows one lane holds for the one layer others read"),
    ):
        if key in rs:
            b.add(f"ddp_tpu_serve_{key}", rs[key], metric_type=kind,
                  help=help_)
    # Latent lanes whose keys an indexer selects (models/glm_dsa.py):
    # present only on an engine that serves such a block.
    la = stats.get("latent_attention") or {}
    for key, kind, help_ in (
        ("dsa_rows_scored_total", "counter",
         "stored rows the indexer scored: t + 1 a query at position t a "
         "layer, over real prompt positions and live decoding lanes"),
        ("dsa_rows_selected_total", "counter",
         "stored rows attention read: min(t + 1, index_topk) a query a "
         "layer"),
        ("moe_pairs_routed_total", "counter",
         "(token, chosen expert) pairs the routed layers' programs "
         "made, over all the experts the router scores"),
        ("moe_pairs_held_total", "counter",
         "those pairs whose expert is held by this process"),
        ("latent_bytes_per_slot", "gauge",
         "latent and indexer rows one lane holds, all layers"),
    ):
        if key in la:
            b.add(f"ddp_tpu_serve_{key}", la[key], metric_type=kind,
                  help=help_)
    b.summary(
        "ddp_tpu_serve_ttft_seconds", stats.get("ttft_s"),
        help="submit to first token",
    )
    # What the frontend adds outside the engine's clock: ttft and
    # queue wait start only once the server's lock is won.
    b.summary(
        "ddp_tpu_serve_submit_lock_wait_seconds",
        stats.get("lock_wait_s"),
        help="frontend call to the server's lock won, before submit",
    )
    b.summary(
        "ddp_tpu_serve_result_pickup_seconds", stats.get("pickup_s"),
        help="request finished to its answer picked up by the frontend",
    )
    b.summary(
        "ddp_tpu_serve_tpot_seconds", stats.get("tpot_s"),
        help="decode seconds per output token (per request)",
    )
    b.summary(
        "ddp_tpu_serve_queue_wait_seconds", stats.get("queue_s"),
        help="submit to decode-lane bind",
    )
    b.summary(
        "ddp_tpu_serve_decode_tokens_per_second",
        stats.get("decode_tokens_per_s"),
    )
    b.summary(
        "ddp_tpu_serve_step_latency_seconds", stats.get("step_latency_s")
    )
    _render_slo(b, stats.get("slo"))
    _render_build_info(b, stats.get("build_info"), "ddp_tpu_build_info")
    for prog, count in sorted((stats.get("compile_counts") or {}).items()):
        b.add(
            "ddp_tpu_serve_compiled_programs", count,
            labels={"program": prog},
            help="jit cache entries (static-shape pin observable)",
        )
    # Decode hot path (ISSUE 10): cache footprint + speculative
    # acceptance. Absent keys (pre-decode-path engines, spec off)
    # render nothing — absent and zero are different facts.
    dp = stats.get("decode_path") or {}
    b.add(
        "ddp_tpu_serve_cache_bytes_per_slot",
        dp.get("cache_bytes_per_slot"),
        help="KV-cache HBM per decode lane, int8 scales included",
    )
    b.add(
        "ddp_tpu_serve_spec_drafted_total", dp.get("spec_drafted_total"),
        metric_type="counter", help="draft tokens proposed",
    )
    b.add(
        "ddp_tpu_serve_spec_accepted_total",
        dp.get("spec_accepted_total"),
        metric_type="counter", help="draft tokens the target accepted",
    )
    b.add(
        "ddp_tpu_serve_spec_acceptance", dp.get("spec_acceptance"),
        help="lifetime accepted/drafted fraction",
    )
    # Paged KV + radix prefix cache (PR 12): pool occupancy and
    # prefix-reuse counters. The whole block is absent-key gated on
    # the engine's paged mode, so a fixed-lane engine's exposition
    # stays byte-identical.
    pg = stats.get("paged") or {}
    b.add(
        "ddp_tpu_serve_prefix_hits_total", pg.get("prefix_hits"),
        metric_type="counter",
        help="requests that matched cached prefix pages at bind",
    )
    b.add(
        "ddp_tpu_serve_prefix_misses_total", pg.get("prefix_misses"),
        metric_type="counter",
    )
    b.add(
        "ddp_tpu_serve_prefix_hit_rate", pg.get("prefix_hit_rate"),
        help="prompt tokens served from cached pages / prompt tokens "
        "admitted (token-level, lifetime)",
    )
    b.add(
        "ddp_tpu_serve_pages_free", pg.get("pages_free"),
        help="allocatable pages (excluding evictable cached prefixes)",
    )
    b.add(
        "ddp_tpu_serve_pages_resident", pg.get("pages_resident"),
        help="pages holding live KV: lane-mapped or prefix-cached",
    )
    b.add(
        "ddp_tpu_serve_pages_shared", pg.get("pages_shared"),
        help="pages mapped by two or more lanes (copy-free forks)",
    )
    gp = stats.get("goodput") or {}
    b.add("ddp_tpu_serve_productive_seconds_total", gp.get("productive_s"),
          metric_type="counter")
    b.add("ddp_tpu_serve_goodput", gp.get("goodput"))
    # Model-lifecycle block (hot-swap tentpole): absent until the
    # engine carries a model version or has swapped/rolled back, so a
    # pre-lifecycle exposition stays byte-identical.
    lc = stats.get("lifecycle") or {}
    b.add(
        "ddp_tpu_serve_reloads_total", lc.get("reloads_total"),
        metric_type="counter",
        help="verified hot-swaps committed (install_params)",
    )
    b.add(
        "ddp_tpu_serve_rollbacks_total", lc.get("rollbacks_total"),
        metric_type="counter",
        help="mid-swap failures rolled back to the previous weights",
    )
    if lc.get("model_version"):
        b.add(
            "ddp_tpu_serve_model_info", 1,
            labels={"version": str(lc["model_version"])},
            help="serving model version (checkpoint@epoch), value "
            "always 1",
        )
    # Compiled-program introspection (obs/xprof.py, engine xprof=...):
    # absent keys render nothing, so an xprof-less engine's exposition
    # stays byte-identical.
    xp = stats.get("xprof") or {}
    b.add(
        "ddp_tpu_serve_compiled_executables", xp.get("programs"),
        help="xprof compile-ledger entries",
    )
    b.add(
        "ddp_tpu_serve_compile_seconds_total", xp.get("compile_s_total"),
        metric_type="counter", help="XLA compile wall time paid",
    )
    mem = xp.get("hbm") or {}
    b.add("ddp_tpu_serve_hbm_used_bytes", mem.get("hbm_used_bytes"))
    b.add(
        "ddp_tpu_serve_hbm_high_water_bytes",
        mem.get("hbm_high_water_bytes"),
        help="peak device memory observed",
    )
    b.add(
        "ddp_tpu_serve_hbm_headroom_frac", mem.get("hbm_headroom_frac"),
        help="1 - high_water/limit (absent off-TPU: no honest limit)",
    )
    return b.render()


def render_fleet(
    snap: dict,
    *,
    up: Optional[bool] = None,
    draining: Optional[bool] = None,
) -> str:
    """Fleet router/manager snapshot → exposition (the fleet
    frontend's /metricsz; serve/fleet.py ``Router.state()`` merged
    with the manager's counters). Per-replica serving metrics stay on
    each replica's own /metricsz — these are the ROUTER's facts:
    health gating, breaker state, replay/hedge accounting, restarts.
    """
    b = PromBuilder()
    if up is not None:
        b.add(
            "ddp_tpu_fleet_up", 1 if up else 0,
            help="1 while at least one replica is dispatchable",
        )
    if draining is not None:
        b.add(
            "ddp_tpu_fleet_draining", 1 if draining else 0,
            help="1 while the fleet frontend rejects new admissions",
        )
    b.add(
        "ddp_tpu_fleet_replicas", snap.get("replicas"),
        help="supervised replica processes",
    )
    b.add(
        "ddp_tpu_fleet_replicas_healthy", snap.get("replicas_healthy"),
        help="replicas passing /healthz and accepting dispatch",
    )
    b.add(
        "ddp_tpu_fleet_replicas_draining", snap.get("replicas_draining"),
    )
    b.add(
        "ddp_tpu_fleet_replicas_dead", snap.get("replicas_dead"),
        help="replicas whose process is down (restarting or out of "
        "restart budget)",
    )
    b.add(
        "ddp_tpu_fleet_breaker_open", snap.get("breaker_open"),
        help="replicas whose circuit breaker is not closed (open or "
        "half-open: shedding user traffic)",
    )
    b.add(
        "ddp_tpu_fleet_breaker_opens_total", snap.get("breaker_opens_total"),
        metric_type="counter",
        help="lifetime closed->open transitions across all breakers",
    )
    b.add(
        "ddp_tpu_fleet_dispatched_total", snap.get("dispatched_total"),
        metric_type="counter", help="requests the router dispatched",
    )
    b.add(
        "ddp_tpu_fleet_retries_total", snap.get("retries_total"),
        metric_type="counter",
        help="re-dispatches after a failed/backpressured attempt",
    )
    b.add(
        "ddp_tpu_fleet_replays_total", snap.get("replays_total"),
        metric_type="counter",
        help="in-flight requests replayed to a surviving replica "
        "after their replica died mid-request",
    )
    b.add(
        "ddp_tpu_fleet_hedges_total", snap.get("hedges_total"),
        metric_type="counter",
        help="straggler requests duplicated to a second replica",
    )
    b.add(
        "ddp_tpu_fleet_hedge_wins_total", snap.get("hedge_wins_total"),
        metric_type="counter",
        help="hedged requests the SECOND replica answered first",
    )
    b.add(
        "ddp_tpu_fleet_restarts_total", snap.get("restarts_total"),
        metric_type="counter",
        help="replica process restarts the manager performed",
    )
    b.add(
        "ddp_tpu_fleet_rolling_restarts_total",
        snap.get("rolling_restarts_total"),
        metric_type="counter",
        help="completed fleet-wide rolling restarts (drain -> wait "
        "-> restart -> re-admit, one replica at a time)",
    )
    # Model-lifecycle series: absent until a fleet reload ran / any
    # replica advertises a version (the gated-state convention).
    b.add(
        "ddp_tpu_fleet_reloads_total", snap.get("fleet_reloads_total"),
        metric_type="counter",
        help="completed fleet-wide verified hot-swaps (/reloadz: one "
        "member /reload at a time, zero process churn)",
    )
    for version, count in sorted(
        (snap.get("model_versions") or {}).items()
    ):
        b.add(
            "ddp_tpu_fleet_model_version", count,
            labels={"version": str(version)},
            help="replicas serving each model version (one series "
            "while converged, two mid-roll)",
        )
    # Disaggregation series (PR 16): every key below is ABSENT from a
    # classic router's state(), so PromBuilder renders nothing and the
    # exposition stays byte-identical when the feature is off.
    for index, role in sorted(
        (snap.get("replica_roles") or {}).items()
    ):
        b.add(
            "ddp_tpu_fleet_role", 1,
            labels={"replica": str(index), "role": role},
            help="replica's serving role (prefill | decode | hybrid)",
        )
    b.add(
        "ddp_tpu_fleet_prefill_handoffs_total",
        snap.get("prefill_handoffs_total"),
        metric_type="counter",
        help="long prompts prefilled on the prefill tier before "
        "their pages migrated to a decode replica",
    )
    b.add(
        "ddp_tpu_fleet_migrations_total", snap.get("migrations_total"),
        metric_type="counter",
        help="completed KV-page migrations (export + install over "
        "POST /pages)",
    )
    b.add(
        "ddp_tpu_fleet_migration_failures_total",
        snap.get("migration_failures_total"),
        metric_type="counter",
        help="migrations abandoned (export miss, pool full, "
        "transport death) — the request replayed from the prompt",
    )
    b.add(
        "ddp_tpu_fleet_pages_migrated_total",
        snap.get("pages_migrated_total"),
        metric_type="counter",
        help="KV pages physically copied between replicas",
    )
    b.add(
        "ddp_tpu_fleet_directory_pulls_total",
        snap.get("directory_pulls_total"),
        metric_type="counter",
        help="prefix-directory lookups that found another replica "
        "owning the prompt's pages and attempted a pull",
    )
    b.add(
        "ddp_tpu_fleet_directory_pull_hits_total",
        snap.get("directory_pull_hits_total"),
        metric_type="counter",
        help="directory pulls whose pages installed on the target",
    )
    b.add(
        "ddp_tpu_fleet_directory_size", snap.get("directory_size"),
        help="distinct leading-page prefixes the router can locate",
    )
    if "migration_seconds" in snap:
        # summary() renders a count-0 series for an EMPTY snapshot, so
        # the absent-key gate lives here, not inside the helper.
        b.summary(
            "ddp_tpu_fleet_migration_seconds",
            snap.get("migration_seconds"),
            help="one migration's export + push wall time",
        )
    # Fleet-tracing series (PR 19): keys absent from an untraced
    # router's state(), so the exposition stays byte-identical with
    # tracing off (the same gate the disaggregation block rides).
    b.add(
        "ddp_tpu_fleet_trace_propagated_total",
        snap.get("trace_propagated_total"),
        metric_type="counter",
        help="completed requests whose serving replica adopted the "
        "router's trace context (echoed the trace id back)",
    )
    b.add(
        "ddp_tpu_fleet_trace_orphaned_total",
        snap.get("trace_orphaned_total"),
        metric_type="counter",
        help="completed requests whose replica did NOT echo the "
        "router's trace id — its timeline is orphaned from the hops",
    )
    for kind, hop_snap in sorted(
        (snap.get("hop_seconds") or {}).items()
    ):
        b.summary(
            "ddp_tpu_fleet_hop_seconds", hop_snap,
            labels={"hop": kind},
            help="per-hop router latency (dispatch, prefill_handoff, "
            "migrate, breaker_wait, ...) by hop kind",
        )
    _render_build_info(b, snap.get("build_info"), "ddp_tpu_build_info")
    return b.render()


def render_train(snap: dict) -> str:
    """Trainer telemetry snapshot → exposition.

    ``snap`` is the trainer's live dict (step/loss/grad_norm/mfu/
    goodput/recompiles/health events/step-time summary); absent keys
    render no series — absent and zero are different facts.
    """
    b = PromBuilder()
    b.add("ddp_tpu_train_up", 1, help="trainer process is live")
    b.add("ddp_tpu_train_step", snap.get("step"), help="global step")
    b.add("ddp_tpu_train_epoch", snap.get("epoch"))
    b.add("ddp_tpu_train_loss", snap.get("loss"), help="last logged loss")
    b.add("ddp_tpu_train_grad_norm", snap.get("grad_norm"))
    b.add("ddp_tpu_train_learning_rate", snap.get("lr"))
    b.add("ddp_tpu_train_accuracy", snap.get("accuracy"))
    b.add("ddp_tpu_train_mfu", snap.get("mfu"), help="model FLOP/s / peak")
    b.add(
        "ddp_tpu_train_goodput", snap.get("goodput"),
        help="productive seconds / wall since first launch",
    )
    b.add(
        "ddp_tpu_train_examples_per_second", snap.get("images_per_sec")
    )
    b.add(
        "ddp_tpu_train_recompiles_total", snap.get("recompiles"),
        metric_type="counter",
    )
    for det, count in sorted((snap.get("health_events") or {}).items()):
        b.add(
            "ddp_tpu_train_health_events_total", count,
            labels={"detector": det}, metric_type="counter",
            help="anomaly sentry detections",
        )
    if snap.get("nonfinite_layer") is not None or (
        snap.get("nonfinite_step") is not None
    ):
        b.add(
            "ddp_tpu_train_nonfinite", 1,
            labels={
                "layer": snap.get("nonfinite_layer") or "unknown",
                "step": str(snap.get("nonfinite_step")),
            },
            help="first non-finite gradient/loss observation",
        )
    # Compiled-program introspection (--xprof, obs/xprof.py): compile
    # ledger totals and the device-memory sampler's view. Absent keys
    # render no series — an xprof-off trainer's exposition is
    # byte-identical to the pre-xprof one.
    b.add(
        "ddp_tpu_train_compiled_executables", snap.get("compile_programs"),
        help="xprof compile-ledger entries",
    )
    b.add(
        "ddp_tpu_train_compile_seconds_total",
        snap.get("compile_seconds_total"),
        metric_type="counter", help="XLA compile wall time paid",
    )
    b.add("ddp_tpu_train_hbm_used_bytes", snap.get("hbm_used_bytes"))
    b.add(
        "ddp_tpu_train_hbm_high_water_bytes",
        snap.get("hbm_high_water_bytes"),
        help="peak device memory observed",
    )
    b.add(
        "ddp_tpu_train_hbm_headroom_frac", snap.get("hbm_headroom_frac"),
        help="1 - high_water/limit (absent off-TPU: no honest limit)",
    )
    b.summary("ddp_tpu_train_step_seconds", snap.get("step_time"))
    _render_build_info(b, snap.get("build_info"), "ddp_tpu_build_info")
    return b.render()


# ---- the trainer's metrics port --------------------------------------


class MetricsPort:
    """Minimal HTTP endpoint: GET /metricsz → ``text_fn()``.

    ``port=0`` binds ephemeral (tests); ``.port`` is the bound one.
    One daemon thread; ``stop()`` (or context exit) shuts it down.
    The handler never lets a renderer exception kill the scrape
    endpoint — it answers 500 with the error text instead.
    """

    def __init__(
        self,
        text_fn: Callable[[], str],
        *,
        host: str = "127.0.0.1",
        port: int = 0,
    ):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: D102
                pass

            def _send(self, status: int, body: str, ctype: str) -> None:
                data = body.encode()
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):  # noqa: N802
                if self.path == "/metricsz":
                    try:
                        text = outer.text_fn()
                    except Exception as e:  # noqa: BLE001
                        self._send(500, f"render failed: {e}\n", "text/plain")
                        return
                    self._send(200, text, CONTENT_TYPE)
                elif self.path == "/healthz":
                    self._send(200, '{"ok": true}', "application/json")
                else:
                    self._send(404, f"no route {self.path}\n", "text/plain")

        self.text_fn = text_fn
        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread: Optional[threading.Thread] = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "MetricsPort":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name="ddp-tpu-metrics-port",
                daemon=True,
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> "MetricsPort":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
