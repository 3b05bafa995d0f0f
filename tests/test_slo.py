"""SLO engine (obs/slo.py), /metricsz gauges, build_info, and the
fleet aggregator (obs/aggregate.py).

Acceptance pins (ISSUE 11):

1. **Burn-rate math** — multi-window (fast/slow) burn rates computed
   from the error budget, breach on current-value violation, the
   alert transition firing exactly once per episode (clock-injected,
   no sleeps).
2. **A seeded breach is visible everywhere** — a deliberately tight
   objective over real engine traffic produces linted
   ``ddp_tpu_slo_*`` gauges on /metricsz, an ``slo_breach`` metrics
   record, a flight-recorder ring entry, and shows up in the
   aggregator's fleet view across ≥2 scraped endpoints.
3. **Disabled is pinned** — an engine without --slo renders a
   byte-identical /metricsz exposition to one whose stats were
   stripped of the slo/reqtrace keys (the PR-2/PR-9 absent-key
   convention).
"""

import json

import pytest

from ddp_tpu.models.lm import LMSpec, init_lm
from ddp_tpu.obs.promtext import render_serve, render_train, validate_promtext
from ddp_tpu.obs.slo import SLOEngine, parse_slo
from ddp_tpu.serve.engine import ServeEngine

SPEC = LMSpec(vocab_size=37, total_len=32, d_model=32, depth=2, num_heads=4)


@pytest.fixture(scope="module")
def params():
    return init_lm(SPEC, seed=0)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


class TestParse:
    def test_grammar_roundtrip(self):
        objs = parse_slo("ttft_p99<0.5s,tpot_p50<80ms,availability>0.999")
        assert [o.name for o in objs] == [
            "ttft_p99", "tpot_p50", "availability",
        ]
        assert objs[0].target == 0.5 and objs[0].percentile == 99.0
        assert objs[1].target == pytest.approx(0.08)  # ms -> s
        assert objs[2].target == 0.999 and objs[2].percentile is None
        assert objs[0].budget == pytest.approx(0.01)
        assert objs[2].budget == pytest.approx(0.001)
        # unitless latency bound defaults to seconds; queue works too
        assert parse_slo("queue_p95<2")[0].target == 2.0

    def test_rejects_malformed(self):
        for bad, why in (
            ("ttft<0.5s", "latency objectives"),  # no percentile
            ("ttft_p99>0.5s", "latency objectives"),  # wrong op
            ("availability<0.999", "availability objectives"),  # wrong op
            ("availability>1.5", "in \\(0, 1\\)"),
            ("bogus_p50<1s", "unknown metric"),
            ("ttft_p0<1s", "percentile"),
            ("ttft_p99<0s", "positive"),
            ("ttft_p99<1s,ttft_p99<2s", "duplicate"),
            ("", "empty"),
            ("&&&", "bad SLO clause"),
        ):
            with pytest.raises(ValueError, match=why):
                parse_slo(bad)


class TestBurnRate:
    def mk(self, spec="ttft_p99<0.1s", **kw):
        clock = FakeClock()
        breaches = []
        kw.setdefault("min_eval_interval_s", 0.0)
        kw.setdefault("fast_window_s", 10.0)
        kw.setdefault("slow_window_s", 100.0)
        eng = SLOEngine(
            spec, clock=clock, on_breach=breaches.append, **kw
        )
        return eng, clock, breaches

    def test_burn_math_and_windows(self):
        eng, clock, _ = self.mk()
        # 9 good + 1 bad in the fast window: 10% violations over a 1%
        # budget = burn 10.
        for _ in range(9):
            eng.observe(ttft_s=0.01)
        eng.observe(ttft_s=0.5)
        (st,) = eng.state()["objectives"]
        assert st["burn_rate_fast"] == pytest.approx(10.0)
        assert st["burn_rate_slow"] == pytest.approx(10.0)
        assert st["breached"] is True  # p99 of the window is 0.5
        # Advance past the fast window: fast burn clears, slow holds.
        clock.t = 50.0
        for _ in range(10):
            eng.observe(ttft_s=0.01)
        (st,) = eng.state()["objectives"]
        assert st["burn_rate_fast"] == 0.0
        assert st["burn_rate_slow"] == pytest.approx(0.05 / 0.01)
        assert st["breached"] is False

    def test_availability_objective(self):
        eng, clock, _ = self.mk("availability>0.9")
        for ok in (True, True, True, False):
            eng.observe(ok=ok)
        (st,) = eng.state()["objectives"]
        assert st["current"] == pytest.approx(0.75)
        assert st["breached"] is True
        assert st["burn_rate_fast"] == pytest.approx(0.25 / 0.1)

    def test_breach_fires_once_and_rearms(self):
        eng, clock, breaches = self.mk(burn_alert=1.0)
        for _ in range(5):
            eng.observe(ttft_s=0.5)  # every request violating
        assert len(breaches) == 1  # latched, not one per observe
        assert breaches[0]["name"] == "ttft_p99"
        assert eng.breach_counts["ttft_p99"] == 1
        # Violations age out -> alert clears -> a new episode fires.
        clock.t = 200.0
        for _ in range(5):
            eng.observe(ttft_s=0.01)
        assert len(breaches) == 1
        clock.t = 201.0
        for _ in range(5):
            eng.observe(ttft_s=0.5)
        assert len(breaches) == 2

    def test_latency_fields_absent_do_not_count(self):
        """Queue-timeout requests carry no ttft — they must not feed
        the latency percentile (they DO feed availability)."""
        eng, clock, _ = self.mk("ttft_p99<0.1s,availability>0.999")
        eng.observe(ttft_s=None, ok=False)
        ttft, avail = eng.state()["objectives"]
        assert ttft["current"] is None and ttft["window_n"] == 0
        assert avail["current"] == 0.0 and avail["breached"] is True


class TestEngineAndGauges:
    def test_seeded_breach_visible_everywhere(self, params, tmp_path):
        """THE acceptance pin: a deliberately tight objective over
        real traffic → burn gauges on /metricsz (linted), an
        slo_breach metrics record, and a flight-recorder entry."""
        from ddp_tpu.obs.recorder import FlightRecorder, load_dump
        from ddp_tpu.utils.metrics import MetricsWriter

        mpath = tmp_path / "m.jsonl"
        recorder = FlightRecorder(str(tmp_path / "flight"))
        slo = SLOEngine(
            "ttft_p99<0.000001s",  # unmeetable: every request violates
            min_eval_interval_s=0.0,
        )
        eng = ServeEngine(
            SPEC, params, slots=2, prefill_len=8,
            metrics=MetricsWriter(str(mpath)),
            slo=slo, recorder=recorder,
        )
        eng.submit([1, 2, 3], 4)
        eng.submit([4, 5], 3)
        eng.run()
        stats = eng.stats()
        assert stats["slo"]["breached"] is True
        text = render_serve(stats, up=True)
        validate_promtext(text)
        assert 'ddp_tpu_slo_target{objective="ttft_p99"} 1e-06' in text
        assert 'ddp_tpu_slo_breached{objective="ttft_p99"} 1' in text
        assert (
            'ddp_tpu_slo_burn_rate{objective="ttft_p99",window="fast"}'
            in text
        )
        assert "ddp_tpu_build_info{" in text
        eng.metrics.close()
        recs = [
            json.loads(line) for line in mpath.read_text().splitlines()
        ]
        breach = [r for r in recs if r["kind"] == "slo_breach"]
        assert breach and breach[0]["objective"] == "ttft_p99"
        assert breach[0]["burn_rate_fast"] >= 1.0
        dump = recorder.dump("test")
        ring = [
            r for r in load_dump(dump)["records"]
            if r["kind"] == "slo_breach"
        ]
        assert ring and ring[0]["objective"] == "ttft_p99"

    def test_disabled_exposition_byte_identical(self, params):
        """The disabled pin: an engine with neither --slo nor request
        tracing renders /metricsz byte-identical to the same stats
        with the (absent anyway) slo/reqtrace keys stripped — i.e.
        the features off contribute zero series."""
        eng = ServeEngine(SPEC, params, slots=1, prefill_len=8)
        eng.submit([1, 2, 3], 2)
        eng.run()
        stats = eng.stats()
        assert "slo" not in stats and "reqtrace" not in stats
        stripped = {
            k: v for k, v in stats.items()
            if k not in ("slo", "reqtrace")
        }
        assert render_serve(stats, up=True) == render_serve(
            stripped, up=True
        )
        assert "ddp_tpu_slo_" not in render_serve(stats, up=True)

    def test_new_base_gauges_render_and_lint(self, params):
        """TPOT/queue-wait summaries + the tokens counter: the new
        always-on serve telemetry this PR's aggregator consumes."""
        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8)
        eng.submit([1, 2, 3], 4)
        eng.run()
        text = render_serve(eng.stats(), up=True)
        n = validate_promtext(text)
        assert n > 0
        assert "ddp_tpu_serve_tpot_seconds_count 1" in text
        assert "ddp_tpu_serve_queue_wait_seconds_count 1" in text
        assert "ddp_tpu_serve_tokens_total 4" in text

    def test_build_info_on_both_renderers(self):
        from ddp_tpu.obs.recorder import build_info

        bi = build_info()
        assert set(bi) - {"libtpu"} == {
            "version", "jax", "jaxlib", "backend", "platform",
            "device_kind", "device_count",
        }
        serve_text = render_serve({"build_info": bi})
        train_text = render_train({"build_info": bi})
        validate_promtext(serve_text)
        validate_promtext(train_text)
        line = f'version="{bi["version"]}"'
        assert line in serve_text and line in train_text
        assert "ddp_tpu_build_info{" in serve_text
        # absent key -> no gauge (pre-build-info snapshots unchanged)
        assert "ddp_tpu_build_info" not in render_train({})


class TestAggregator:
    def _drive(self, params, **ekw):
        eng = ServeEngine(SPEC, params, slots=2, prefill_len=8, **ekw)
        eng.submit([1, 2, 3], 4)
        eng.submit([4, 5], 3)
        eng.run()
        return eng

    def test_fleet_view_across_two_scraped_endpoints(self, params):
        """THE acceptance pin: two live servers (one with a seeded
        breach), scraped over HTTP, merged into one fleet view whose
        counts are EXACT and whose worst-SLO pointer names the sick
        endpoint."""
        from ddp_tpu.obs.aggregate import merge_fleet, render_fleet, scrape_endpoint
        from ddp_tpu.serve.server import LMServer

        healthy = self._drive(params)
        sick = self._drive(
            params,
            slo=SLOEngine(
                "ttft_p99<0.000001s", min_eval_interval_s=0.0
            ),
        )
        with LMServer(healthy) as s1, LMServer(sick) as s2:
            views = [
                scrape_endpoint(s1.url), scrape_endpoint(s2.url),
            ]
        assert all(v["ok"] for v in views)
        assert all(v["metricsz_samples"] > 0 for v in views)
        fleet = merge_fleet(views)
        assert fleet["healthy"] == 2 and fleet["unhealthy"] == 0
        # Exact merged counts: 2 requests per endpoint, ttft count 4.
        agg = fleet["aggregate"]
        assert agg["requests_by_status"] == {"complete": 4}
        assert agg["ttft_s"]["count"] == 4
        assert agg["tokens_total"] == (
            healthy.tokens_emitted_total + sick.tokens_emitted_total
        )
        worst = fleet["slo_worst"]
        assert worst["endpoint"] == views[1]["endpoint"]  # the sick one
        assert worst["objective"] == "ttft_p99" and worst["breached"]
        text = render_fleet(fleet)
        assert "SLO-BREACHED" in text and "fleet view" in text
        # a dead endpoint renders as a hole, not a crash
        from ddp_tpu.obs.aggregate import scrape_endpoint as scrape

        dead = scrape("http://127.0.0.1:9", timeout=0.5)
        fleet2 = merge_fleet(views + [dead])
        assert fleet2["unhealthy"] == 1

    def test_offline_metrics_files_merge(self, params, tmp_path):
        """Offline mode: per-rank JSONL streams reconstruct the same
        fleet shape — summaries rebuilt and merged exactly."""
        from ddp_tpu.obs.aggregate import load_metrics_file, merge_fleet
        from ddp_tpu.utils.metrics import MetricsWriter

        paths = []
        for i in range(2):
            p = tmp_path / f"rank{i}.jsonl"
            eng = self._drive(
                params, metrics=MetricsWriter(str(p)),
            )
            eng.metrics.close()
            paths.append(str(p))
        # one stream with a torn tail line: must still load
        with open(paths[0], "a") as f:
            f.write('{"kind": "serve_request", "trunc')
        views = [load_metrics_file(p) for p in paths]
        fleet = merge_fleet(views)
        assert fleet["healthy"] == 2
        assert fleet["aggregate"]["requests_by_status"] == {"complete": 4}
        assert fleet["aggregate"]["ttft_s"]["count"] == 4
        assert fleet["aggregate"]["tpot_s"]["count"] == 4

    def test_cli_end_to_end(self, params, tmp_path):
        """scripts/obs_aggregate.py: offline targets, JSON output,
        exit status reflects fleet health."""
        import os
        import subprocess
        import sys

        from ddp_tpu.utils.metrics import MetricsWriter

        p = tmp_path / "m.jsonl"
        eng = self._drive(params, metrics=MetricsWriter(str(p)))
        eng.metrics.close()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "scripts", "obs_aggregate.py"),
                "--json", str(p),
            ],
            capture_output=True, text=True, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr
        fleet = json.loads(proc.stdout)
        assert fleet["healthy"] == 1
        assert fleet["aggregate"]["requests_by_status"] == {"complete": 2}
        missing = subprocess.run(
            [
                sys.executable,
                os.path.join(repo, "scripts", "obs_aggregate.py"),
                str(tmp_path / "nope.jsonl"),
            ],
            capture_output=True, text=True, cwd=repo,
        )
        assert missing.returncode == 1
