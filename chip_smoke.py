#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                  # needs a TPU; anything else exits non-zero
    python3 chip_smoke.py --cpu-rehearsal  # the same control flow at a tiny size on
                                           # the CPU, labelled as a rehearsal

Drives the main path once through the entry points a user calls, at
the full width of the spec-driven causal LM (d_model 1024, 8 heads of
128, vocab 8192, T=2048, bf16, Adam, batch 8 per chip; depth 8), with
random weights from a seed:

  K  scripts/check_kernels.py — the Pallas flash forward+backward and
     flash-decode kernels compile (Mosaic) and agree with their
     references at these shapes.
  A  train.py — a few tens of steps, then a checkpoint.
  B  train.py again, a fresh process — resumes from that checkpoint,
     and its compile time shows the persistent cache hit.
  C  scripts/serve.py — restores the checkpoint, answers /generate
     (greedy, seeded in parallel, an over-long prompt refused),
     healthy before and after, drains on SIGTERM.

This parent is stdlib only and never imports jax: a chip belongs to
one process at a time, so each phase is a child, one after another.
The first child that exits non-zero, times out or fails a check ends
the run with a non-zero exit and no result line. On success the last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as
JAX reported it to the children. Logs, the JSONL streams and
``summary.json`` land under ``chiprun_out/chip_smoke/``; the
checkpoints are deleted on the way out.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import glob
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
WORK = os.path.join(OUT, "work")  # checkpoints: big, removed at exit

# The whole run must fit 1200 s, compilation included.
DEADLINE_S = 1150.0

FULL = dict(
    model_dim=1024, depth=8, heads=8, vocab=8192, seq_len=2048,
    batch=8, steps=24, slots=8, max_new=32, kernel_args=[],
)
# --cpu-rehearsal: every phase and check, nothing about the chip.
TINY = dict(
    model_dim=64, depth=2, heads=2, vocab=512, seq_len=128,
    batch=2, steps=4, slots=2, max_new=8, kernel_args=["--tiny"],
)


class SmokeFailure(Exception):
    """A phase failed; the run ends non-zero."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def tail(path: str, lines: int = 60) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as e:
        return f"(no log: {e})"


def json_lines(path: str) -> list[dict]:
    """Every line of ``path`` that parses as a JSON object."""
    out = []
    with open(path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


class Children:
    """Every process this run started, so that none outlives it."""

    def __init__(self, env: dict, deadline: float):
        self.env = env
        self.deadline = deadline
        self.live: list[subprocess.Popen] = []

    def remaining(self) -> float:
        return self.deadline - time.monotonic()

    def start(self, argv: list[str], log_path: str) -> subprocess.Popen:
        log = open(log_path, "w")
        try:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=HERE, env=self.env,
                stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,  # its own group: killable whole
            )
        finally:
            log.close()  # the child holds its own descriptor
        self.live.append(proc)
        return proc

    def wait(self, proc, name: str, log_path: str, budget: float) -> None:
        try:
            rc = proc.wait(timeout=max(1.0, min(budget, self.remaining())))
        except subprocess.TimeoutExpired:
            self.kill(proc)
            raise SmokeFailure(
                f"{name}: timed out\n--- {log_path} ---\n{tail(log_path)}"
            )
        if rc != 0:
            raise SmokeFailure(
                f"{name}: exit code {rc}\n--- {log_path} ---\n{tail(log_path)}"
            )

    def run(self, name: str, argv: list[str], budget: float) -> tuple[str, float]:
        """One child to completion → (log path, seconds)."""
        log_path = os.path.join(OUT, f"{name}.log")
        t0 = time.monotonic()
        self.wait(self.start(argv, log_path), name, log_path, budget)
        return log_path, time.monotonic() - t0

    def kill(self, proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def kill_all(self) -> None:
        for proc in self.live:
            self.kill(proc)


# ---- phase K: kernels against their references -----------------------


def phase_kernels(ch: Children, size: dict, platform: str) -> dict:
    log, seconds = ch.run(
        "kernels", ["scripts/check_kernels.py", *size["kernel_args"]], 400.0
    )
    recs = json_lines(log)
    info = next((r for r in recs if "build_info" in r), None)
    check(info is not None, f"kernels: no build_info line in {log}")
    bi = info["build_info"]
    check(
        bi["platform"] == platform,
        f"kernels: ran on {bi['platform']!r}, wanted {platform!r}",
    )
    cases = [r for r in recs if "case" in r]
    check(
        bool(cases) and all(r["ok"] for r in cases),
        f"kernels: a case failed\n{tail(log)}",
    )
    if platform == "tpu":
        check(
            all(r["kernel"] == "pallas-compiled" for r in cases),
            "kernels: not compiled under Mosaic",
        )
    say(
        f"phase K ok in {seconds:.1f}s — "
        + ", ".join(
            f"{r['case']} err {r['max_abs_err']:.2e}"
            + (f" (grad {r['grad_max_abs_err']:.2e})"
               if "grad_max_abs_err" in r else "")
            for r in cases
        )
    )
    return {
        "seconds": round(seconds, 1),
        "build_info": bi,
        "cases": cases,
    }


# ---- phases A and B: train, checkpoint, resume -----------------------


def train_argv(size: dict, backend: str, n: int, epochs: int, name: str):
    return [
        "train.py", "--backend", backend, "--model", "causal_lm",
        "--model_dim", str(size["model_dim"]),
        "--model_depth", str(size["depth"]),
        "--num_heads", str(size["heads"]),
        "--vocab_size", str(size["vocab"]),
        "--seq_len", str(size["seq_len"]),
        "--compute_dtype", "bfloat16", "--optimizer", "adam",
        "--lr", "1e-4", "--batch_size", str(size["batch"]),
        "--epochs", str(epochs), "--synthetic_size", str(n),
        "--checkpoint_dir", os.path.join(WORK, "ck"),
        "--metrics_file", os.path.join(OUT, f"{name}.jsonl"),
        # Every step's loss is checked, so every step is logged; the
        # compile ledger gives compile seconds, the collectives of the
        # compiled step and each device's peak memory.
        "--log_interval", "1", "--xprof",
    ]


def phase_train(
    ch: Children, size: dict, platform: str, devices: int,
    *, name: str, resumed: bool,
) -> dict:
    steps = size["steps"]
    n = steps * size["batch"] * devices  # batch_size is per data shard
    # The same command; one more epoch makes the second process resume.
    log, seconds = ch.run(
        name,
        train_argv(size, platform, n, 2 if resumed else 1, name),
        600.0,
    )
    recs = json_lines(os.path.join(OUT, f"{name}.jsonl"))
    start = next((r for r in recs if r.get("kind") == "run_start"), None)
    check(start is not None, f"{name}: no run_start record")
    bi = start["build_info"]
    check(
        bi["platform"] == platform and bi["device_count"] == devices,
        f"{name}: ran on {bi['platform']} x{bi['device_count']}, "
        f"wanted {platform} x{devices}",
    )
    check(
        start["data_shards"] == devices
        and start["global_batch_size"] == size["batch"] * devices,
        f"{name}: batch not split over {devices} device(s): {start}",
    )
    if platform == "tpu":
        check(
            start.get("attention")
            == {"impl": "flash", "kernel": "pallas-compiled"},
            f"{name}: attention built as {start.get('attention')}",
        )
    first_epoch = 1 if resumed else 0
    check(
        start["start_epoch"] == first_epoch,
        f"{name}: started at epoch {start['start_epoch']}",
    )
    if resumed:
        with open(log, errors="replace") as f:
            check(
                "Resumed from checkpoint epoch 0" in f.read(),
                f"{name}: did not resume\n{tail(log)}",
            )
    step_recs = [r for r in recs if r.get("kind") == "step"]
    check(
        len(step_recs) == steps,
        f"{name}: {len(step_recs)} step records, expected {steps}",
    )
    losses = [r.get("loss") for r in step_recs]
    check(
        all(isinstance(x, float) and math.isfinite(x) for x in losses),
        f"{name}: non-finite loss in {losses}",
    )
    epoch_recs = [r for r in recs if r.get("kind") == "epoch"]
    check(
        [r["epoch"] for r in epoch_recs] == [first_epoch],
        f"{name}: history holds epochs {[r['epoch'] for r in epoch_recs]}",
    )
    epoch = epoch_recs[0]
    for path in (f"epoch_{first_epoch}", "lm_spec.json"):
        check(
            os.path.exists(os.path.join(WORK, "ck", path)),
            f"{name}: checkpoint lacks {path}",
        )
    out = {
        "seconds": round(seconds, 1),
        "steps": steps,
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "compile_s": epoch["compile_s"],
        # Tracing + lowering is Python and never cached; the rest is
        # XLA's compile, which the persistent cache replaces by a load.
        "xla_compile_s": round(epoch["compile_s"] - epoch["lower_s"], 4),
        "compiled_programs": epoch["compiled_programs"],
        "epoch_seconds": epoch["seconds"],
        # The epoch's clock includes the first step's compile; this is
        # the same count over what is left. Set-up information.
        "tokens_per_sec_after_compile": round(
            steps * size["batch"] * devices * size["seq_len"]
            / max(1e-9, epoch["seconds"] - epoch["compile_s"])
        ),
        "compile_cache": start.get("compile_cache"),
        "hbm_peak_bytes_by_device": epoch.get("hbm_peak_bytes_by_device"),
    }
    if platform == "tpu":
        peaks = out["hbm_peak_bytes_by_device"] or []
        check(
            len(peaks) == devices and all(p > 0 for p in peaks),
            f"{name}: per-device peak memory {peaks}",
        )
    if devices > 1:
        # Data parallelism is the point: the compiled step must hold a
        # gradient-sized reduction, not just the scalar metrics'.
        step_prog = next(
            (
                r for r in recs
                if r.get("kind") == "compile" and r.get("label") == "train_step"
            ),
            {},
        )
        coll = out["train_step_collectives"] = step_prog.get("collectives")
        check(
            bool(coll)
            and sum(c["result_bytes"] for c in coll.values()) > 1 << 20,
            f"{name}: no gradient reduction in the compiled step: {coll}",
        )
    say(
        f"phase {name} ok in {seconds:.1f}s — {steps} steps, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, compile {out['compile_s']}s, "
        f"epoch {out['epoch_seconds']}s "
        f"({out['tokens_per_sec_after_compile']} tokens/s after compile)"
    )
    return out


# ---- phase C: restore, serve, drain ----------------------------------


def http(method: str, url: str, body: dict | None = None, timeout=120.0):
    """→ (status, parsed JSON body). 4xx/5xx are answers, not errors."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")
    except OSError as e:  # refused, reset, timed out: the server is gone
        raise SmokeFailure(f"{method} {url}: {e}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_serve(ch: Children, size: dict, platform: str) -> dict:
    log = os.path.join(OUT, "serve.log")
    port = free_port()
    t0 = time.monotonic()
    proc = ch.start(
        [
            "scripts/serve.py", "--checkpoint_dir", os.path.join(WORK, "ck"),
            "--port", str(port), "--slots", str(size["slots"]),
            "--metrics_file", os.path.join(OUT, "serve.jsonl"),
        ],
        log,
    )
    # The startup JSON is printed once the program set is compiled and
    # the socket is bound.
    startup = None
    budget = min(500.0, ch.remaining())
    while startup is None:
        check(
            proc.poll() is None,
            f"serve: exited {proc.returncode} during startup\n{tail(log)}",
        )
        check(
            time.monotonic() - t0 < budget,
            f"serve: no startup line in {budget:.0f}s\n{tail(log)}",
        )
        startup = next(
            (r for r in json_lines(log) if "serving" in r), None
        )
        time.sleep(0.5)
    startup_s = time.monotonic() - t0
    url = startup["serving"]
    bi = startup["build_info"]
    check(
        bi["platform"] == platform,
        f"serve: runs on {bi['platform']!r}, wanted {platform!r}",
    )
    if platform == "tpu":
        check(
            startup["decode_attn"] == "flash"
            and startup["decode_kernel"] == "pallas-compiled",
            f"serve: decode attention built as {startup['decode_attn']}/"
            f"{startup['decode_kernel']}",
        )
    check(
        startup["total_len"] == size["seq_len"]
        and startup["vocab_size"] == size["vocab"]
        and startup["slots"] == size["slots"],
        f"serve: restored a different model: {startup}",
    )

    def healthy(when: str) -> None:
        status, body = http("GET", url + "/healthz")
        check(
            status == 200 and body.get("ok") is True,
            f"serve: /healthz {when}: {status} {body}\n{tail(log)}",
        )

    def generate(body: dict) -> list[int]:
        status, out = http("POST", url + "/generate", body)
        check(
            status == 200 and out.get("status") == "complete",
            f"serve: /generate {body} -> {status} {out}\n{tail(log)}",
        )
        toks = out["tokens"]
        check(
            len(toks) == body["max_new_tokens"]
            and all(0 <= t < size["vocab"] for t in toks),
            f"serve: bad tokens for {body}: {toks}",
        )
        return toks

    healthy("before traffic")
    new = size["max_new"]
    t_req = time.monotonic()
    greedy = {"prompt_tokens": [1, 2, 3, 4], "max_new_tokens": new}
    first = generate(greedy)
    check(
        generate(greedy) == first,
        "serve: the same greedy request gave two different answers",
    )
    # A burst shares one running decode batch (continuous batching).
    seeded = [
        {
            "prompt_tokens": [7, 8, 9], "max_new_tokens": new // 2,
            "temperature": 0.8, "seed": seed,
        }
        for seed in range(1, 7)
    ]
    with concurrent.futures.ThreadPoolExecutor(len(seeded)) as pool:
        bursts = list(pool.map(generate, seeded))
    # Over-long prompt: refused at the door with the documented status.
    too_long = list(range(startup["prefill_len"] + 1))
    status, body = http(
        "POST", url + "/generate",
        {"prompt_tokens": [t % size["vocab"] for t in too_long],
         "max_new_tokens": 4},
    )
    check(
        status == 400 and body.get("error") == "prompt_too_long",
        f"serve: over-long prompt -> {status} {body}",
    )
    requests_s = time.monotonic() - t_req
    status, stats = http("GET", url + "/stats")
    check(status == 200, f"serve: /stats -> {status}")
    check(
        stats["compile_counts"] == startup["compile_counts"],
        "serve: traffic compiled new programs: "
        f"{startup['compile_counts']} -> {stats['compile_counts']}",
    )
    healthy("after traffic")
    # SIGTERM: drain running lanes, exit 0.
    proc.send_signal(signal.SIGTERM)
    ch.wait(proc, "serve", log, 90.0)
    drained = next((r for r in json_lines(log) if "draining" in r), None)
    check(
        drained is not None and drained["drained"] is True,
        f"serve: no clean drain\n{tail(log)}",
    )
    served = 2 * new + sum(len(t) for t in bursts)
    say(
        f"phase C ok — startup {startup_s:.1f}s, {2 + len(seeded)} requests "
        f"({served} tokens) in {requests_s:.1f}s, decode "
        f"{startup['decode_attn']}/{startup['decode_kernel']}, "
        "over-long prompt refused (400), drained"
    )
    return {
        "seconds": round(time.monotonic() - t0, 1),
        "startup_seconds": round(startup_s, 1),
        "requests_seconds": round(requests_s, 1),
        "requests": 2 + len(seeded),
        "tokens_served": served,
        "decode_attn": startup["decode_attn"],
        "decode_kernel": startup["decode_kernel"],
        "prefill_attn": startup["prefill_attn"],
        "compile_counts": startup["compile_counts"],
        "compile_cache": startup["compile_cache"],
        "build_info": bi,
    }


# ---- the run ---------------------------------------------------------


def cache_entries(cache_dir: str | None) -> int | None:
    if not cache_dir or not os.path.isdir(cache_dir):
        return None
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="tiny sizes on the CPU: rehearses the control flow and "
        "says nothing about the chip",
    )
    args = p.parse_args()
    rehearsal = args.cpu_rehearsal
    platform = "cpu" if rehearsal else "tpu"
    size = TINY if rehearsal else FULL

    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(WORK)
    # JAX_PLATFORMS=tpu makes JAX refuse to start without a chip
    # instead of carrying on on the CPU.
    env = dict(os.environ, JAX_PLATFORMS=platform)
    ch = Children(env, time.monotonic() + DEADLINE_S)
    summary: dict = {"ok": False, "cpu_rehearsal": rehearsal, "phases": {}}
    t0 = time.monotonic()
    try:
        k = summary["phases"]["K"] = phase_kernels(ch, size, platform)
        bi = k["build_info"]
        device = {
            "platform": bi["platform"],
            "kind": bi["device_kind"],
            "count": bi["device_count"],
        }
        summary["device"] = device
        summary["versions"] = {
            key: bi.get(key) for key in ("version", "jax", "jaxlib", "libtpu")
        }
        say(f"device {device}  versions {summary['versions']}")
        a = summary["phases"]["A"] = phase_train(
            ch, size, platform, device["count"],
            name="train_a", resumed=False,
        )
        a["cache_entries"] = cache_entries(a["compile_cache"])
        b = summary["phases"]["B"] = phase_train(
            ch, size, platform, device["count"],
            name="train_b", resumed=True,
        )
        b["cache_entries"] = cache_entries(b["compile_cache"])
        say(
            f"compile seconds: cold (A) {a['compile_s']} of which XLA "
            f"{a['xla_compile_s']}, warm (B) {b['compile_s']} of which "
            f"XLA {b['xla_compile_s']}, cache {a['compile_cache']}"
        )
        if not rehearsal:  # the CPU backend keeps no persistent cache
            check(
                b["xla_compile_s"] < 0.25 * a["xla_compile_s"],
                f"persistent compile cache did not hit: phase B spent "
                f"{b['xla_compile_s']}s in XLA's compile against phase "
                f"A's {a['xla_compile_s']}s",
            )
        c = summary["phases"]["C"] = phase_serve(ch, size, platform)
        c["cache_entries"] = cache_entries(c["compile_cache"])
        check(
            c["build_info"]["device_kind"] == device["kind"],
            "serve saw another device than the trainer",
        )
        summary["ok"] = True
    except SmokeFailure as e:
        summary["failure"] = str(e)
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        ch.kill_all()
        shutil.rmtree(WORK, ignore_errors=True)
        summary["seconds"] = round(time.monotonic() - t0, 1)
        # Built only by the image pipeline's loader (data/loader.py
        # gates it off for the LM path), so normally absent here.
        summary["native_library_built"] = bool(
            glob.glob(os.path.join(HERE, "ddp_tpu/native/_build/*.so"))
        )
        # Seconds and rates above are set-up information for this
        # device_kind, not a benchmark; nothing is claimed.
        summary["claim"] = None
        with open(os.path.join(OUT, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
    say(json.dumps(summary))
    result = {"ok": True, "device": device}
    if rehearsal:
        result = {"cpu_rehearsal": True, **result}
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
