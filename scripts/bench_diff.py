#!/usr/bin/env python
"""Provenance-aware diff of two bench JSON sidecars.

    python scripts/bench_diff.py OLD.json NEW.json
    python scripts/bench_diff.py OLD.json NEW.json --threshold 0.1

Bench sidecars (``bench.py`` headline lines, BENCH_EXTRA.json) mix
capture shapes — headline records, ``parsed`` wrappers from the
driver, named side-bench maps — and can mix backends: the ``run_*``
entries run on the CPU too, and comparing a CPU number against an
on-chip one manufactures a 1000x "regression" that means nothing.
This tool
compares ONLY records whose provenance trio (``platform`` /
``backend`` / ``cpu_fallback``) matches between the two files; every
provenance-mismatched pair is reported as skipped, never diffed.

What gets diffed: throughput leaves (``*per_s``/``*per_sec`` keys and
the headline ``value``, higher is better) and latency leaves (``p50``/
``p99`` and ``*_p50_s``-style keys, lower is better). A move past
``--threshold`` (default 5%) in the bad direction is a regression;
exit code is 1 when any regression is flagged, so CI can gate on it.
"""

from __future__ import annotations

import argparse
import json
import sys

PROVENANCE_KEYS = ("platform", "backend", "cpu_fallback", "device_kind")


def load_records(path: str) -> dict[str, dict]:
    """One sidecar file -> {record_name: record_dict}.

    Accepts every shape in the repo's trajectory: a bare headline
    record ({"metric": ...}), a driver wrapper ({"parsed": {...}}),
    the LKG envelope ({"captured": ..., "record": {...}}), and the
    EXTRA map ({name: record, ...}).
    """
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise SystemExit(f"{path}: expected a JSON object")
    if isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    elif isinstance(doc.get("record"), dict):
        doc = doc["record"]
    if "metric" in doc:
        return {str(doc["metric"]): doc}
    out = {}
    for name, rec in doc.items():
        if isinstance(rec, dict) and ("metric" in rec or "value" in rec):
            out[str(rec.get("metric", name))] = rec
    if not out:
        raise SystemExit(f"{path}: no bench records recognized")
    return out


def provenance_matches(a: dict, b: dict) -> tuple[bool, str]:
    """Records are comparable only when every provenance field present
    in BOTH agrees — a record that never says (BENCH_EXTRA entries
    carry device_kind only) is judged on what it does say."""
    for key in PROVENANCE_KEYS:
        if key in a and key in b and a[key] != b[key]:
            return False, f"{key} {a[key]!r} vs {b[key]!r}"
    return True, ""


def _flatten(rec: dict, prefix: str = "") -> dict[str, float]:
    """Dotted-path -> numeric leaf, excluding copied-context subtrees
    (bool is an int subclass — cpu_fallback must not become a leaf)."""
    out: dict[str, float] = {}
    for key, value in rec.items():
        path = f"{prefix}{key}"
        if isinstance(value, bool):
            continue
        if isinstance(value, (int, float)):
            out[path] = float(value)
        elif isinstance(value, dict):
            out.update(_flatten(value, f"{path}."))
    return out


def direction(path: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 not a perf leaf."""
    leaf = path.rsplit(".", 1)[-1]
    if "per_s" in leaf or "per_sec" in leaf or leaf == "tokens_s":
        return +1
    if leaf == "value":  # headline units are all throughput
        return +1
    if leaf in ("p50", "p99") or leaf.endswith(("_p50_s", "_p99_s")):
        return -1
    if leaf.endswith("_ms") and "token" in leaf:
        return -1
    return 0


def diff_records(old: dict, new: dict, threshold: float) -> list[dict]:
    flat_old, flat_new = _flatten(old), _flatten(new)
    flagged = []
    for path in sorted(set(flat_old) & set(flat_new)):
        sign = direction(path)
        if sign == 0:
            continue
        a, b = flat_old[path], flat_new[path]
        if a <= 0:
            continue
        delta = (b - a) / a
        if sign * delta < -threshold:
            flagged.append(
                {
                    "path": path,
                    "old": a,
                    "new": b,
                    "delta": round(delta, 4),
                    "direction": "higher_better" if sign > 0
                    else "lower_better",
                }
            )
    return flagged


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("old", help="baseline sidecar JSON")
    p.add_argument("new", help="candidate sidecar JSON")
    p.add_argument(
        "--threshold", type=float, default=0.05,
        help="relative move in the bad direction that flags a "
        "regression (0.05 = 5%%)",
    )
    args = p.parse_args(argv)

    old_recs = load_records(args.old)
    new_recs = load_records(args.new)
    compared, regressions, skipped = [], [], []
    for name in sorted(set(old_recs) & set(new_recs)):
        ok, why = provenance_matches(old_recs[name], new_recs[name])
        if not ok:
            skipped.append({"metric": name, "provenance": why})
            continue
        compared.append(name)
        for r in diff_records(
            old_recs[name], new_recs[name], args.threshold
        ):
            regressions.append({"metric": name, **r})
    only_old = sorted(set(old_recs) - set(new_recs))
    only_new = sorted(set(new_recs) - set(old_recs))
    print(
        json.dumps(
            {
                "old": args.old,
                "new": args.new,
                "threshold": args.threshold,
                "compared": compared,
                "regressions": regressions,
                **(
                    {"skipped_provenance": skipped} if skipped else {}
                ),
                **({"only_in_old": only_old} if only_old else {}),
                **({"only_in_new": only_new} if only_new else {}),
            }
        )
    )
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())
