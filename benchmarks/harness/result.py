"""What a driver hands back, and the lines a run prints."""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field


@dataclass
class Check:
    """One number compared, beside its limit."""

    name: str
    value: float
    limit: float
    what: str = ""

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def to_json(self) -> dict:
        return {"name": self.name, "value": self.value,
                "limit": self.limit, "ok": self.ok, "what": self.what}


@dataclass
class Run:
    """A finished run. Readers of per-layer metrics take what they need
    from here: ``counters`` (program counters and the harness's own),
    ``spans`` (host spans' totals), ``trace`` (a reduced profiler
    trace, traced runs only), ``blocks``."""

    cell: object
    end_to_end: dict = field(default_factory=dict)  # name -> value
    attempted: int = 0
    failed: int = 0
    checks: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    spans: object = None
    trace: object = None
    blocks: list = field(default_factory=list)
    setup_split: dict = field(default_factory=dict)
    window: dict = field(default_factory=dict)  # window.describe()
    device: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def emit(tag: str, payload: dict) -> None:
    """An earlier line: ``# <tag> <json>``. The result is the LAST line
    and the only one without the leading '#'."""
    print(f"# {tag} " + json.dumps(payload, default=float), flush=True)


def result_line(run: Run, metrics: dict, units: dict,
                breakdown: dict | None = None) -> str:
    out = {
        "correct": run.correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": {
            k: {"value": float(v), "unit": units[k]}
            for k, v in metrics.items()
        },
        "device": run.device,
    }
    if breakdown:
        out["breakdown"] = breakdown
    return json.dumps(out)
