"""Declarative knob search spaces, one per tunable site.

A **site** is a place the codebase already exposes a measured knob
surface: the serve scheduler (``min_bucket`` / ``prefill_chunk`` /
``step_token_budget`` / spec γ / ``page_size`` / ``kv_pages``), the
zero optimizer (``bucket_mb`` × ``gather_dtype`` × hier-vs-flat), and
the Pallas decode kernel (``block_k``). Each site enumerates a small
grid and filters it through a **validity predicate that IS the
engine's own construction validation** — ``serve_space`` calls
``serve.engine.resolve_engine_knobs`` (the exact function
``ServeEngine.__init__`` runs) and ``zero_space`` calls
``parallel.zero.build_layout`` + the gather-dtype table — so the
tuner can never propose a config the CLI would reject: there is no
second copy of the rules to drift.

Candidates that resolve to the same effective config (pow2 snapping
makes grids alias: ``min_bucket 5`` and ``8`` both resolve to 8)
dedupe on the resolved tuple — measuring an alias twice would charge
wall-clock for zero information. Every drop is counted and reported
(``proposed`` vs ``valid`` vs ``aliased``), never silent.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass(frozen=True)
class Candidate:
    """One proposable knob assignment at a site."""

    site: str
    config: tuple[tuple[str, Any], ...]  # sorted (knob, value) pairs

    @property
    def knobs(self) -> dict[str, Any]:
        return dict(self.config)

    def key(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.config)


def _cand(site: str, knobs: dict[str, Any]) -> Candidate:
    return Candidate(site=site, config=tuple(sorted(knobs.items())))


@dataclass
class SpaceReport:
    """An enumerated site: the valid candidates plus the accounting
    that proves nothing was silently capped."""

    site: str
    proposed: int = 0  # raw grid size
    rejected: int = 0  # failed the engine's own validation
    aliased: int = 0  # valid but resolved identical to an earlier one
    candidates: list[Candidate] = field(default_factory=list)
    # candidate.key() → the resolved effective config (what the engine
    # would actually run); measurement and provenance use this, not
    # the raw proposal.
    resolved: dict[str, dict] = field(default_factory=dict)


# ---- serve scheduler site -------------------------------------------

# Knob grids: small by design — the cost model prunes before
# wall-clock, but the grid itself should stay enumerable in one
# report line.
SERVE_MIN_BUCKETS = (4, 8, 16)
SERVE_CHUNKS = (16, 32, 64)
SERVE_BUDGET_SCALES = (1.0, 1.5, 2.0)  # × the floor for the config


def serve_space(
    spec,
    *,
    slots: int = 4,
    prefill_len: Optional[int] = None,
    spec_tokens: tuple[int, ...] = (0,),
    page_sizes: tuple[int, ...] = (0,),
    draft_spec=None,
) -> SpaceReport:
    """Enumerate the serve scheduler's knob surface.

    γ values other than 0 and paged values other than 0 are only
    proposed when the caller actually has a draft model / wants the
    paged layout in scope — the tuner tunes what the deployment can
    run, not the whole engine feature matrix. Validity and resolution
    both come from ``resolve_engine_knobs``; budget candidates are
    expressed as scales of each config's own starvation floor so the
    grid tracks the validity frontier instead of fighting it.
    """
    from ddp_tpu.serve.engine import resolve_engine_knobs

    report = SpaceReport(site="serve")
    for mb, ck, bscale, gamma, psize in itertools.product(
        SERVE_MIN_BUCKETS,
        SERVE_CHUNKS,
        SERVE_BUDGET_SCALES,
        spec_tokens,
        page_sizes,
    ):
        report.proposed += 1
        knobs = {
            "min_bucket": mb,
            "prefill_chunk": ck,
            "spec_tokens": gamma,
            "page_size": psize,
        }
        try:
            base = resolve_engine_knobs(
                spec,
                slots=slots,
                prefill_len=prefill_len,
                prefill_chunk=ck,
                min_bucket=mb,
                page_size=psize,
                spec_tokens=gamma,
                draft_spec=draft_spec,
                has_draft_params=draft_spec is not None,
            )
            # Budget floor for THIS config's resolved bucket geometry.
            floor = (
                base["min_bucket"]
                + slots * base["tokens_per_decode"]
            )
            budget = int(round(floor * bscale))
            resolved = resolve_engine_knobs(
                spec,
                slots=slots,
                prefill_len=prefill_len,
                prefill_chunk=ck,
                min_bucket=mb,
                step_token_budget=budget,
                page_size=psize,
                spec_tokens=gamma,
                draft_spec=draft_spec,
                has_draft_params=draft_spec is not None,
            )
        except ValueError:
            report.rejected += 1
            continue
        knobs["step_token_budget"] = budget
        cand = _cand("serve", knobs)
        eff = {
            k: resolved[k]
            for k in (
                "chunk",
                "min_bucket",
                "step_token_budget",
                "spec_tokens",
                "tokens_per_decode",
                "page_size",
                "kv_pages",
            )
        }
        if any(r == eff for r in report.resolved.values()):
            report.aliased += 1
            continue
        report.candidates.append(cand)
        report.resolved[cand.key()] = eff
    return report


# ---- zero optimizer site --------------------------------------------

ZERO_BUCKET_MB = (1.0, 4.0, 16.0)
ZERO_GATHER_DTYPES = ("fp32", "bf16")


def zero_space(
    params,
    world: int,
    *,
    dcn: int = 1,
) -> SpaceReport:
    """bucket_mb × gather_dtype × hier, validated by the strategy's
    own constructors (``build_layout`` raises on a bad bucket_mb; the
    gather dtype must be in the strategy's table; hier needs a DCN
    axis)."""
    from ddp_tpu.parallel.zero import GATHER_DTYPES, build_layout

    report = SpaceReport(site="zero")
    hiers = (False, True) if dcn > 1 else (False,)
    for mb, gd, hier in itertools.product(
        ZERO_BUCKET_MB, ZERO_GATHER_DTYPES, hiers
    ):
        report.proposed += 1
        if gd not in GATHER_DTYPES:
            report.rejected += 1
            continue
        try:
            layout = build_layout(params, world, bucket_mb=mb)
        except ValueError:
            report.rejected += 1
            continue
        knobs = {
            "zero_bucket_mb": mb,
            "zero_gather_dtype": gd,
            "hier": hier,
        }
        cand = _cand("zero", knobs)
        eff = dict(knobs)
        eff["buckets"] = len(layout.buckets)
        eff["padded_total"] = layout.padded_total
        if any(r == eff for r in report.resolved.values()):
            report.aliased += 1
            continue
        report.candidates.append(cand)
        report.resolved[cand.key()] = eff
    return report


# ---- Pallas decode-block site ---------------------------------------

DECODE_BLOCKS = (32, 64, 128, 256, 512)


def decode_block_space(total_len: int) -> SpaceReport:
    """``block_k`` for ``ops/decode.flash_decode_attention``.

    ``ops.flash.pick_block`` snaps each request to the largest
    tile-aligned divisor (rejecting lane lengths that have none), and
    the snap makes grids alias hard (all requests ≥ L collapse to L):
    the dedupe is what keeps TPU measurement cheap.
    """
    import jax.numpy as jnp

    from ddp_tpu.ops.flash import pick_block

    report = SpaceReport(site="decode_block")
    for bk in DECODE_BLOCKS:
        report.proposed += 1
        try:
            eff = {"block_k": pick_block(total_len, bk, jnp.float32)}
        except ValueError:
            report.rejected += 1
            continue
        knobs = {"block_k": bk}
        cand = _cand("decode_block", knobs)
        if any(r == eff for r in report.resolved.values()):
            report.aliased += 1
            continue
        report.candidates.append(cand)
        report.resolved[cand.key()] = eff
    return report
