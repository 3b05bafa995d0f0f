"""Window arithmetic: all the work over all the time, and what says why
a run read low.

A measured window is cut into blocks, each closed by a fence and timed
on its own. The end-to-end rate is the whole-window QUOTIENT: all the
window's work over all its wall time, first start to last end, stalls
included, because a stall is time a user pays for. The MEDIAN of the
blocks' rates is printed beside it in every run and kept as a per-layer
diagnostic, so an outlier can be diagnosed: one slow block moves the
quotient and not the median (``stall_share`` says by how much); every
block slow moves both.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass, field


@dataclass
class Block:
    start: float  # host clock, seconds
    end: float
    work: float  # tokens (or steps) completed inside [start, end]
    steps: int = 0
    traced: bool = False
    extra: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def rate(self) -> float:
        return self.work / self.seconds


def block_rates(blocks: list[Block]) -> list[float]:
    return [b.rate for b in blocks]


def median_block_rate(blocks: list[Block]) -> float:
    """The diagnostic reading that one stall cannot move. Even counts
    average the middle two."""
    if not blocks:
        raise ValueError("a window needs at least one block")
    return statistics.median(block_rates(blocks))


def window_quotient(blocks: list[Block]) -> float:
    """The end-to-end reading: all work over all wall time, first start
    to last end, gaps between blocks included."""
    if not blocks:
        raise ValueError("a window needs at least one block")
    return sum(b.work for b in blocks) / (blocks[-1].end - blocks[0].start)


def stall_share(blocks: list[Block]) -> float:
    """Share of the window's wall time beyond what its blocks would
    take at the median block's pace: 1 - n*median(block seconds per
    unit work)*work / wall. Zero for an even window; one stall of s
    seconds in a window of w reads about s/w."""
    wall = blocks[-1].end - blocks[0].start
    worked = [b for b in blocks if b.work > 0]
    if not worked:
        return 0.0
    pace = statistics.median(b.seconds / b.work for b in worked)
    ideal = pace * sum(b.work for b in blocks)
    return max(0.0, 1.0 - ideal / wall)


def slowest_block(blocks: list[Block]) -> tuple[int, float]:
    """(index, its rate over the median rate) of the slowest block."""
    rates = block_rates(blocks)
    i = min(range(len(rates)), key=rates.__getitem__)
    return i, rates[i] / statistics.median(rates)


def describe(blocks: list[Block], unit: str, scale: float = 1.0) -> dict:
    """The diagnostic line every run prints before its result."""
    i, rel = slowest_block(blocks)
    return {
        "unit": unit,
        "blocks": len(blocks),
        "median_block_rate": median_block_rate(blocks) * scale,
        "window_quotient": window_quotient(blocks) * scale,
        "stall_share": stall_share(blocks),
        "slowest_block": i,
        "slowest_over_median": rel,
        "block_rates": [r * scale for r in block_rates(blocks)],
        "block_seconds": [b.seconds for b in blocks],
    }
