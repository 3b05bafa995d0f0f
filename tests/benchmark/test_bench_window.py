"""The end-to-end reading is the whole-window quotient: all work over
all time, so a stall moves it; the block median printed beside it is
the diagnostic that one stall cannot move."""

import pytest

from benchmarks.harness.window import (
    Block, describe, median_block_rate, slowest_block, stall_share,
    window_quotient,
)


def series(seconds: list[float], work: float = 18432.0) -> list[Block]:
    out, t = [], 100.0
    for s in seconds:
        out.append(Block(t, t + s, work))
        t += s
    return out


EVEN = [2.0] * 17


@pytest.mark.parametrize("stall_at", [0, 8, 16])
def test_one_stall_moves_quotient_not_median(stall_at):
    secs = list(EVEN)
    secs[stall_at] += 1.2  # a host stall of over a second in one block
    blocks = series(secs)
    assert median_block_rate(blocks) == pytest.approx(18432 / 2.0)
    q = window_quotient(blocks)
    assert q == pytest.approx(17 * 18432 / 35.2)
    assert q < 0.97 * median_block_rate(blocks)  # PR 22's 3.4% outlier
    i, rel = slowest_block(blocks)
    assert i == stall_at and rel == pytest.approx(2.0 / 3.2)
    assert stall_share(blocks) == pytest.approx(1.2 / 35.2)


def test_all_blocks_slow_moves_both():
    slow = series([2.0 * 1.034] * 17)
    fast = series(EVEN)
    for reading in (median_block_rate, window_quotient):
        assert reading(slow) == pytest.approx(reading(fast) / 1.034)
    assert stall_share(slow) == pytest.approx(0.0, abs=1e-12)


def test_gap_between_blocks_counts_in_the_quotient_only():
    blocks = series(EVEN)
    for b in blocks[9:]:
        b.start += 0.9  # something ran between two blocks
        b.end += 0.9
    assert median_block_rate(blocks) == pytest.approx(9216.0)
    assert window_quotient(blocks) == pytest.approx(17 * 18432 / 34.9)
    assert stall_share(blocks) == pytest.approx(0.9 / 34.9)


def test_even_count_averages_the_middle_two():
    blocks = series([2.0, 2.0, 4.0, 4.0])
    assert median_block_rate(blocks) == pytest.approx((9216 + 4608) / 2)


def test_describe_carries_both_readings_and_the_series():
    d = describe(series([2.0, 2.0, 3.0]), "tokens/s/chip")
    assert d["blocks"] == 3 and len(d["block_rates"]) == 3
    assert d["median_block_rate"] > d["window_quotient"]
    assert d["slowest_block"] == 2


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        median_block_rate([])


def test_empty_window_has_no_quotient_either():
    with pytest.raises(ValueError):
        window_quotient([])


@pytest.mark.parametrize("stalls", [1, 4])
def test_quotient_pays_every_stall_the_median_none(stalls):
    # a later PR that adds a periodic stall (a flush, a GC, a lock) has
    # to show in the end-to-end reading: all work over all time
    secs = list(EVEN)
    for i in range(stalls):
        secs[3 * i] += 1.5
    blocks = series(secs)
    assert median_block_rate(blocks) == pytest.approx(9216.0)
    wall = 34.0 + 1.5 * stalls
    assert window_quotient(blocks) == pytest.approx(17 * 18432 / wall)
    assert stall_share(blocks) == pytest.approx(1.5 * stalls / wall)


def test_quotient_of_mixed_blocks_is_their_work_over_their_time():
    # a serving window: decode-only blocks at 77 tokens/s, blocks with
    # prefill lower; the quotient weighs each by its time, not its rank
    rates = [77.0] * 8 + [74.0, 72.0, 70.0, 66.0, 62.0, 60.0, 55.0, 53.0]
    blocks = [Block(i * 2.0, i * 2.0 + 2.0, r * 2.0)
              for i, r in enumerate(rates)]
    assert window_quotient(blocks) == pytest.approx(sum(rates) / len(rates))
    assert median_block_rate(blocks) > window_quotient(blocks)


def test_describe_names_no_third_reading():
    d = describe(series(EVEN), "tokens/s")
    assert {k for k in d if k.endswith("_rate") or k == "window_quotient"} \
        == {"median_block_rate", "window_quotient"}
