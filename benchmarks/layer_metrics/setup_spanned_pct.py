"""The share of set-up the program can account for: of the ``setup_s``
seconds before the window opens, the percentage that at least one of
the program's own records covers (the kept store and the ring). The
rest is what no span of the program sees: the interpreter and the
backend coming up, the benchmark's seeded weights."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_spanned_pct"
UNIT = "%"
LAYER, MOVES, SOURCE = su.LAYER, su.MOVES, su.SOURCE


def read(run):
    share = su.spanned_share(run)
    if share is None:
        return None
    return su.say(NAME, 100.0 * share, {
        "setup_s": run.end_to_end["setup_s"],
        "records_kept": len(su.kept(run)), "records_refused": su.refused(),
    })
