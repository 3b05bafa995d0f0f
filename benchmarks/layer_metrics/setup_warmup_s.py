"""Seconds of set-up ``ServeEngine.warmup()`` takes: the program's
``startup.warmup`` records. The ``#`` line gives each call under it
(``startup.warmup_program``: program, width) and the wait for the
device (``startup.warmup_wait``)."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_warmup_s"
UNIT, LAYER, MOVES, SOURCE = su.UNIT, su.LAYER, su.MOVES, su.SOURCE


def read(run):
    calls = {
        "/".join(str(n) for n in e[4]): e[2]
        for e in su.named(run, "startup.warmup_program")
    }
    waits = su.named(run, "startup.warmup_wait")
    return su.say(NAME, su.union_s(su.named(run, "startup.warmup")), {
        "programs_s": calls, "wait_s": sum(e[2] for e in waits),
    })
