"""Seconds of set-up the program spends in the backend's compile, or in
loading the executable from the persistent cache: the union of its
``compile.backend`` records. The ``#`` line counts the executables and
the cache hits among them, and names the three dearest programs by
``fun_name`` with their trace / lower / backend seconds."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_backend_s"
UNIT, LAYER, MOVES, SOURCE = su.UNIT, su.LAYER, su.MOVES, su.SOURCE


def read(run):
    programs = su.programs(run)
    return su.say(NAME, su.union_s(su.named(run, "compile.backend")), {
        "executables": sum(p["compiles"] for p in programs.values()),
        "cache_hits": sum(p["cache_hits"] for p in programs.values()),
        "dearest_programs": dict(list(programs.items())[:3]),
    })
