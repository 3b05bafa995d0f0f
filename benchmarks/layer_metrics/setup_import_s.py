"""Seconds of set-up the program spends importing: the union of its
``startup.import`` records (the whole import of its heavy modules,
stamped at their first line and recorded at their last, and of each
third-party package that costs over a quarter of a second, around the
import statement). The ``#`` line names the dearest modules; they nest,
so their seconds do not add up."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_import_s"
UNIT, LAYER, MOVES, SOURCE = su.UNIT, su.LAYER, su.MOVES, su.SOURCE


def read(run):
    records = su.named(run, "startup.import")
    modules: dict = {}
    for e in records:
        module = str(e[4][0]) if e[4] else "?"
        modules[module] = modules.get(module, 0.0) + e[2]
    return su.say(NAME, su.union_s(records),
                  {"dearest_modules_s": su.dearest(modules)})
