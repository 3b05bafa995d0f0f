"""Seconds of set-up the program spends tracing its programs to jaxprs:
the union of its ``compile.trace`` records, one per JAX
``jaxpr_trace_duration`` event. Nested records are covered once."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_trace_s"
UNIT, LAYER, MOVES, SOURCE = su.UNIT, su.LAYER, su.MOVES, su.SOURCE


def read(run):
    records = su.named(run, "compile.trace")
    return su.say(NAME, su.union_s(records), {"records": len(records)})
