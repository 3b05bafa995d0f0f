"""Seconds of set-up the program spends lowering its programs to MLIR
modules (Mosaic's lowering of a Pallas kernel's body is in here): the
union of its ``compile.lower`` records, one per JAX
``jaxpr_to_mlir_module_duration`` event."""

from benchmarks.layer_metrics import _setup_common as su

NAME = "setup_lower_s"
UNIT, LAYER, MOVES, SOURCE = su.UNIT, su.LAYER, su.MOVES, su.SOURCE


def read(run):
    records = su.named(run, "compile.lower")
    return su.say(NAME, su.union_s(records), {"records": len(records)})
