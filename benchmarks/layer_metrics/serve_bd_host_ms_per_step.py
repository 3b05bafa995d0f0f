"""Host milliseconds one engine step costs in the block-diffusion cell:
the program's ``serve.step`` span less the ``serve.sample`` waits inside
it, as ``serve_host_ms_per_step`` reads it (its arithmetic, imported),
over the steps of the untraced blocks. A step here dispatches one
forward over every lane's block and books the report of the one
before."""

from benchmarks.harness import program_spans as ps
from benchmarks.layer_metrics import serve_host_ms_per_step as plain

NAME = "serve_bd_host_ms_per_step"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    if "block_slots" not in run.counters:
        return None
    return ps.both(NAME, lambda traced: plain._value(run, traced))
