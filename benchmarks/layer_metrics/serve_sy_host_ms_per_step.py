"""Host milliseconds one engine step costs in the SambaY cell: the
program's ``serve.step`` span less the ``serve.sample`` waits inside
it, as ``serve_host_ms_per_step`` reads it (its arithmetic, imported),
over the steps of the untraced blocks."""

from benchmarks.harness import program_spans as ps
from benchmarks.layer_metrics import _sy_common as sy
from benchmarks.layer_metrics import serve_host_ms_per_step as plain

NAME = "serve_sy_host_ms_per_step"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    if not sy.is_sambay(run):
        return None
    return ps.both(NAME, lambda traced: plain._value(run, traced))
