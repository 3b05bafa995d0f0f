"""Host milliseconds one engine step costs in the GLM-5 cell: the
program's ``serve.step`` span less the ``serve.sample`` waits inside
it, as ``serve_host_ms_per_step`` reads it (its arithmetic, imported),
over the steps of the untraced blocks. A step here dispatches a decode
program and at most one prefill chunk, and, at the last step of a
request that asked for it, the copy of what its lane selected
(``jit_serve_lane_selection``)."""

from benchmarks.harness import program_spans as ps
from benchmarks.layer_metrics import _gd_common as gd
from benchmarks.layer_metrics import serve_host_ms_per_step as plain

NAME = "serve_gd_host_ms_per_step"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    if not gd.is_glm(run):
        return None
    return ps.both(NAME, lambda traced: plain._value(run, traced))
