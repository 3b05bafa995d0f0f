"""Host milliseconds one engine step costs in the hybrid cell: the
program's ``serve.step`` span less the ``serve.sample`` waits inside
it, as ``serve_host_ms_per_step`` reads it (its arithmetic, imported),
over the steps of the untraced blocks. A step here dispatches at most
one prefill chunk and one decode of all live lanes, and names the live
lanes to the device when their set changed."""

from benchmarks.harness import program_spans as ps
from benchmarks.layer_metrics import _hy_common as hy
from benchmarks.layer_metrics import serve_host_ms_per_step as plain

NAME = "serve_hy_host_ms_per_step"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    if not hy.is_hybrid(run):
        return None
    return ps.both(NAME, lambda traced: plain._value(run, traced))
