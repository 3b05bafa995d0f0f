"""How long a finished answer lies in the engine before its client has
it: ``pickup_s`` of the program's ``server.request`` events (the
engine's ``_finish`` to ``pop_result`` returning the answer, polled
under the lock the engine loop holds), median over the requests handed
back in the untraced blocks."""

from benchmarks.harness import program_spans as ps

NAME = "serve_result_pickup_ms_p50"
UNIT = "ms"
LAYER = "Serve frontend"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    return ps.both(NAME, lambda traced: ps.request_ms_p50(run, 2, traced))
