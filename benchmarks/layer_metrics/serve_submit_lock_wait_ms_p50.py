"""What a request waits for the server's lock before it is submitted:
``lock_wait_s`` of the program's ``server.request`` events (call of
``submit_and_wait`` to the lock won, before ``engine.submit``), median
over the requests handed back in the untraced blocks. The engine's own
``ttft_s`` and ``queue_s`` start after it."""

from benchmarks.harness import program_spans as ps

NAME = "serve_submit_lock_wait_ms_p50"
UNIT = "ms"
LAYER = "Serve frontend"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    return ps.both(NAME, lambda traced: ps.request_ms_p50(run, 1, traced))
