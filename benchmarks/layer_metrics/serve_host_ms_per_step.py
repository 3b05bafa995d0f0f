"""Host milliseconds one engine step costs: the program's ``serve.step``
span less the ``serve.sample`` spans inside it (the blocking token
fetch: a wait for the device, not host work), mean over the steps of
the untraced blocks. Where the device's decode step comes down to this,
the host is the floor."""

from benchmarks.harness import program_spans as ps

NAME = "serve_host_ms_per_step"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def _value(run, traced: bool):
    spans = ps.inside(ps.ring(), ps.blocks_of(run, traced))
    steps = [e for e in spans if e[0] == "serve.step"]
    if not steps:
        return None
    waits = sorted((e[1], e[2]) for e in spans if e[0] == ps.WAIT)
    host = 0.0
    for _, t0, dur, _, _ in steps:
        host += dur - sum(d for t, d in waits if t0 <= t and t + d <= t0 + dur)
    return host / len(steps) * 1e3


def read(run):
    return ps.both(NAME, lambda traced: _value(run, traced))
