"""Host milliseconds a step costs the Trainer loop: the benchmark's
spans around the loader fetch and the dispatch of ``train_step``,
summed over the run and divided by the steps."""

NAME = "train_host_ms_per_step"
UNIT = "ms"
LAYER = "Trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    sp = run.spans
    n = sp.count.get("bench.dispatch", 0) if sp else 0
    if not n:
        return None
    host = sp.total_s["bench.loader_fetch"] + sp.total_s["bench.dispatch"]
    return host / n * 1e3
