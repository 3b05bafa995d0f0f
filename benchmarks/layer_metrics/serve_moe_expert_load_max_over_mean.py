"""How uneven the routing is: the fullest expert's rows in a layer call
over the mean expert's (rows routed over experts), averaged over the
timed window's layer calls (counters ``moe_expert_load_max_sum``,
``moe_tokens_routed_total``, ``moe_layer_calls_total``). 1 is perfectly
even; the grouped kernels' tiles and the fullest expert's tail grow
with it."""

from benchmarks.layer_metrics import _bd_common as bd

NAME = "serve_moe_expert_load_max_over_mean"
UNIT = "ratio"
LAYER = "Expert routing"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    d = bd.delta(run, "timed")
    sizes = run.counters.get("sizes")
    if not d or not d.get("moe_tokens_routed_total"):
        return None
    return (d["moe_expert_load_max_sum"] * sizes["num_experts"]
            / d["moe_tokens_routed_total"])
