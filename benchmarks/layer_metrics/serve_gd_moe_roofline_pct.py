"""The grouped expert kernels' share of their roofline in the GLM-5
cell: the least time the chip could take for what a routed layer's two
calls had to do (``harness/glm_dsa_flops.py``: the pairs held here, the
held experts those pairs are expected to hit, whose three matrices
cross once a call), the larger of its bytes over the HBM's peak and its
operations over the bf16 peak, over the device time the two kernels
take a call. The need is counted CALL BY CALL and then averaged: a
decode step routes every lane's token (16: ~8 pairs held, 6-7 of the 16
experts hit) and a prefill chunk its real positions (~1,000 pairs, all
16), and the experts hit are no linear function of the tokens, so the
need of the MEAN call (~120 tokens: all 16 experts) is not the mean
need; taken that way a window of 133 steps and 8 chunks read 168% (my
chip run, PR 43, second round). A call's tokens come from the program's
``serve.decode_selected`` / ``serve.chunk_selected`` records inside the
traced blocks; the pairs held, the engine's ``moe_pairs_held_total``
while those blocks were sampled, go to the calls by their tokens; the
time is the kernels' executions in the trace."""

from benchmarks.harness import glm_dsa_flops as gf
from benchmarks.layer_metrics import _gd_common as gd

NAME = "serve_gd_moe_roofline_pct"
UNIT = "%"
LAYER = "Kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    d = gd.delta(run, "traced") if gd.is_glm(run) else None
    spent = gd.kernel_seconds(run, gd.GROUPED)
    ran = gd.kernel_events(run, gd.GROUPED[1])
    if not d or spent is None or not ran:
        return None
    sizes = run.counters["sizes"]
    # an idle lane rides along: a decode step routes every lane's token
    tokens = ([run.counters["glm_dsa_slots"]]
              * len(gd.traced_spans(run, "serve.decode_selected"))
              + [e[4][2] for e in gd.traced_spans(run, "serve.chunk_selected")])
    expected = gf.routed_layers(sizes) * sum(
        gf.pairs_held(sizes, t) for t in tokens)
    if not expected:
        return None
    # held over expected: what the router's choices made of the share
    k = d["moe_pairs_held_total"] / expected
    least = sum(
        gd.least_seconds(
            run,
            gf.grouped_expert_bytes(sizes, k * gf.pairs_held(sizes, t),
                                    gf.experts_hit(sizes, k * t)),
            gf.grouped_expert_flops(sizes, k * gf.pairs_held(sizes, t)))
        for t in tokens)
    return least / len(tokens) / (spent / ran) * 100.0
