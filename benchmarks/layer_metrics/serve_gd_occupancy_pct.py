"""Lanes in use in the GLM-5 cell: the engine's ``active`` over its
lanes, read with each block of the window and averaged."""


NAME = "serve_gd_occupancy_pct"
UNIT = "%"
LAYER = "Serve frontend"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    slots = run.counters.get("glm_dsa_slots")
    act = [b.extra["active"] for b in run.blocks if "active" in b.extra]
    if not slots or not act:
        return None
    return sum(act) / len(act) / slots * 100.0
