"""The engine's own time per output token: the median, over requests
that finished in the window, of decode seconds over tokens after the
first, as each answer's ``decode_tokens_per_s`` carries it (the engine's
clock, first token to finish). With every lane decoding, tokens per
second is the lanes over this. The client-side reading, which adds the
wait to pick the answer up, is in the run's ``# window`` line."""

NAME = "serve_tpot_engine_p50_ms"
UNIT = "ms"
LAYER = "Serve engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    return run.window.get("tpot_engine_p50_ms")
