"""Host milliseconds a step spends in the loader: the program's
``data.next_batch`` span (gather the next batch's rows and dispatch its
transfer, inside ``ShardedLoader.epoch``), total over the untraced
blocks' steps."""

from benchmarks.harness import program_spans as ps

NAME = "train_loader_ms_per_step"
UNIT = "ms"
LAYER = "Trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    return ps.both(
        NAME, lambda traced: ps.per_step_ms(run, "data.next_batch", traced)
    )
