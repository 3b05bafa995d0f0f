"""Host milliseconds a step spends dispatching: the program's
``train.dispatch`` span (the call of the jitted step, where
``Trainer.train_step`` is set), total over the untraced blocks'
steps."""

from benchmarks.harness import program_spans as ps

NAME = "train_dispatch_ms_per_step"
UNIT = "ms"
LAYER = "Trainer loop"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    return ps.both(
        NAME, lambda traced: ps.per_step_ms(run, "train.dispatch", traced)
    )
