"""Device milliseconds a step spends in the flash forward kernel: the
operations named ``flash_fwd`` in the profiler's trace, over the traced
steps, averaged over the chips. Nothing where the kernels carry no
names of their own."""

from benchmarks.harness import program_spans as ps

NAME = "train_attn_fwd_ms_per_step"
UNIT = "ms"
LAYER = "Kernels"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return ps.kernel_ms_per_step(run, ("flash_fwd",))
