"""Device milliseconds a step spends in the flash backward kernels: the
operations named ``flash_dq`` and ``flash_dkv`` in the profiler's
trace, over the traced steps, averaged over the chips. With
``train_attn_fwd_ms_per_step`` it adds up to the Pallas kernels' device
time a step."""

from benchmarks.harness import program_spans as ps

NAME = "train_attn_bwd_ms_per_step"
UNIT = "ms"
LAYER = "Kernels"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return ps.kernel_ms_per_step(run, ("flash_dq", "flash_dkv"))
