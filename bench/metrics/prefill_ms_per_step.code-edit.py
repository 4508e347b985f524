"""Per-layer metric ``prefill_ms_per_step.code-edit``: device time of the single-request prefill programs (jit_prefill_bucket) in the traced window per decode step of the window, per chip."""
from harness import readers

NAME = "prefill_ms_per_step.code-edit"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "engine (serve/engine)"
MOVES = "tokens_per_s"
READS = ("device time of the single-request prefill programs (jit_prefill_bucket) in the traced window per decode step of the window, per chip")
MODULES = ("jit_prefill_bucket",)   # the prefill's XLA module, as the trace names it


def read(ctx):
    return readers.program_ms_per_step(ctx, MODULES)
