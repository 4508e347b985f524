"""Per-layer metric ``decode_step_ms.code-edit``: device time of the token-step program (jit_step) per decode step in the traced window, per chip."""
from harness import readers

NAME = "decode_step_ms.code-edit"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "engine (serve/engine)"
MOVES = "tokens_per_s"
READS = ("device time of the token-step program (jit_step) per decode step in the traced window, per chip")
MODULES = ("jit_step",)     # the token step's XLA module, as the trace names it


def read(ctx):
    return readers.program_ms_per_step(ctx, MODULES)
