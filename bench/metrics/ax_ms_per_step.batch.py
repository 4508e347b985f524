"""Per-layer metric ``ax_ms_per_step.batch``: device time of the token-step program's (jit_step) ops whose innermost named scope is ax.<target> (quantize, limbs, int8 dot, dequantize of the approximate projections), per decode step in the traced window, per chip."""
from harness import spans

NAME = "ax_ms_per_step.batch"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "quant/ax"
MOVES = "tokens_per_s"
READS = ("device time of the token-step program's (jit_step) ops whose innermost named scope is ax.<target> (quantize, limbs, int8 dot, dequantize of the approximate projections), per decode step in the traced window, per chip")


def read(ctx):
    return spans.scope_ms_per_step(ctx, "ax")
