"""Per-layer metric ``window_attn_ms_per_step.code-edit``: device time of the token-step program's (jit_step) ops whose innermost named scope is attn.local (the sliding-window attention proper: ring write, scores, softmax, values), per decode step in the traced window, per chip."""
from harness import window

NAME = "window_attn_ms_per_step.code-edit"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "attention (models/layers)"
MOVES = "tokens_per_s"
READS = ("device time of the token-step program's (jit_step) ops whose innermost named scope is attn.local (the sliding-window attention proper: ring write, scores, softmax, values), per decode step in the traced window, per chip")


def read(ctx):
    return window.attn_ms_per_step(ctx, "jit_step", "local")
