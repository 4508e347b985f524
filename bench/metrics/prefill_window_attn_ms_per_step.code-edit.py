"""Per-layer metric ``prefill_window_attn_ms_per_step.code-edit``: device time of the prefill programs' (jit_prefill_bucket) ops whose innermost named scope is attn.local (banded windowed attention and the ring fill), per decode step in the traced window, per chip."""
from harness import window

NAME = "prefill_window_attn_ms_per_step.code-edit"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "attention (models/layers)"
MOVES = "tokens_per_s"
READS = ("device time of the prefill programs' (jit_prefill_bucket) ops whose innermost named scope is attn.local (banded windowed attention and the ring fill), per decode step in the traced window, per chip")


def read(ctx):
    return window.attn_ms_per_step(ctx, "jit_prefill_bucket", "local")
