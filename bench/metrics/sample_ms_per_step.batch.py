"""Per-layer metric ``sample_ms_per_step.batch``: device time of the token-step program's (jit_step) ops whose innermost named scope is sample (the sampler over the vocabulary's logits), per decode step in the traced window, per chip."""
from harness import spans

NAME = "sample_ms_per_step.batch"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "serve/engine sampling"
MOVES = "tokens_per_s"
READS = ("device time of the token-step program's (jit_step) ops whose innermost named scope is sample (the sampler over the vocabulary's logits), per decode step in the traced window, per chip")


def read(ctx):
    return spans.scope_ms_per_step(ctx, "sample")
