"""Per-layer metric ``telemetry_ms_per_step.batch``: device time of the token-step program's (jit_step) ops whose innermost named scope is ax_telemetry.<target> (the controller's operand and tile summaries with their observe gate), per decode step in the traced window, per chip."""
from harness import spans

NAME = "telemetry_ms_per_step.batch"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "runtime/telemetry"
MOVES = "tokens_per_s"
READS = ("device time of the token-step program's (jit_step) ops whose innermost named scope is ax_telemetry.<target> (the controller's operand and tile summaries with their observe gate), per decode step in the traced window, per chip")


def read(ctx):
    return spans.scope_ms_per_step(ctx, "ax_telemetry")
