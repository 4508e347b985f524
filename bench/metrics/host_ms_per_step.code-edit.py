"""Per-layer metric ``host_ms_per_step.code-edit``: mean over the decode steps of the traced window of the host time from the end of the previous step's token_read span to the end of the step's token_step dispatch, less the time inside admit spans (prefill waits)."""
from harness import spans

NAME = "host_ms_per_step.code-edit"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "scheduler (fleet/scheduler token loop)"
MOVES = "tokens_per_s"
READS = ("mean over the decode steps of the traced window of the host time from the end of the previous step's token_read span to the end of the step's token_step dispatch, less the time inside admit spans (prefill waits)")


def read(ctx):
    return spans.host_ms_per_step(ctx)
