"""Per-layer metric ``runtime_host_ms_per_step.batch``: host time inside the union of the controller_observe, qor_observe, policy_poll, policy_tree, slo_observe, audit_append and retune spans in the traced window, per decode step in the window."""
from harness import spans

NAME = "runtime_host_ms_per_step.batch"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "runtime (controller, QoR, SLO, audit)"
MOVES = "tokens_per_s"
READS = ("host time inside the union of the controller_observe, qor_observe, policy_poll, policy_tree, slo_observe, audit_append and retune spans in the traced window, per decode step in the window")


def read(ctx):
    return spans.runtime_host_ms_per_step(ctx)
