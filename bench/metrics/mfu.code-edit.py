"""Per-layer metric ``mfu.code-edit``: least time at the chip's peaks for the window's work (the work count of the configuration's reference module, bench/references/: int8 ops of the approximated projections at the int8 peak, the rest at the bf16 peak; prompt and output tokens, real lengths, attention over the keys each token sees) / (window x chips); tokens and positions from the token_step and splice spans."""
from harness import readers

NAME = "mfu.code-edit"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "model step (whole step)"
MOVES = "tokens_per_s"
READS = ("least time at the chip's peaks for the window's work (the work count of the configuration's reference module, bench/references/: int8 ops of the approximated projections at the int8 peak, the rest at the bf16 peak; prompt and output tokens, real lengths, attention over the keys each token sees) / (window x chips); tokens and positions from the token_step and splice spans")


def read(ctx):
    return readers.mfu_pct(ctx)
