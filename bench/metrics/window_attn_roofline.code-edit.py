"""Per-layer metric ``window_attn_roofline.code-edit``: 100 x the least time to read the K and V bytes of the window's decode tokens (window_bytes of the configuration's reference module at each token's position) at the chip's HBM bandwidth peak / the device time of jit_step's ops under attn.local in the traced window."""
from harness import window

NAME = "window_attn_roofline.code-edit"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "attention (models/layers)"
MOVES = "tokens_per_s"
READS = ("100 x the least time to read the K and V bytes of the window's decode tokens (window_bytes of the configuration's reference module at each token's position) at the chip's HBM bandwidth peak / the device time of jit_step's ops under attn.local in the traced window")


def read(ctx):
    return window.roofline_pct(ctx)
