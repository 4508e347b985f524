"""Per-layer metric ``device_idle.batch``: 100 x (1 - union of device-op intervals / traced window), the mean over the cell's chips."""
from harness import readers

NAME = "device_idle.batch"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "tokens_per_s"
READS = ("100 x (1 - union of device-op intervals / traced window), the mean over the cell's chips")


def read(ctx):
    return readers.idle_pct(ctx)
