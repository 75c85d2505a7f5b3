"""Host->HBM expert megabytes per prompt token prefilled in the window."""
from benchmarks.chip import readers


def read(ctx):
    return readers.h2d_mb_per(ctx, readers.delta(ctx, "prefilled"))
