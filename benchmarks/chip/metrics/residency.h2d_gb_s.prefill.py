"""Host->HBM expert bytes per second in the prefill cell's window."""
from benchmarks.chip import readers


def read(ctx):
    return readers.h2d_gb_s(ctx)
