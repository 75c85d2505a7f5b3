"""Share of the prefill cell's traced window in which no operation ran on
the device."""
from benchmarks.chip import readers


def read(ctx):
    return readers.idle_pct(ctx)
