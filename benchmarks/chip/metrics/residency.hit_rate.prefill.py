"""Share of the prefill cell's expert lookups in the window that found the
expert resident (residency hits over hits and misses)."""
from benchmarks.chip import readers


def read(ctx):
    return readers.hit_rate_pct(ctx)
