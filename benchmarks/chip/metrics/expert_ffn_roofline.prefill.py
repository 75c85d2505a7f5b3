"""Roofline share of the prefill expert FFN: the least time for the work the
algorithm needs (k rows per prompt token and layer, each distinct expert
of a chunk read once) over the device time of the prefill FFN programs."""
from benchmarks.chip import readers

# the per-expert prefill FFN program (serving/engine.py EngineCore._jit_fns)
PROGRAMS = ("expert_apply",)


def read(ctx):
    return readers.roofline_pct(ctx, PROGRAMS, *readers.prefill_ffn_work(ctx))
