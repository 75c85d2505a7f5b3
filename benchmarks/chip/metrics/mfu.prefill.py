"""Whole-step model FLOP utilization of the prefill cell: the FLOPs of the
prompt tokens prefilled in the window (active parameters, attention over
the mean prefix) over the window times the chip's bf16 peak."""
from benchmarks.chip import flops, readers


def read(ctx):
    per_token = flops.token_flops(ctx.dims, ctx.prefill_context, head=False)
    return readers.mfu_pct(ctx, readers.delta(ctx, "prefilled") * per_token)
