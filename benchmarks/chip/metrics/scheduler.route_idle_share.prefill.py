"""Share of the prefill cell's traced window in which the device is idle
while the host's innermost span is routing (``moe.route``: the gate ids
read back and the scheduler's plan built)."""
from benchmarks.chip import idle

SPANS = ("moe.route",)


def read(ctx):
    got = idle.split_of(ctx)
    return None if got is None else got.share_pct(SPANS)
