"""Share of the prefill cell's traced window in which the device is idle
while the host's innermost span is an expert copy (``expert.fetch``) or a
barrier on one (``expert.wait``)."""
from benchmarks.chip import idle

SPANS = ("expert.fetch", "expert.wait")


def read(ctx):
    got = idle.split_of(ctx)
    return None if got is None else got.share_pct(SPANS)
