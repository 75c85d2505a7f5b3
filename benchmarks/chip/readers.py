"""Arithmetic shared by the per-layer metric readers (``metrics/*.py``).

Each reader gets one ``ctx`` (see ``harness.MetricContext``): the window's
program counters at its opening and close, what the client received, the
configuration's sizes, the chip's peaks and, in a traced run, the trace's
reduction. A reader returns None where its run has nothing for it to read;
the harness then leaves the metric out of the line.
"""
from __future__ import annotations

from typing import Iterable, Optional

from benchmarks.chip import flops


def delta(ctx, key: str) -> float:
    return ctx.close[key] - ctx.open[key]


def hit_rate_pct(ctx) -> Optional[float]:
    hits, misses = delta(ctx, "hits"), delta(ctx, "misses")
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)


def h2d_bytes(ctx) -> float:
    """Host->HBM bytes in the window: one transfer-log entry is one whole
    expert (its three slabs)."""
    return ctx.transfers * ctx.bytes_per_expert


def h2d_gb_s(ctx) -> Optional[float]:
    return h2d_bytes(ctx) / ctx.window_s / 1e9 if ctx.transfers else None


def h2d_mb_per(ctx, tokens: float) -> Optional[float]:
    return h2d_bytes(ctx) / tokens / 1e6 if tokens and ctx.transfers \
        else None


def program_seconds(ctx, names: Iterable[str]) -> float:
    return sum(ctx.trace.programs.get(n, 0.0) for n in names)


def roofline_pct(ctx, names, flop: float, nbytes: float) -> Optional[float]:
    """Least time the chip could take for the work (the larger of FLOPs
    over peak FLOP/s and bytes over peak bandwidth) over the device time
    of the programs ``names``."""
    if ctx.trace is None:
        return None
    t = program_seconds(ctx, names)
    if t <= 0 or (flop <= 0 and nbytes <= 0):
        return None
    t_min = max(flop / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / t


def prefill_ffn_work(ctx):
    """(FLOPs, bytes) the prefill expert FFN needs in the window: every
    prompt token's top-k rows, each distinct expert of each layer and
    chunk read once (one launch per distinct expert)."""
    d = ctx.dims
    rows = delta(ctx, "prefilled") * d.n_layers * d.top_k
    return (rows * flops.expert_flops_per_row(d),
            delta(ctx, "prefill_ffn_launches") * flops.expert_bytes(d))


def mfu_pct(ctx, model_flops: float) -> Optional[float]:
    if model_flops <= 0:
        return None
    return 100.0 * model_flops / (ctx.window_s
                                  * ctx.peaks["bf16_flops_per_s"])


def idle_pct(ctx) -> Optional[float]:
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
