"""The device's idle time in a traced window, put down to what the host was
doing: for every idle stretch, the innermost host span that covers it.

The spans are read from the trace itself: the harness's ``bench.*``
annotations and the engine's spans, which the program's span recorder
writes into the profiler's trace under the names in
``repro.obs.spans.SPAN_NAMES`` (a program without them leaves nothing to
read, and ``split_of`` returns None). Device operations are found as
``trace.reduce`` finds them: the ``XLA Ops`` line of each ``/device:``
plane or, in a trace recorded on the CPU, the events with an
``hlo_module`` stat. Idle time is averaged over the devices, as busy time
is there.

The per-layer readers call ``split_of(ctx)``. The harness hands them the
trace's reduction but not the trace, so the file is found in the
harness's temporary trace directory: the newest whose window and busy
time are the reduction's.
"""
from __future__ import annotations

import dataclasses
import tempfile
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import trace as TR

OUTSIDE = "host: outside any span"
TRACE_DIRS = "chipbench-trace-*"

Span = Tuple[str, int, int]


@dataclasses.dataclass
class IdleSplit:
    window_s: float
    busy_s: float
    by_span: Dict[str, float]     # innermost covering span -> idle seconds
    n_engine_spans: int           # engine spans inside the window

    @property
    def idle_s(self) -> float:
        return self.window_s - self.busy_s

    def share_pct(self, names: Sequence[str]) -> float:
        """Percent of the window in which the device is idle while the
        host's innermost span is one of ``names``."""
        return 100.0 * sum(self.by_span.get(n, 0.0)
                           for n in names) / self.window_s


def split(path: str, names: Sequence[str]) -> IdleSplit:
    """Idle seconds of the trace at ``path`` by innermost host span, over
    the ``bench.window`` annotation (else the span of device operations);
    ``names`` are the engine's span names."""
    import jax

    engine = set(names)
    ops: Dict[str, List[TR.Interval]] = {}
    cpu_ops: List[TR.Interval] = []
    host: List[Span] = []
    window: Optional[TR.Interval] = None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                ops[plane.name] = [(s, e) for _, s, e, _ in TR._events(line)]
            continue
        for line in plane.lines:
            for name, s, e, ev in TR._events(line):
                if name == TR.WINDOW_SPAN:
                    window = (s, e)
                elif name.startswith("bench.") or name in engine:
                    host.append((name, s, e))
                elif (plane.name.startswith("/host:")
                      and TR._stat(ev, "hlo_module") is not None):
                    cpu_ops.append((s, e))
    if not ops and cpu_ops:                 # a CPU trace: no device plane
        ops["/host:CPU"] = cpu_ops
    if not ops:
        raise ValueError(f"{path}: no device operation in the trace")
    if window is None:
        every = [iv for ivs in ops.values() for iv in ivs]
        window = (min(s for s, _ in every), max(e for _, e in every))
    lo, hi = window
    host = [(n, max(s, lo), min(e, hi)) for n, s, e in host
            if e > lo and s < hi]
    by_span: Dict[str, float] = {}
    busy_ns = 0
    for ivs in ops.values():
        busy = TR.union_ns(TR._clip(ivs, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, ns in _attribute(TR._gaps(busy, lo, hi), host).items():
            by_span[name] = by_span.get(name, 0.0) + ns / 1e9
    n_dev = len(ops)
    return IdleSplit(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / n_dev / 1e9,
        by_span={k: v / n_dev for k, v in by_span.items()},
        n_engine_spans=sum(1 for n, _, _ in host if n in engine))


def _attribute(gaps: Sequence[TR.Interval], host: Sequence[Span]
               ) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` under each innermost covering span: of the
    spans open at an instant, the one that began last (of two that began
    together, the one that ends first)."""
    # sweep over every span edge and gap edge in time order; at equal
    # times, ends before starts
    edges = []
    for i, (_, s, e) in enumerate(host):
        edges.append((s, 1, i))
        edges.append((e, 0, i))
    for s, e in gaps:
        edges.append((s, 1, -1))
        edges.append((e, 0, -1))
    edges.sort()
    out: Dict[str, int] = {}
    open_: Dict[int, Tuple[int, int]] = {}   # span -> (start, -end)
    idle = 0
    t = None
    for when, is_start, i in edges:
        if t is not None and idle and when > t:
            if open_:
                inner = max(open_, key=open_.__getitem__)
                name = host[inner][0]
            else:
                name = OUTSIDE
            out[name] = out.get(name, 0) + when - t
        t = when
        if i < 0:
            idle += 1 if is_start else -1
        elif is_start:
            open_[i] = (host[i][1], -host[i][2])
        else:
            open_.pop(i, None)
    return out


def split_of(ctx) -> Optional[IdleSplit]:
    """The idle split of a traced run's window, computed once for all the
    readers of one run; None without a trace, without the program's span
    names, or where the trace holds no engine span."""
    global _last
    if ctx.trace is None:
        return None
    if _last[0] is ctx.trace:
        return _last[1]
    try:
        from repro.obs.spans import SPAN_NAMES
    except ImportError:                     # a program without the names
        return None
    got = _matching_split(ctx.trace, SPAN_NAMES)
    if got is not None and got.n_engine_spans == 0:
        got = None
    _last = (ctx.trace, got)
    return got


_last: Tuple[object, Optional[IdleSplit]] = (None, None)


def _matching_split(summary, names) -> Optional[IdleSplit]:
    """The split of the harness's trace file whose window and busy time are
    those of ``summary`` (the harness's ``trace.reduce`` of it): the newest
    such file under the temporary directory; None where none matches."""
    found = []
    for p in Path(tempfile.gettempdir()).glob(f"{TRACE_DIRS}/**/*.xplane.pb"):
        try:
            found.append((p.stat().st_mtime, p))
        except OSError:                     # another run removed it
            continue
    for _, p in sorted(found, reverse=True):
        try:
            got = split(str(p), names)
        except (OSError, RuntimeError, ValueError):   # partial or removed
            continue
        if (abs(got.window_s - summary.window_s) < 1e-9
                and abs(got.busy_s - summary.busy_s) < 1e-9):
            return got
    return None
