"""Reduction of one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device events are the operations that ran on an accelerator: the ``XLA
Ops`` line of each ``/device:`` plane, and the ``XLA Modules`` line for
the time of each program. A trace recorded on the CPU has no device plane;
there the events that carry an ``hlo_module`` stat stand in for both, so
the reduction can be checked without a chip.

- busy: the union of device-operation intervals inside the window, averaged
  over the devices; idle share = 1 - busy / window;
- programs: device seconds per program name (``jit_grouped_raw(17)`` ->
  ``grouped_raw``), averaged over the devices;
- gaps: the longest idle stretches, each named by the host spans that
  cover its middle: the harness's own ``bench.*`` annotations and, where
  given, the engine's spans mapped onto the trace's clock.

The window is the host annotation ``bench.window`` where the trace has one.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
_PROGRAM_RE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")

Interval = Tuple[int, int]


def program_name(event_name: str) -> str:
    return _PROGRAM_RE.match(event_name).group(1)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    programs: Dict[str, float]
    gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ops(self, n: int = 10) -> List[List]:
        top = sorted(self.programs.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]


def union_ns(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def reduce(path: str, host_spans: Sequence[Tuple[str, int, int]] = (),
           n_gaps: int = 10) -> TraceSummary:
    """Reduce the trace at ``path`` over the window. ``host_spans`` are extra (name, start_ns, end_ns) host spans on the
    trace's clock (the engine's)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}      # device -> op intervals
    mods: Dict[str, List[Tuple[str, int, int]]] = {}
    host: List[Tuple[str, int, int]] = list(host_spans)
    window: Optional[Interval] = None
    cpu_ops: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            mod_line = lines.get("XLA Modules") or op_line
            if op_line is None:
                continue
            ops[plane.name] = [(s, e) for _, s, e, _ in _events(op_line)]
            mods[plane.name] = [(program_name(n), s, e)
                                for n, s, e, _ in _events(mod_line)]
            continue
        for line in plane.lines:
            for name, s, e, ev in _events(line):
                if name == WINDOW_SPAN:
                    window = (s, e)
                if name.startswith("bench."):
                    host.append((name, s, e))
                    continue
                mod = _stat(ev, "hlo_module") if plane.name.startswith(
                    "/host:") else None
                if mod is not None:
                    cpu_ops.append((program_name(str(mod)), s, e))
    if not ops and cpu_ops:                 # a CPU trace: no device plane
        ops["/host:CPU"] = [(s, e) for _, s, e in cpu_ops]
        mods["/host:CPU"] = cpu_ops
    if not ops:
        raise ValueError(f"{path}: no device operation in the trace")
    if window is None:
        every = [iv for ivs in ops.values() for iv in ivs]
        window = (min(s for s, _ in every), max(e for _, e in every))
    lo, hi = window
    n_dev = len(ops)
    busy_ns = 0
    busy_all: List[Interval] = []
    for ivs in ops.values():
        u = union_ns(_clip(ivs, lo, hi))
        busy_ns += sum(e - s for s, e in u)
        busy_all.extend(u)
    programs: Dict[str, float] = {}
    for evs in mods.values():
        for name, s, e in evs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                programs[name] = programs.get(name, 0.0) + (e - s) / 1e9
    programs = {k: v / n_dev for k, v in programs.items()}
    gaps = _gaps(union_ns(busy_all), lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_label(host, (s + e) // 2), (e - s) / 1e9)
             for s, e in gaps[:n_gaps]]
    return TraceSummary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / n_dev / 1e9,
                        n_devices=n_dev, programs=programs, gaps=named)


def _gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(host: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The host spans covering ``t``, outermost first."""
    cover = sorted((s, -e, n) for n, s, e in host
                   if s <= t < e and n != WINDOW_SPAN)
    return "/".join(n for _, _, n in cover) or "host: outside any span"
