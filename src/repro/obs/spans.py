"""Request-lifecycle and engine-phase span recorder.

One :class:`SpanRecorder` per engine (``engine.obs``). Spans are opened
and closed ONLY at the lifecycle points declared in
``repro.analysis.rules.SPAN_SCOPES`` — anywhere else is a lint finding —
so the taxonomy stays small enough to read as a timeline:

  lifecycle lane : request.queued → request.admitted → request.paused /
                   request.restored → terminal (length / stop_token /
                   cancelled / slo_shed / rejected), plus
                   handoff.snapshot / handoff.restore flow endpoints and
                   autopilot.shed / autopilot.preempt annotations
  prefill lane   : prefill (monolithic) / prefill.chunk spans, and under
                   them attention / moe.route / moe.combine / sample; a
                   final kv.scatter instant
  decode lane    : decode.step spans, and under them attention /
                   moe.route / moe.combine / sample
  prefetch lane  : expert.fetch / expert.wait spans (one expert's copy
                   into its pool slot, and the barrier on it),
                   prefetch.correction spans, prefetch.dispatch instants

Every interval name the engine opens is in ``SPAN_NAMES``; the lint
checks ``begin`` sites against it and trace readers select by it.

Design constraints, in order:

  * **Off by default, cheap when off.** Every public method starts with
    an ``enabled`` check; disabled cost is one attribute load + branch.
  * **On the profiler's clock.** When enabled, ``begin``/``end`` also
    enter/exit a ``jax.profiler.TraceAnnotation`` of the span's name, so
    a ``jax.profiler`` trace holds every interval span beside the device
    events (instants are not mirrored). Outside a profiler session an
    annotation records nothing.
  * **Bounded.** Closed spans live in a ``deque(maxlen=capacity)`` ring;
    open spans live in a separate dict keyed by the token ``begin``
    returned, so ring eviction structurally cannot orphan an open span.
  * **Sampled.** Per-request spans are kept for a deterministic hash
    subset of rids (``sample=``); engine-phase spans (``rid=None``) are
    always kept when enabled, they are O(1) per step.
  * **One clock.** ``monotonic()`` is the single time source for spans
    AND for ``RequestSnapshot.t_snapshot`` / ``RequestHandle.handoffs``
    ``t_restore`` — handoff latency can never go negative under
    wall-clock adjustment because nothing here reads wall clock.
  * **Terminal integrity.** ``terminal(rid, reason)`` raises on a second
    terminal for the same rid; tests drive every finish path through
    this check. (A restored request has a NEW rid — handoff chains are
    linked by flow ids, not by rid reuse.)
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

# The one monotonic clock shared by spans, snapshot/restore stamps, and
# handoff records. perf_counter is monotonic and unaffected by NTP slew.
monotonic = time.perf_counter

# Lane taxonomy: maps to one Perfetto track-thread per replica-process.
SPAN_LANES = ("lifecycle", "prefill", "decode", "prefetch")

# Every interval span the engine opens. Parents first, then the leaves
# that say what the host is doing inside a chunk or step.
SPAN_NAMES = ("prefill", "prefill.chunk", "decode.step",
              "prefetch.correction", "attention", "moe.route",
              "moe.combine", "sample", "expert.fetch", "expert.wait")

# Knuth multiplicative hash for deterministic rid sampling.
_HASH_K = 2654435761
_HASH_M = float(1 << 32)


@dataclass
class Span:
    """One recorded interval (or instant, when ``t1 == t0``)."""
    name: str
    lane: str
    t0: float
    t1: float
    rid: Optional[int] = None
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class SpanRecorder:
    """Ring-buffer-bounded span sink for one engine/replica."""

    def __init__(self, enabled: bool = False, capacity: int = 8192,
                 sample: float = 1.0, replica: int = 0):
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self.sample = float(sample)
        self.replica = int(replica)
        self.closed: Deque[Span] = collections.deque(maxlen=self.capacity)
        self._open: Dict[int, Span] = {}
        self._annotations: Dict[int, object] = {}  # token -> open annotation
        self._next_token = 0
        # rid -> terminal reason; bounded FIFO so a long-lived server
        # doesn't accumulate one entry per request forever.
        self._terminal: Dict[int, str] = {}
        self._terminal_order: Deque[int] = collections.deque()
        self._terminal_window = max(4 * self.capacity, 65536)
        self.n_dropped = 0  # closed spans evicted by the ring

    # -- sampling ----------------------------------------------------------
    def sampled(self, rid: Optional[int]) -> bool:
        """Deterministic: the same rid is kept or dropped consistently, so
        a kept request's lifecycle is complete rather than gap-toothed."""
        if rid is None or self.sample >= 1.0:
            return True
        if self.sample <= 0.0:
            return False
        return ((abs(int(rid)) * _HASH_K) & 0xFFFFFFFF) / _HASH_M < self.sample

    # -- span API ----------------------------------------------------------
    def begin(self, name: str, lane: str = "lifecycle",
              rid: Optional[int] = None, **args) -> Optional[int]:
        """Open a span; returns an opaque token for ``end`` (None when
        disabled or sampled out — ``end(None)`` is a no-op)."""
        if not self.enabled or not self.sampled(rid):
            return None
        tok = self._next_token
        self._next_token += 1
        # imported here, so that importing repro.obs does not import jax
        from jax.profiler import TraceAnnotation
        ann = TraceAnnotation(name)
        ann.__enter__()
        self._annotations[tok] = ann
        self._open[tok] = Span(name, lane, monotonic(), 0.0, rid, args)
        return tok

    def end(self, token: Optional[int], **args) -> None:
        if token is None:
            return
        span = self._open.pop(token, None)
        if span is None:
            raise ValueError(f"span token {token} ended twice or never opened")
        span.t1 = monotonic()
        self._annotations.pop(token).__exit__(None, None, None)
        if args:
            span.args.update(args)
        if len(self.closed) == self.capacity:
            self.n_dropped += 1
        self.closed.append(span)

    def instant(self, name: str, lane: str = "lifecycle",
                rid: Optional[int] = None, **args) -> None:
        if not self.enabled or not self.sampled(rid):
            return
        t = monotonic()
        if len(self.closed) == self.capacity:
            self.n_dropped += 1
        self.closed.append(Span(name, lane, t, t, rid, args))

    def terminal(self, rid: int, reason: str, **args) -> None:
        """Record the request's ONE terminal transition. A second terminal
        for the same rid is a lifecycle bug and raises immediately."""
        if not self.enabled:
            return
        prev = self._terminal.get(rid)
        if prev is not None:
            raise RuntimeError(
                f"rid {rid} reached a second terminal {reason!r} "
                f"(already {prev!r})")
        self._terminal[rid] = reason
        self._terminal_order.append(rid)
        while len(self._terminal_order) > self._terminal_window:
            self._terminal.pop(self._terminal_order.popleft(), None)
        self.instant(f"request.{reason}", lane="lifecycle", rid=rid,
                     reason=reason, **args)

    # -- views -------------------------------------------------------------
    def spans(self) -> List[Span]:
        """Closed spans, oldest first."""
        return list(self.closed)

    def open_spans(self) -> List[Span]:
        return list(self._open.values())

    def terminal_reasons(self) -> Dict[int, str]:
        return dict(self._terminal)

    def clear(self) -> None:
        self.closed.clear()
        for ann in self._annotations.values():
            ann.__exit__(None, None, None)
        self._annotations.clear()
        self._open.clear()
        self._terminal.clear()
        self._terminal_order.clear()
        self.n_dropped = 0
