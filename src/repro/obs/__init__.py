"""repro.obs — the observability spine of the serving stack.

Three pieces, one discipline (enforced by the ``obs-discipline`` lint in
``repro.analysis``):

  * ``metrics``      — the typed metric registry (Counter / Gauge /
    Histogram with labels). Every number the stack tracks lives here;
    legacy attributes (``PerfCounters`` fields, ``prefilled_tokens``,
    ``ReplicaPool.handoff_bytes``, ...) are thin read-only views over it.
  * ``spans``        — low-overhead request-lifecycle + engine-phase span
    recorder (ring-buffer bounded, off by default, sampled when on, and
    mirrored into ``jax.profiler`` traces when on) and the ONE monotonic
    clock every serving timestamp shares.
  * ``chrome_trace`` — export recorded spans as a Perfetto /
    chrome://tracing JSON: one track (pid) per replica, one lane (tid)
    per phase, flow arrows linking disagg handoff hops across tracks.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               validate_metrics_snapshot)
from repro.obs.spans import (SPAN_LANES, SPAN_NAMES, Span, SpanRecorder,
                             monotonic)
from repro.obs.chrome_trace import (to_chrome_trace, validate_trace,
                                    write_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "validate_metrics_snapshot",
    "SPAN_LANES", "SPAN_NAMES", "Span", "SpanRecorder", "monotonic",
    "to_chrome_trace", "validate_trace", "write_trace",
]
