"""repro.obs: metrics registry, span recorder, and Perfetto export
(ISSUE 10 tentpole).

  * Registry — get-or-create identity, kind conflicts, label series,
    push/pull gauges, P² histogram summaries, a GOLDEN Prometheus
    exposition, and snapshot schema validation (bool/None/inf rejected).
  * Spans — nesting, double-end detection, disabled/sampled-out no-ops,
    deterministic rid sampling, ring eviction that can never orphan an
    open span, and the one-terminal-per-rid invariant.
  * Lifecycle integration — every finish path a request can take
    (length, cancel, admission reject, slo_shed, preempt+resume,
    cancel-while-paused, disagg handoff) records EXACTLY ONE terminal
    span on the rid chain that served it.
  * Perfetto export — schema-valid JSON, one process per replica with
    lifecycle/prefill/decode/prefetch lanes, handoff flow s/f pairing
    across replica tracks, and unpaired flows rejected.
  * Clocks — `RequestHandle.handoffs` t_snapshot/t_restore come from the
    one monotonic clock, so hop latency is non-negative by construction.
"""
import json
import math
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.models.model import build
from repro.obs import (MetricsRegistry, SpanRecorder, monotonic,
                       to_chrome_trace, validate_metrics_snapshot,
                       validate_trace, write_trace)
from repro.obs.metrics import METRICS_SCHEMA
from repro.serving.api import GenerationRequest, SamplingParams
from repro.serving.batching import BatchedServingEngine
from repro.serving.cluster import ClusterFrontend, QosAutopilot, ReplicaPool
from repro.serving.frontend import ServingFrontend

MAX_NEW = 4


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral_8x7b"))
    params = build(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (12, 16, 9, 14)]
    return cfg, params, prompts


def _fe(cfg, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_budget", 3)
    kw.setdefault("spans", True)
    return ServingFrontend(BatchedServingEngine(
        cfg, params, policy="duo", max_seq=32, temperature=0.0, **kw))


def _spec(p, max_new=MAX_NEW, **kw):
    return GenerationRequest(prompt=p,
                             params=SamplingParams(max_new_tokens=max_new),
                             **kw)


def _poll_until(fe, pred, limit=500):
    for _ in range(limit):
        if pred():
            return
        fe.poll()
    raise AssertionError("condition not reached")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_monotonic():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create: same (name, labels) is the same object
    assert reg.counter("reqs_total") is c
    assert reg.counter("reqs_total", replica="1") is not c


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_gauge_push_and_pull():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(2)
    g.max_update(7)
    g.max_update(3)
    assert g.value == 7.0
    src = {"v": 0}
    p = reg.gauge("pulled", fn=lambda: src["v"])
    src["v"] = 42
    assert p.value == 42.0          # evaluated at read time
    with pytest.raises(ValueError, match="pull-mode"):
        p.set(1)


def test_gauge_late_fn_binding():
    """gauge() without fn first (e.g. a reader), then with fn: the callback
    binds onto the existing instrument instead of being dropped."""
    reg = MetricsRegistry()
    g1 = reg.gauge("late")
    g2 = reg.gauge("late", fn=lambda: 5)
    assert g1 is g2 and g1.value == 5.0


def test_histogram_summary():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", qs=(50,))
    assert h.summary() == {"count": 0.0, "sum": 0.0}   # no min/max/pXX yet
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 0.1 and s["max"] == 0.3
    assert s["p50"] == pytest.approx(0.2)


def test_snapshot_label_keys():
    reg = MetricsRegistry()
    reg.counter("shed_total", reason="ttft").inc(2)
    reg.counter("shed_total", reason="tbt").inc(1)
    snap = reg.snapshot()
    assert snap['shed_total{reason="tbt"}'] == 1.0
    assert snap['shed_total{reason="ttft"}'] == 2.0
    assert len(reg.series("shed_total")) == 2


def test_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("requests_total", "Requests offered", replica="0").inc(3)
    reg.gauge("queue_depth", "Waiting requests").set(2)
    h = reg.histogram("step_seconds", "Decode step wall", qs=(50,))
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    assert reg.exposition() == (
        "# HELP queue_depth Waiting requests\n"
        "# TYPE queue_depth gauge\n"
        "queue_depth 2\n"
        "# HELP requests_total Requests offered\n"
        "# TYPE requests_total counter\n"
        'requests_total{replica="0"} 3\n'
        "# HELP step_seconds Decode step wall\n"
        "# TYPE step_seconds summary\n"
        'step_seconds{quantile="0.5"} 0.2\n'
        f"step_seconds_sum {repr(0.1 + 0.2 + 0.3)}\n"
        "step_seconds_count 3\n")


def test_validate_metrics_snapshot():
    good = {"schema": METRICS_SCHEMA,
            "cluster": {"handoffs": 3},
            "replicas": [{"a{r=\"0\"}": 1.5, "note": "str ok",
                          "h": {"p50": float("nan")}}]}
    assert validate_metrics_snapshot(good) == []
    assert validate_metrics_snapshot({"schema": "wrong"})
    assert validate_metrics_snapshot({"schema": METRICS_SCHEMA, "x": True})
    assert validate_metrics_snapshot({"schema": METRICS_SCHEMA, "x": None})
    assert validate_metrics_snapshot(
        {"schema": METRICS_SCHEMA, "x": float("inf")})
    assert validate_metrics_snapshot([1, 2])


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------


def test_span_nesting_and_order():
    rec = SpanRecorder(enabled=True)
    outer = rec.begin("decode.step", lane="decode")
    inner = rec.begin("prefetch.correction", lane="prefetch", layer=0)
    rec.end(inner)
    rec.end(outer, batch=2)
    spans = rec.spans()
    assert [s.name for s in spans] == ["prefetch.correction", "decode.step"]
    inner_s, outer_s = spans
    # the inner interval nests inside the outer one
    assert outer_s.t0 <= inner_s.t0 <= inner_s.t1 <= outer_s.t1
    assert outer_s.args["batch"] == 2 and not rec.open_spans()


def test_span_double_end_raises():
    rec = SpanRecorder(enabled=True)
    tok = rec.begin("x")
    rec.end(tok)
    with pytest.raises(ValueError, match="twice"):
        rec.end(tok)


def test_span_disabled_is_noop():
    rec = SpanRecorder(enabled=False)
    assert rec.begin("x") is None
    rec.end(None)                       # no-op by contract
    rec.instant("y")
    rec.terminal(1, "length")
    assert rec.spans() == [] and rec.terminal_reasons() == {}


def test_sampling_deterministic_and_engine_spans_kept():
    rec = SpanRecorder(enabled=True, sample=0.5)
    kept = {rid for rid in range(200) if rec.sampled(rid)}
    assert 0 < len(kept) < 200              # a strict subset survives
    assert kept == {rid for rid in range(200) if rec.sampled(rid)}
    assert rec.sampled(None)                # engine-phase spans always kept
    for rid in range(200):
        rec.instant("request.queued", rid=rid)
    assert {s.rid for s in rec.spans()} == kept


def test_ring_eviction_never_orphans_open_spans():
    rec = SpanRecorder(enabled=True, capacity=4)
    tok = rec.begin("decode.step", lane="decode")
    for i in range(10):
        rec.instant("prefetch.dispatch", lane="prefetch", layer=i)
    assert len(rec.spans()) == 4 and rec.n_dropped == 6
    assert [s.args["layer"] for s in rec.spans()] == [6, 7, 8, 9]
    # the open span survived the churn and still closes cleanly
    assert len(rec.open_spans()) == 1
    rec.end(tok)
    assert rec.spans()[-1].name == "decode.step" and not rec.open_spans()


def _trace_events(logdir):
    """name -> [(start_ns, end_ns)] of every event in the one
    ``.xplane.pb`` under ``logdir``."""
    from pathlib import Path
    path = next(Path(logdir).rglob("*.xplane.pb"))
    out = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_enabled_spans_reach_a_profiler_trace(tmp_path):
    """An enabled recorder's intervals appear in a ``jax.profiler`` trace
    under their own names, also when they end out of order; instants and a
    disabled recorder write nothing there."""
    on, off = SpanRecorder(enabled=True), SpanRecorder(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    outer = on.begin("decode.step", lane="decode")
    inner = on.begin("expert.fetch", lane="prefetch", layer=0)
    hidden = off.begin("moe.route", lane="decode")
    on.instant("prefetch.dispatch", lane="prefetch")
    time.sleep(0.002)
    on.end(outer)                       # before the span it encloses
    time.sleep(0.002)
    on.end(inner)
    off.end(hidden)
    left_open = on.begin("sample", lane="decode")
    on.clear()                          # closes the open annotation
    jax.profiler.stop_trace()
    ev = _trace_events(tmp_path)
    assert "moe.route" not in ev and "prefetch.dispatch" not in ev
    (s0, e0), = ev["decode.step"]
    (s1, e1), = ev["expert.fetch"]
    assert s0 <= s1 < e0 < e1
    assert e1 - e0 >= 1_500_000                  # the second sleep
    assert len(ev["sample"]) == 1 and left_open is not None


def test_terminal_twice_raises():
    rec = SpanRecorder(enabled=True)
    rec.terminal(7, "length")
    assert rec.terminal_reasons() == {7: "length"}
    with pytest.raises(RuntimeError, match="second terminal"):
        rec.terminal(7, "cancelled")


# ---------------------------------------------------------------------------
# request lifecycle: exactly one terminal per rid chain, every finish path
# ---------------------------------------------------------------------------


def _terminals(*engines):
    out = {}
    for e in engines:
        for rid, reason in e.obs.terminal_reasons().items():
            assert rid not in out, f"rid {rid} terminal on two engines"
            out[rid] = reason
    return out


def test_terminal_once_finished(setup):
    cfg, params, prompts = setup
    fe = _fe(cfg, params)
    hs = [fe.submit(_spec(p)) for p in prompts[:3]]
    fe.drain()
    terms = _terminals(fe.engine)
    assert sorted(terms) == sorted(h.rid for h in hs)
    assert set(terms.values()) == {"length"}
    # queued/admitted instants present for each rid; no span left open
    names = {(s.rid, s.name) for s in fe.engine.obs.spans()}
    for h in hs:
        assert (h.rid, "request.queued") in names
        assert (h.rid, "request.admitted") in names
    assert fe.engine.obs.open_spans() == []


def test_terminal_once_cancelled(setup):
    cfg, params, prompts = setup
    fe = _fe(cfg, params)
    h = fe.submit(_spec(prompts[0], max_new=16))
    _poll_until(fe, lambda: len(h.tokens) >= 2)
    h.cancel()
    fe.drain()
    assert _terminals(fe.engine)[h.rid] == "cancelled"


def test_terminal_once_slo_shed(setup):
    cfg, params, prompts = setup
    fe = _fe(cfg, params)
    QosAutopilot(fe)
    h = fe.submit(_spec(prompts[0], max_new=16, tbt_slo=60.0))
    _poll_until(fe, lambda: len(h.tokens) >= 2)
    fe.poll(time.perf_counter() + 100.0)    # deadline long past -> shed
    assert h.finish_reason == "slo_shed"
    assert _terminals(fe.engine)[h.rid] == "slo_shed"
    fe.drain()
    assert _terminals(fe.engine)[h.rid] == "slo_shed"   # still exactly one


def test_terminal_once_admission_rejected(setup):
    cfg, params, prompts = setup
    fe = _fe(cfg, params, max_batch=1)
    busy = fe.submit(_spec(prompts[0], max_new=16))
    _poll_until(fe, lambda: len(busy.tokens) >= 1)
    # an unmeetable TTFT deadline behind a busy slot is rejected at
    # admission — that rejection is that rid's one terminal
    doomed = fe.submit(_spec(prompts[1], ttft_slo=1e-9))
    _poll_until(fe, lambda: doomed.done)
    assert doomed.finish_reason == "rejected"
    assert _terminals(fe.engine)[doomed.rid] == "rejected"
    busy.cancel()
    fe.drain()


def test_terminal_once_preempt_resume(setup):
    """pause+resume re-rids the request; the CHAIN still ends in exactly
    one terminal (on the resumed rid), and the paused/restored instants
    carry the linkage."""
    cfg, params, prompts = setup
    fe = _fe(cfg, params, max_batch=1)
    ap = QosAutopilot(fe, preempt=True)
    lo = fe.submit(_spec(prompts[0], priority=0))
    rid0 = lo.rid
    _poll_until(fe, lambda: len(lo.tokens) >= 2)
    hi = fe.submit(_spec(prompts[2], priority=5))
    fe.poll()
    assert lo.status == "paused" and ap.n_preempted == 1
    fe.drain()
    assert lo.done and hi.done
    terms = _terminals(fe.engine)
    assert rid0 not in terms                # paused is not a terminal
    assert terms[lo.rid] == "length" and terms[hi.rid] == "length"
    spans = fe.engine.obs.spans()
    assert any(s.name == "request.paused" and s.rid == rid0 for s in spans)
    assert any(s.name == "request.restored" and s.rid == lo.rid
               and s.args["source_rid"] == rid0 for s in spans)
    assert any(s.name == "autopilot.preempt" and s.rid == rid0
               for s in spans)


def test_terminal_once_cancel_while_paused(setup):
    """A handle cancelled while paused never touches an engine again; the
    frontend records the chain's one terminal on the owning recorder."""
    cfg, params, prompts = setup
    fe = _fe(cfg, params, max_batch=1)
    QosAutopilot(fe, preempt=True)
    lo = fe.submit(_spec(prompts[0], priority=0))
    _poll_until(fe, lambda: len(lo.tokens) >= 1)
    hi = fe.submit(_spec(prompts[2], priority=5))
    fe.poll()
    assert lo.status == "paused"
    paused_rid = lo.rid
    lo.cancel()
    assert lo.finish_reason == "cancelled"
    assert _terminals(fe.engine)[paused_rid] == "cancelled"
    fe.drain()


def test_terminal_once_disagg_handoff(setup):
    """Across the prefill->decode hop the chain is: source rid (paused at
    the handoff, never terminal) -> destination rid (one terminal)."""
    cfg, params, prompts = setup
    pool = ReplicaPool.build(
        cfg, params, policy="duo", max_batch=2, max_seq=32,
        prefill_budget=3, temperature=0.0, spans=True,
        overrides=[{"role": "prefill"}, {"role": "decode"}])
    fe = ClusterFrontend(pool, router="disagg")
    hs = [fe.submit(_spec(p)) for p in prompts[:2]]
    fe.drain()
    src, dst = pool.engines
    assert src.obs.terminal_reasons() == {}     # prefill replica: no finishes
    terms = _terminals(src, dst)
    assert sorted(terms) == sorted(h.rid for h in hs)
    assert set(terms.values()) == {"length"}
    # the hop itself: snapshot instant on source, restore instant on dest,
    # sharing a flow id
    snaps = [s for s in src.obs.spans() if s.name == "handoff.snapshot"]
    rests = [s for s in dst.obs.spans() if s.name == "handoff.restore"]
    assert len(snaps) == len(rests) == 2
    assert ({s.args["flow"] for s in snaps}
            == {r.args["flow"] for r in rests})


def test_handoff_timing_monotonic(setup):
    """t_snapshot/t_restore come from the spans' monotonic clock: the hop
    latency is non-negative and consistent with `monotonic()` now."""
    cfg, params, prompts = setup
    pool = ReplicaPool.build(
        cfg, params, policy="duo", max_batch=2, max_seq=32,
        prefill_budget=3, temperature=0.0,
        overrides=[{"role": "prefill"}, {"role": "decode"}])
    fe = ClusterFrontend(pool, router="disagg")
    h = fe.submit(_spec(prompts[0]))
    fe.drain()
    assert len(h.handoffs) == 1
    hop = h.handoffs[0]
    assert hop["t_snapshot"] <= hop["t_restore"] <= monotonic()


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def _two_replica_recorders():
    a = SpanRecorder(enabled=True, replica=0)
    b = SpanRecorder(enabled=True, replica=1)
    t = a.begin("prefill.chunk", lane="prefill", rid=1, tokens=3)
    a.end(t)
    a.instant("handoff.snapshot", lane="lifecycle", flow=7, src=0, dst=1)
    b.instant("handoff.restore", lane="lifecycle", flow=7, src=0, dst=1)
    t = b.begin("decode.step", lane="decode", batch=2)
    b.end(t)
    b.instant("prefetch.dispatch", lane="prefetch", layer=0, n=2)
    return a, b


def test_chrome_trace_layout_and_flows():
    a, b = _two_replica_recorders()
    trace = to_chrome_trace([a, b])
    assert validate_trace(trace) == []
    evs = trace["traceEvents"]
    # one process per replica, named lanes
    names = {(e["pid"], e["args"]["name"]) for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    for pid in (0, 1):
        for lane in ("lifecycle", "prefill-chunk", "batched-decode",
                     "expert-prefetch"):
            assert (pid, lane) in names
    # intervals are X on the right lane-tid; instants are i
    x = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert x["prefill.chunk"]["pid"] == 0 and x["prefill.chunk"]["cat"] == "prefill"
    assert x["decode.step"]["pid"] == 1
    assert x["prefill.chunk"]["tid"] != x["decode.step"]["tid"]
    assert any(e["ph"] == "i" and e["name"] == "prefetch.dispatch"
               for e in evs)
    # the handoff flow: s on pid 0, f (bp="e") on pid 1, same id
    s = next(e for e in evs if e["ph"] == "s")
    f = next(e for e in evs if e["ph"] == "f")
    assert s["id"] == f["id"] == 7
    assert s["pid"] == 0 and f["pid"] == 1 and f["bp"] == "e"
    # timestamps are non-negative and rebased to the earliest span
    assert min(e["ts"] for e in evs if "ts" in e) == 0.0


def test_unpaired_flow_rejected():
    a, _ = _two_replica_recorders()
    trace = to_chrome_trace([a])        # restore end lives on recorder b
    errs = validate_trace(trace)
    assert errs and "unpaired" in errs[0]


def test_write_trace_roundtrip(tmp_path, setup):
    cfg, params, prompts = setup
    pool = ReplicaPool.build(
        cfg, params, policy="duo", max_batch=2, max_seq=32,
        prefill_budget=3, temperature=0.0, spans=True,
        overrides=[{"role": "prefill"}, {"role": "decode"}])
    fe = ClusterFrontend(pool, router="disagg")
    hs = [fe.submit(_spec(p)) for p in prompts[:2]]
    fe.drain()
    assert all(h.done for h in hs)
    out = tmp_path / "trace.json"
    write_trace(str(out), pool.recorders())
    trace = json.loads(out.read_text())
    assert validate_trace(trace) == []
    cats = {e.get("cat") for e in trace["traceEvents"]}
    assert {"prefill", "decode", "lifecycle", "handoff"} <= cats
    # the pool-level metrics snapshot validates too
    snap = pool.metrics_snapshot()
    assert validate_metrics_snapshot(snap) == []
    assert snap["cluster"]["cluster_handoffs_total"] == 2.0
    assert len(snap["replicas"]) == 2


# ---------------------------------------------------------------------------
# legacy views over the registry
# ---------------------------------------------------------------------------


def test_perf_counters_are_registry_views(setup):
    cfg, params, prompts = setup
    fe = _fe(cfg, params)
    h = fe.submit(_spec(prompts[0]))
    fe.drain()
    assert h.done
    eng = fe.engine
    # prefilled_tokens is a counter view and matches the offered prompt
    assert eng.prefilled_tokens == len(prompts[0])
    with pytest.raises(AttributeError):
        eng.prefilled_tokens = 0
    # PerfCounters fields read through the registry and reject writes
    assert eng.perf.decode_layers > 0
    with pytest.raises(AttributeError):
        eng.perf.decode_layers = 0
    snap = eng.metrics.snapshot()
    assert snap["engine_prefilled_tokens_total"] == float(len(prompts[0]))
    exp = eng.metrics.exposition()
    assert "# TYPE engine_prefilled_tokens_total counter" in exp
    assert math.isfinite(snap["decode_step_seconds"]["sum"])
