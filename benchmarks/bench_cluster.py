"""Cluster-layer benchmark: replicas x router policy under non-stationary
arrivals (serving/cluster.py).

Sweeps a ReplicaPool of N BatchedServingEngine replicas behind each router
policy (round_robin / least_loaded / slo_headroom / expert_affinity) and
offers the same workload — alternating LONG and SHORT prompts, arriving by
the chosen process (default: bursty Gamma-renewal clumps, the regime where
load-oblivious routing falls over; see benchmarks.common.arrival_offsets).
Alternating lengths are round-robin's blind spot: with 2 replicas it sends
every long prompt to the same replica while the other idles through shorts,
so the long-prompt TTFT tail measures exactly what load/SLO-aware dispatch
buys. Per (replicas, router) run it reports:

  * TTFT p50/p99 and TPOT p50/p99 over completed requests
  * SLO attainment: fraction of OFFERED requests that completed with
    TTFT <= --ttft-slo (sheds count as misses)
  * shed rate, split by source: router rejections (slo_headroom found no
    capable replica), per-replica admission rejections, autopilot sheds
  * per-replica request balance and expert-HBM accounting — device bytes
    must equal ``pool_capacity * bytes_per_expert`` with zero regrows on
    EVERY replica (the PR-3 bound, now per replica)

``--disagg`` switches to the phase-disaggregation sweep instead: for each
replica count N it compares the symmetric pool (N interchangeable
replicas, least_loaded) against every prefill:decode split (1p:(N-1)d ...
(N-1)p:1d) under the disagg router — same bursty workload — and
additionally reports, per run, the handoff count, the snapshot->first-
post-handoff-token latency (p50/p99), the host-side KV bytes moved by
migrations, the peak host KV bytes parked by autopilot preemption, and
the per-ROLE expert-HBM bound. Handles follow their requests across the
prefill->decode hop, so TTFT/TPOT are end-to-end as the client sees them.

``--smoke`` (CI) runs a tiny sweep and asserts the acceptance criteria:
a 1-replica cluster is bit-exact vs a plain ServingFrontend at temperature
0, every replica's expert HBM stays at the fixed bound, and slo_headroom
or expert_affinity beats round_robin on p99 TTFT or SLO attainment at 2
replicas under bursty arrivals. ``--smoke --disagg`` instead asserts the
disagg acceptance criteria: a 1-prefill + 1-decode pool is bit-exact vs
the plain frontend, every completed request took exactly one handoff, and
the per-role HBM bound holds with zero regrows.

  PYTHONPATH=src python -m benchmarks.bench_cluster \
      --replicas 1,2 --routers round_robin,slo_headroom \
      --arrival bursty --requests 12 [--autopilot] [--disagg] [--smoke]
"""
import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.common import (ARRIVALS, arrival_offsets,  # noqa: E402
                               emit_bench_json)

from repro.configs.base import get_config, reduced  # noqa: E402
from repro.core.qos import percentile_report  # noqa: E402
from repro.core.scheduler import default_capacity  # noqa: E402
from repro.obs import (validate_metrics_snapshot, validate_trace,  # noqa: E402
                       write_trace)
from repro.serving.api import (GenerationRequest,  # noqa: E402
                               SamplingParams, TokenEvent)
from repro.serving.batching import (BatchedServingEngine,  # noqa: E402
                                    parse_prefill_budget)
from repro.serving.cluster import (ClusterFrontend, QosAutopilot,  # noqa: E402
                                   ReplicaPool, ROUTERS)
from repro.serving.frontend import ServingFrontend  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def make_prompts(n: int, long_len: int, short_len: int, vocab: int,
                 seed: int = 11):
    """Alternating long/short prompts — the workload shape that exposes
    size-oblivious routing."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab,
                         size=(long_len if i % 2 == 0 else short_len))
            .astype(np.int32) for i in range(n)]


def warm_pool(pool: ReplicaPool, prompts) -> None:
    """Compile each replica's kernels outside the measurement window: one
    long + one short prompt per replica (both final-chunk shapes, decode
    batch sizes 1-2) — and seed every replica's EWMA LatencyModel with real
    costs so slo_headroom predictions are honest from the first request.
    On a role='prefill' replica a direct submission parks in `held` forever
    (no decode replica is wired to a raw frontend), so warm-up there polls
    until both requests are held and cancels them — prefill shapes are
    exactly what that role executes in steady state."""
    longest = max(prompts, key=len)
    shortest = min(prompts, key=len)
    for i, fe in enumerate(pool.frontends):
        hs = [fe.submit(GenerationRequest(
                  prompt=p, params=SamplingParams(max_new_tokens=1)))
              for p in (longest, shortest)]
        if pool.roles[i] == "prefill":
            for _ in range(1000):
                if all(h.req.state == "held" or h.done for h in hs):
                    break
                fe.poll()
            for h in hs:
                h.cancel()
        else:
            fe.drain()


def hbm_report(pool: ReplicaPool) -> list:
    out = []
    for eng in pool.engines:
        res = eng.cache
        bound = res.pool_capacity * res.bytes_per_expert
        out.append({
            "device_bytes": int(res.device_bytes),
            "bound_bytes": int(bound),
            "regrow_events": int(res.regrow_events),
            "ok": bool(res.hbm_bound_ok),
        })
    return out


def run_cluster(cfg, params, prompts, *, n_replicas: int, router: str,
                rate: float, arrival: str, max_new: int, max_batch: int,
                policy: str, prefill_budget, ttft_slo, tbt_slo,
                autopilot: bool, seed: int = 0, warm: bool = True,
                overrides=None, preempt: bool = False, spans: bool = False,
                pool_sink=None) -> dict:
    pool = ReplicaPool.build(
        cfg, params, n_replicas, policy=policy, max_batch=max_batch,
        max_seq=max(len(p) for p in prompts) + max_new + 2,
        prefill_budget=prefill_budget, tbt_slo=tbt_slo, temperature=0.0,
        overrides=overrides, spans=spans)
    if pool_sink is not None:
        pool_sink.append(pool)
    if warm:
        warm_pool(pool, prompts)
    fe = ClusterFrontend(pool, router=router)
    ap = QosAutopilot(fe, preempt=preempt) if autopilot else None

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    arrivals = t0 + arrival_offsets(arrival, rate, len(prompts), rng)
    pending = list(zip(arrivals, prompts))
    handles = []
    paused_kv_peak = 0
    while pending or not fe.idle:
        now = time.perf_counter()
        while pending and pending[0][0] <= now:
            arr, p = pending.pop(0)
            handles.append(fe.submit(GenerationRequest(
                prompt=p, params=SamplingParams(max_new_tokens=max_new),
                ttft_slo=ttft_slo, tbt_slo=tbt_slo, arrival=arr)))
        ev = fe.poll(now)
        if ap is not None:
            paused_kv_peak = max(paused_kv_peak, ap.paused_kv_bytes)
        if not ev.did_work and pending:
            time.sleep(max(pending[0][0] - time.perf_counter(), 0.0))
    wall = time.perf_counter() - t0

    done = [h for h in handles
            if h.finish_reason in ("length", "stop_token")]
    results = [h.req.result() for h in done]
    ttfts = [r.ttft_wall for r in results]
    tpots = [(r.e2e_wall - r.ttft_wall) / max(len(r.tokens) - 1, 1)
             for r in results]
    n_adm_rej = sum(len(e.queue.rejected) for e in pool.engines)
    n_router_rej = fe.n_router_rejected
    n_shed = ap.n_shed if ap else 0
    offered = len(prompts)
    # snapshot -> first post-handoff token (the client-visible cost of the
    # prefill->decode hop; the first-ever token lands BEFORE the handoff)
    handoff_lat = []
    for h in done:
        if h.handoffs:
            t_s = h.handoffs[0]["t_snapshot"]
            after = [ev.t for ev in h.events
                     if isinstance(ev, TokenEvent) and ev.t >= t_s]
            if after:
                handoff_lat.append(min(after) - t_s)
    rec = {
        "replicas": n_replicas,
        "roles": list(pool.roles),
        "router": router,
        "arrival": arrival,
        "rate_req_s": rate,
        "offered": offered,
        "completed": len(done),
        "router_rejected": n_router_rej,
        "admission_rejected": n_adm_rej,
        "autopilot_shed": n_shed,
        "shed_rate": (n_router_rej + n_adm_rej + n_shed) / offered,
        "ttft": percentile_report(ttfts),
        "tpot": percentile_report(tpots),
        "tokens_per_s": sum(len(r.tokens) for r in results) / max(wall, 1e-9),
        "balance": [sum(1 for h in handles if h.replica == i)
                    for i in range(n_replicas)],
        "per_replica_hbm": hbm_report(pool),
        # snapshot-primitive traffic + host-side memory accounting: KV bytes
        # in flight during migrations and parked by autopilot preemption
        # live on the HOST, outside every replica's device bound above
        "handoffs": int(pool.n_handoffs),
        "migrated": int(pool.n_migrated),
        "handoff_kv_bytes": int(pool.handoff_bytes),
        "handoff_latency": (percentile_report(handoff_lat)
                            if handoff_lat else None),
        "preempted": int(ap.n_preempted) if ap else 0,
        "paused_kv_bytes_peak": int(paused_kv_peak),
        "wall_s": wall,
    }
    if ttft_slo is not None:
        rec["slo_attainment"] = sum(
            1 for r in results if r.ttft_wall <= ttft_slo) / offered
    return rec


def parity_check(cfg, params, prompts, *, max_new: int, max_batch: int,
                 policy: str, prefill_budget, routers) -> None:
    """1-replica cluster == plain ServingFrontend, bit-exact at temp 0,
    for every router policy (no SLOs: tokens must not depend on wall
    time)."""
    max_seq = max(len(p) for p in prompts) + max_new + 2
    eng = BatchedServingEngine(cfg, params, policy=policy,
                               max_batch=max_batch, max_seq=max_seq,
                               prefill_budget=prefill_budget,
                               temperature=0.0)
    base = ServingFrontend(eng)
    ref = [base.submit(GenerationRequest(
        prompt=p, params=SamplingParams(max_new_tokens=max_new)))
        for p in prompts]
    base.drain()
    for router in routers:
        pool = ReplicaPool.build(cfg, params, 1, policy=policy,
                                 max_batch=max_batch, max_seq=max_seq,
                                 prefill_budget=prefill_budget,
                                 temperature=0.0)
        fe = ClusterFrontend(pool, router=router)
        got = [fe.submit(GenerationRequest(
            prompt=p, params=SamplingParams(max_new_tokens=max_new)))
            for p in prompts]
        fe.drain()
        for r, g in zip(ref, got):
            assert list(r.tokens) == list(g.tokens), \
                f"1-replica cluster diverged under {router}"
        for h in hbm_report(pool):
            assert h["ok"], f"expert-HBM bound violated: {h}"
        print(f"  parity OK: 1-replica {router} == ServingFrontend "
              f"({len(prompts)} requests)")


def disagg_parity_check(cfg, params, prompts, *, max_new: int,
                        max_batch: int, policy: str, prefill_budget) -> None:
    """1-prefill + 1-decode pool == plain ServingFrontend, bit-exact at
    temp 0 — the KV snapshot handed across the hop must reproduce the
    uninterrupted computation exactly. Also asserts every request actually
    took the hop and both ROLES kept their expert-HBM bound."""
    max_seq = max(len(p) for p in prompts) + max_new + 2
    base = ServingFrontend(BatchedServingEngine(
        cfg, params, policy=policy, max_batch=max_batch, max_seq=max_seq,
        prefill_budget=prefill_budget, temperature=0.0))
    ref = [base.submit(GenerationRequest(
        prompt=p, params=SamplingParams(max_new_tokens=max_new)))
        for p in prompts]
    base.drain()
    pool = ReplicaPool.build(
        cfg, params, policy=policy, max_batch=max_batch, max_seq=max_seq,
        prefill_budget=prefill_budget, temperature=0.0,
        overrides=[{"role": "prefill"}, {"role": "decode"}])
    fe = ClusterFrontend(pool, router="disagg")
    got = [fe.submit(GenerationRequest(
        prompt=p, params=SamplingParams(max_new_tokens=max_new)))
        for p in prompts]
    fe.drain()
    for r, g in zip(ref, got):
        assert list(r.tokens) == list(g.tokens), \
            "disagg 1p+1d cluster diverged from plain frontend"
        assert len(g.handoffs) == 1 and g.replica == 1, \
            "request did not take the prefill->decode hop"
    assert pool.n_handoffs == len(prompts)
    for h in hbm_report(pool):
        assert h["ok"], f"per-role expert-HBM bound violated: {h}"
    print(f"  disagg parity OK: 1p+1d == ServingFrontend "
          f"({len(prompts)} requests, {pool.n_handoffs} handoffs, "
          f"{pool.handoff_bytes} host KV bytes moved)")


def check_disagg_trace(trace: dict) -> None:
    """The --trace-out acceptance criteria on a disagg run's Perfetto
    export: prefill-chunk / batched-decode / expert-prefetch spans land on
    DISTINCT lanes (tids) across the replica tracks, and at least one
    handoff flow pair links a source track to a different destination
    track (the arrow Perfetto draws for the prefill->decode hop)."""
    errs = validate_trace(trace)
    assert not errs, f"trace failed schema validation: {errs[:5]}"
    lane_tids = {}      # cat -> set of (pid, tid)
    for ev in trace["traceEvents"]:
        if ev["ph"] in ("X", "i") and ev.get("cat") in ("prefill", "decode",
                                                        "prefetch"):
            lane_tids.setdefault(ev["cat"], set()).add((ev["pid"], ev["tid"]))
    missing = {"prefill", "decode", "prefetch"} - set(lane_tids)
    assert not missing, f"no spans on lane(s) {sorted(missing)}"
    tids = {cat: {t for _, t in pts} for cat, pts in lane_tids.items()}
    assert tids["prefill"].isdisjoint(tids["decode"]) \
        and tids["decode"].isdisjoint(tids["prefetch"]), \
        f"lanes share a tid: {tids}"
    flows = {}          # id -> {ph: pid}
    for ev in trace["traceEvents"]:
        if ev["ph"] in ("s", "f"):
            flows.setdefault(ev["id"], {})[ev["ph"]] = ev["pid"]
    linked = [fid for fid, d in flows.items()
              if "s" in d and "f" in d and d["s"] != d["f"]]
    assert linked, "no handoff flow links two distinct replica tracks"
    print(f"  trace OK: {len(trace['traceEvents'])} events, "
          f"{len(linked)} cross-replica handoff flow(s), "
          "prefill/decode/prefetch on distinct lanes")


def run_disagg_sweep(cfg, params, prompts, args, budget) -> None:
    """--disagg mode: per replica count N, symmetric pool (least_loaded)
    vs every prefill:decode split under the disagg router; asserts the
    smoke acceptance criteria when --smoke is also set."""
    print("disagg parity check:")
    disagg_parity_check(cfg, params, prompts[:4], max_new=args.max_new,
                        max_batch=args.max_batch, policy=args.policy,
                        prefill_budget=budget)

    print(f"\n{'repl':>4s} {'split':>8s} {'done':>4s} "
          f"{'ttft_p99':>9s} {'tpot_p99':>9s} {'attain':>6s} "
          f"{'hoffs':>5s} {'hoff_p99':>9s} {'hoff_MB':>8s} "
          f"{'paused_KB':>9s} {'hbm':>4s}")
    records = []
    want_obs = args.trace_out is not None or args.metrics_out is not None
    obs_pools = []      # first disagg-router pool, spans enabled
    for n_rep in [int(r) for r in args.replicas.split(",")]:
        if n_rep < 2:
            print(f"{n_rep:4d}    (skip: disagg needs >= 2 replicas)")
            continue
        runs = [("sym", "least_loaded", None)]
        for p in range(1, n_rep):
            runs.append((f"{p}p:{n_rep - p}d", "disagg",
                         [{"role": "prefill"}] * p
                         + [{"role": "decode"}] * (n_rep - p)))
        for split, router, overrides in runs:
            capture = want_obs and router == "disagg" and not obs_pools
            ov = overrides
            if capture and ov is not None:
                # Tiny smoke grids fit every (layer, expert) inside the
                # policy-default capacity, which silences the prefetch
                # stream entirely (everything is resident after the first
                # pass). Cap the captured decode replicas just below the
                # full grid so the timeline shows the dual-phase
                # prefetch/correction traffic it exists to visualise.
                grid = cfg.n_layers * cfg.n_experts
                cap = default_capacity(args.policy, cfg.n_layers,
                                       cfg.n_experts, cfg.top_k,
                                       batch=args.max_batch)
                if cap >= grid:
                    ov = [dict(o, cache_capacity=max(cfg.n_experts,
                                                     grid - 2))
                          if o.get("role") == "decode" else o for o in ov]
            rec = run_cluster(
                cfg, params, prompts, n_replicas=n_rep, router=router,
                rate=args.rate, arrival=args.arrival, max_new=args.max_new,
                max_batch=args.max_batch, policy=args.policy,
                prefill_budget=budget, ttft_slo=args.ttft_slo,
                tbt_slo=args.tbt_slo, overrides=ov,
                autopilot=args.autopilot or args.smoke,
                preempt=args.autopilot, spans=capture,
                pool_sink=obs_pools if capture else None)
            rec["split"] = split
            records.append(rec)
            hbm_ok = all(h["ok"] for h in rec["per_replica_hbm"])
            hl = rec["handoff_latency"]
            print(f"{n_rep:4d} {split:>8s} {rec['completed']:4d} "
                  f"{rec['ttft']['p99']:8.3f}s {rec['tpot']['p99']:8.3f}s "
                  f"{rec.get('slo_attainment', float('nan')):6.2f} "
                  f"{rec['handoffs']:5d} "
                  f"{(hl['p99'] if hl else float('nan')):8.3f}s "
                  f"{rec['handoff_kv_bytes'] / 2**20:8.2f} "
                  f"{rec['paused_kv_bytes_peak'] / 2**10:9.1f} "
                  f"{'ok' if hbm_ok else 'VIOLATED':>4s}")
            assert hbm_ok, ("per-role expert-HBM bound violated: "
                            f"{rec['per_replica_hbm']}")
            if router == "disagg":
                assert rec["handoffs"] >= rec["completed"], \
                    "a completed request never took the prefill->decode hop"

    if obs_pools:
        pool = obs_pools[0]
        for p in (args.trace_out, args.metrics_out):
            if p and os.path.dirname(p):
                os.makedirs(os.path.dirname(p), exist_ok=True)
        if args.trace_out:
            trace = write_trace(args.trace_out, pool.recorders())
            print(f"wrote {args.trace_out} "
                  f"({len(trace['traceEvents'])} events)")
            check_disagg_trace(trace)
        if args.metrics_out:
            snap = pool.metrics_snapshot()
            errs = validate_metrics_snapshot(snap)
            assert not errs, f"metrics snapshot invalid: {errs[:5]}"
            with open(args.metrics_out, "w") as f:
                json.dump(snap, f, indent=1, sort_keys=True)
            print(f"wrote {args.metrics_out}")

    if args.smoke:
        d = next(r for r in records if r["router"] == "disagg")
        emit_bench_json("cluster_disagg", {
            "offered": d["offered"], "completed": d["completed"],
            "handoffs": d["handoffs"],
            "handoff_kv_bytes": d["handoff_kv_bytes"],
            "ttft_p99_s": d["ttft"]["p99"], "tpot_p99_s": d["tpot"]["p99"],
            "tokens_per_s": d["tokens_per_s"], "wall_s": d["wall_s"],
        })
        print("\nbench_cluster --disagg smoke OK: 1p+1d bit-exact vs plain "
              "frontend; every completed request took the handoff; "
              "per-role expert HBM bounded")

    out = args.out
    if out is None:
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "cluster_disagg.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {out}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--replicas", default="1,2")
    ap.add_argument("--routers", default=",".join(ROUTERS))
    ap.add_argument("--arrival", default="bursty", choices=list(ARRIVALS))
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean offered load (req/s); bursty clumps it")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--long-len", type=int, default=40)
    ap.add_argument("--short-len", type=int, default=6)
    ap.add_argument("--policy", default="duo+")
    ap.add_argument("--prefill-budget", default="4")
    ap.add_argument("--ttft-slo", type=float, default=2.0)
    ap.add_argument("--tbt-slo", type=float, default=None)
    ap.add_argument("--autopilot", action="store_true",
                    help="attach the QosAutopilot (mid-flight SLO shedding)")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill:decode split sweep (vs symmetric pool) "
                         "instead of the router sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI sweep asserting 1-replica parity, the "
                         "per-replica expert-HBM bound, and an SLO/"
                         "affinity-router win over round_robin")
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-out", default=None,
                    help="(--disagg) write a Perfetto JSON timeline of the "
                         "first disagg run (spans on) and assert the "
                         "prefill/decode/prefetch lanes + handoff flows")
    ap.add_argument("--metrics-out", default=None,
                    help="(--disagg) dump the captured pool's "
                         "cluster+replica metrics snapshot as JSON")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.replicas, args.requests, args.max_new = "2", 10, 3
        args.routers = "round_robin,slo_headroom,expert_affinity"

    cfg = reduced(get_config(args.arch))
    from repro.models.model import build
    params = build(cfg).init(jax.random.PRNGKey(0))
    prompts = make_prompts(args.requests, args.long_len, args.short_len,
                           cfg.vocab)
    budget = parse_prefill_budget(args.prefill_budget)
    routers = args.routers.split(",")

    if args.disagg:
        run_disagg_sweep(cfg, params, prompts, args, budget)
        return

    print("1-replica parity check:")
    parity_check(cfg, params, prompts[:4], max_new=args.max_new,
                 max_batch=args.max_batch, policy=args.policy,
                 prefill_budget=budget,
                 routers=routers if args.smoke else routers[:1])

    print(f"\n{'repl':>4s} {'router':>16s} {'done':>4s} {'shed':>4s} "
          f"{'ttft_p50':>9s} {'ttft_p99':>9s} {'tpot_p99':>9s} "
          f"{'attain':>6s} {'balance':>12s} {'hbm':>4s}")
    records = []
    for n_rep in [int(r) for r in args.replicas.split(",")]:
        for router in routers:
            rec = run_cluster(
                cfg, params, prompts, n_replicas=n_rep, router=router,
                rate=args.rate, arrival=args.arrival, max_new=args.max_new,
                max_batch=args.max_batch, policy=args.policy,
                prefill_budget=budget, ttft_slo=args.ttft_slo,
                tbt_slo=args.tbt_slo,
                autopilot=args.autopilot or args.smoke)
            records.append(rec)
            hbm_ok = all(h["ok"] for h in rec["per_replica_hbm"])
            att = rec.get("slo_attainment", float("nan"))
            n_shed = (rec["router_rejected"] + rec["admission_rejected"]
                      + rec["autopilot_shed"])
            print(f"{n_rep:4d} {router:>16s} {rec['completed']:4d} "
                  f"{n_shed:4d} {rec['ttft']['p50']:8.3f}s "
                  f"{rec['ttft']['p99']:8.3f}s {rec['tpot']['p99']:8.3f}s "
                  f"{att:6.2f} {str(rec['balance']):>12s} "
                  f"{'ok' if hbm_ok else 'VIOLATED':>4s}")
            assert hbm_ok, \
                f"per-replica expert-HBM bound violated: {rec['per_replica_hbm']}"

    if args.smoke:
        by = {(r["replicas"], r["router"]): r for r in records}
        rr = by[(2, "round_robin")]
        wins = []
        for name in ("slo_headroom", "expert_affinity"):
            c = by[(2, name)]
            wins.append(c["ttft"]["p99"] < rr["ttft"]["p99"])
            wins.append(c.get("slo_attainment", 0.0)
                        > rr.get("slo_attainment", 0.0))
        assert any(wins), (
            "neither slo_headroom nor expert_affinity beat round_robin on "
            f"p99 TTFT or SLO attainment: {json.dumps(records, indent=1)}")
        emit_bench_json("cluster", {
            name: {"completed": by[(2, name)]["completed"],
                   "ttft_p99_s": by[(2, name)]["ttft"]["p99"],
                   "slo_attainment": by[(2, name)].get(
                       "slo_attainment", float("nan")),
                   "tokens_per_s": by[(2, name)]["tokens_per_s"]}
            for name in ("round_robin", "slo_headroom", "expert_affinity")})
        print("\nbench_cluster smoke OK: QoS-aware routing beats "
              "round_robin under bursty arrivals; per-replica expert HBM "
              "bounded; 1-replica cluster bit-exact")

    out = args.out
    if out is None:
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "cluster_router.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
