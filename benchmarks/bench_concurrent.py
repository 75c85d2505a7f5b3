"""Concurrent-load QoS benchmark: request arrivals (``--arrival``: poisson /
bursty / ramp, see benchmarks.common.arrival_offsets) into the streaming
serving front-end, p50/p99 TTFT + TPOT vs offered load — plus per-request
TBT-SLO attainment and mid-flight cancellation latency, both measured off
the event stream.

The paper reports single-request TTFT/E2E; this driver measures the serving
regime those SLOs actually matter in — requests arriving mid-flight, decode
batched across in-flight requests, one shared expert cache, callers
streaming tokens through ``RequestHandle``s. Per offered load it reports:

  * TTFT p50/p99  (arrival -> first token, includes queueing)
  * TPOT p50/p99  (per-output-token decode latency after the first token)
  * TBT-SLO attainment (with --tbt-slo): per finished request, the fraction
    of its inter-token gaps under its tbt_slo (TBTLedger.attainment) —
    mean across requests + the fraction of requests fully attained
  * time-to-cancel (with --cancel-frac): wall time from the caller's
    cancel() to the engine's FinishEvent("cancelled") — i.e. until the KV
    slot, expert-residency contributions, and TBT entry are reclaimed
  * throughput (tokens/s), mean decode batch size, shed (SLO-rejected) count

  PYTHONPATH=src python benchmarks/bench_concurrent.py \
      --rates 0.5,1.0,2.0 --requests 8 --max-new 6 [--ttft-slo 30] \
      [--tbt-slo 0.5] [--cancel-frac 0.25 --cancel-after 2]
"""
import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.common import ARRIVALS, arrival_offsets  # noqa: E402

from repro.configs.base import get_config, reduced
from repro.core.qos import AdmissionController, percentile_report
from repro.data.pipeline import PromptWorkload, squad_like
from repro.models.model import build
from repro.serving.api import GenerationRequest, SamplingParams
from repro.serving.batching import (BatchedServingEngine, RequestQueue,
                                    parse_prefill_budget)
from repro.serving.frontend import ServingFrontend
from repro.launch.compile_cache import enable_compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def run_load(cfg, params, prompts, *, rate: float, max_new: int,
             max_batch: int, policy: str, ttft_slo, seed: int = 0,
             prefill_budget=None, tbt_slo=None, fairness="rr",
             cancel_frac: float = 0.0, cancel_after: int = 2,
             arrival: str = "poisson") -> dict:
    """Offer `prompts` at mean rate `rate` req/s through a ServingFrontend
    (arrival process: poisson / bursty / ramp — benchmarks.common); drain;
    summarize. With cancel_frac > 0, an evenly spread fraction of requests
    is cancelled mid-flight once it has streamed `cancel_after` tokens."""
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    arrivals = t0 + arrival_offsets(arrival, rate, len(prompts), rng)

    queue = RequestQueue(AdmissionController(default_ttft_slo=ttft_slo))
    eng = BatchedServingEngine(cfg, params, policy=policy,
                               max_batch=max_batch,
                               max_seq=max(len(p) for p in prompts)
                               + max_new + 2,
                               prefill_budget=prefill_budget,
                               tbt_slo=tbt_slo, prefill_fairness=fairness,
                               queue=queue, temperature=0.0)
    fe = ServingFrontend(eng)
    n_cancel = int(round(len(prompts) * cancel_frac))
    # submission order == arrival order, so rid == prompt index
    cancel_rids = (set(np.linspace(0, len(prompts) - 1, n_cancel,
                                   dtype=int).tolist()) if n_cancel else set())
    handles = {}
    cancel_times = []

    pending = list(zip(arrivals, prompts))
    while pending or not fe.idle:
        now = time.perf_counter()
        while pending and pending[0][0] <= now:
            arr, p = pending.pop(0)
            h = fe.submit(GenerationRequest(
                prompt=p, params=SamplingParams(max_new_tokens=max_new),
                tbt_slo=tbt_slo, arrival=arr))
            handles[h.rid] = h
        ev = fe.poll(now)
        # mid-flight cancellation, timed off the event stream: the
        # FinishEvent's timestamp is when the engine finished reclaiming
        # the request's KV slot / residency / ledger resources
        for rid in sorted(cancel_rids):
            h = handles.get(rid)
            if h is None:
                continue
            if h.done:
                cancel_rids.discard(rid)
                continue
            if len(h.tokens) >= cancel_after:
                t_req = time.perf_counter()
                if h.cancel():
                    fin = h.events[-1]
                    cancel_times.append(fin.t - t_req)
                cancel_rids.discard(rid)
        if not ev.did_work and pending:
            # idle until the next arrival
            time.sleep(max(pending[0][0] - time.perf_counter(), 0.0))
    wall = time.perf_counter() - t0

    done = [r.result() for r in eng.finished]
    ttfts = [r.ttft_wall for r in done]
    tpots = [(r.e2e_wall - r.ttft_wall) / max(len(r.tokens) - 1, 1)
             for r in done]
    total_tokens = sum(len(r.tokens) for r in done)
    rec = {
        "rate_req_s": rate,
        "arrival": arrival,
        "offered": len(prompts),
        "completed": len(done),
        "rejected": len(eng.queue.rejected),
        "cancelled": len(eng.cancelled),
        "ttft": percentile_report(ttfts),
        "tpot": percentile_report(tpots),
        "tokens_per_s": total_tokens / max(wall, 1e-9),
        "mean_decode_batch": (float(np.mean(eng.decode_batch_hist))
                              if eng.decode_batch_hist else 0.0),
        "wall_s": wall,
    }
    if tbt_slo is not None:
        # per-request TBT-SLO attainment over each finished request's gaps
        atts = [eng.tbt.attainment(r.rid, tbt_slo) for r in eng.finished]
        atts = [a for a in atts if not np.isnan(a)]
        rec["tbt_attain_mean"] = float(np.mean(atts)) if atts else float("nan")
        rec["tbt_attain_full"] = (float(np.mean([a == 1.0 for a in atts]))
                                  if atts else float("nan"))
    if cancel_times:
        rec["time_to_cancel"] = percentile_report(cancel_times)
        rec["time_to_cancel"]["max"] = float(max(cancel_times))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--rates", default="0.5,2.0",
                    help="comma list of offered loads (requests/s)")
    ap.add_argument("--arrival", default="poisson", choices=list(ARRIVALS),
                    help="arrival process: stationary poisson, bursty "
                         "(Gamma-renewal clumping), or a linear rate ramp")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=5)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--policy", default="duo+")
    ap.add_argument("--ttft-slo", type=float, default=None,
                    help="seconds; requests predicted to breach are shed")
    ap.add_argument("--prefill-budget", default=None,
                    help="chunked prefill tokens per step, 'auto' to derive "
                         "from the live LatencyModel (needs --tbt-slo), or "
                         "omit for monolithic")
    ap.add_argument("--tbt-slo", type=float, default=None,
                    help="per-request inter-token-gap target (s): drives "
                         "admission, the auto budget, and the attainment "
                         "report")
    ap.add_argument("--fairness", default="rr", choices=["rr", "fifo", "srf"],
                    help="chunked-prefill budget sharing across requests")
    ap.add_argument("--cancel-frac", type=float, default=0.0,
                    help="fraction of requests cancelled mid-flight "
                         "(time-to-cancel measured off the event stream)")
    ap.add_argument("--cancel-after", type=int, default=2,
                    help="tokens a victim streams before it is cancelled")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    wl = PromptWorkload(squad_like(cfg.vocab), seed=11)
    prompts = [p[: args.prompt_len] for p, _ in wl.prompts(args.requests)]

    print(f"{'rate':>6s} {'done':>5s} {'shed':>5s} {'cancel':>6s} "
          f"{'ttft_p50':>9s} {'ttft_p99':>9s} {'tpot_p50':>9s} "
          f"{'tpot_p99':>9s} {'tok/s':>7s} {'avgB':>5s} {'tbt_att':>8s} "
          f"{'t_cancel':>9s}")
    records = []
    for rate in [float(r) for r in args.rates.split(",")]:
        rec = run_load(cfg, params, prompts, rate=rate,
                       max_new=args.max_new, max_batch=args.max_batch,
                       policy=args.policy, ttft_slo=args.ttft_slo,
                       prefill_budget=parse_prefill_budget(args.prefill_budget),
                       tbt_slo=args.tbt_slo, fairness=args.fairness,
                       cancel_frac=args.cancel_frac,
                       cancel_after=args.cancel_after,
                       arrival=args.arrival)
        records.append(rec)
        att = rec.get("tbt_attain_mean", float("nan"))
        ttc = rec.get("time_to_cancel", {}).get("p99", float("nan"))
        print(f"{rate:6.2f} {rec['completed']:5d} {rec['rejected']:5d} "
              f"{rec['cancelled']:6d} "
              f"{rec['ttft']['p50']:8.2f}s {rec['ttft']['p99']:8.2f}s "
              f"{rec['tpot']['p50']:8.2f}s {rec['tpot']['p99']:8.2f}s "
              f"{rec['tokens_per_s']:7.2f} {rec['mean_decode_batch']:5.2f} "
              f"{att:8.2f} {ttc * 1e3:8.1f}m")

    out = args.out
    if out is None:
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "concurrent_qos.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
