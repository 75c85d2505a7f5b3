"""Streaming serving demo: concurrent requests through the request-handle
front-end (spec -> handle -> events) vs the same requests served one-by-one,
with token-parity verification, a live mid-flight cancellation, and both
SLO-shedding layers — admission rejection and the QosAutopilot's mid-flight
"slo_shed" cancellation (serving/cluster.py; see examples/serve_cluster.py
for the multi-replica layer above this).

The serving API in three moves:

  1. Describe a request:   GenerationRequest(prompt, SamplingParams(...),
                           ttft_slo=..., tbt_slo=..., priority=...)
  2. Submit, get a handle: h = frontend.submit(spec)  — an iterator that
                           streams tokens as the engine emits them, with
                           .status / .result() / .cancel()
  3. Drive cooperatively:  iterating a handle (or frontend.poll()) runs the
                           engine step loop; no threads anywhere.

Cancellation is synchronous: h.cancel() frees the request's KV slot,
drops its expert-residency contributions from the shared ledger, closes
its TBT entry, and the handle is terminal before the call returns —
surviving requests' tokens are bit-unaffected.

  PYTHONPATH=src python examples/serve_concurrent.py --requests 4 --max-new 5
  PYTHONPATH=src python examples/serve_concurrent.py --smoke   # CI
"""
import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config, reduced
from repro.core.qos import AdmissionController, LatencyModel, percentile_report
from repro.data.pipeline import PromptWorkload, squad_like
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build
from repro.serving.api import GenerationRequest, SamplingParams
from repro.serving.batching import (BatchedServingEngine, RequestQueue,
                                    parse_prefill_budget)
from repro.serving.cluster import QosAutopilot
from repro.serving.engine import MoEServingEngine
from repro.serving.frontend import ServingFrontend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=5)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--policy", default="duo+")
    ap.add_argument("--prefill-budget", default=None,
                    help="prompt tokens of chunked prefill per engine step "
                         "(stall-free interleaving), or 'auto' to derive "
                         "the chunk from the live LatencyModel via "
                         "--tbt-slo; default monolithic")
    ap.add_argument("--tbt-slo", type=float, default=None,
                    help="target inter-token gap (s) for auto budget")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI: small workload, chunked "
                         "prefill, asserts parity + cancellation safety")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.requests, args.max_new = 3, 3
        args.prefill_budget = args.prefill_budget or "2"

    cfg = reduced(get_config(args.arch))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    wl = PromptWorkload(squad_like(cfg.vocab), seed=5)
    prompts = [p[:16] for p, _ in wl.prompts(args.requests)]

    # sequential baseline (paper-scope engine, one request at a time)
    seq = MoEServingEngine(cfg, params, policy=args.policy, temperature=0.0)
    t0 = time.perf_counter()
    seq_results = [seq.serve(p, max_new=args.max_new) for p in prompts]
    seq_wall = time.perf_counter() - t0

    # [streaming] all requests in flight through the request-handle
    # front-end: submit typed specs, stream each handle round-robin so the
    # tokens print in the interleaved order the engine produces them
    eng = BatchedServingEngine(cfg, params, policy=args.policy,
                               max_batch=args.max_batch, max_seq=64,
                               prefill_budget=parse_prefill_budget(
                                   args.prefill_budget),
                               tbt_slo=args.tbt_slo,
                               temperature=0.0)
    fe = ServingFrontend(eng)
    t0 = time.perf_counter()
    handles = [fe.submit(GenerationRequest(
        prompt=p, params=SamplingParams(max_new_tokens=args.max_new),
        priority=i % 2))          # alternate priorities, just to show them
        for i, p in enumerate(prompts)]
    streams = [[] for _ in handles]
    iters = [iter(h) for h in handles]
    live = list(range(len(handles)))
    while live:
        for i in list(live):
            try:
                streams[i].append(next(iters[i]))
            except StopIteration:
                live.remove(i)
    batch_wall = time.perf_counter() - t0

    print(f"{args.requests} requests, max_new={args.max_new}, "
          f"policy={args.policy}")
    ok = True
    for i, (h, s) in enumerate(zip(handles, seq_results)):
        same = bool(np.array_equal(np.asarray(streams[i]), s.tokens))
        ok &= same
        print(f"  req{i}: streamed={streams[i]} status={h.status} "
              f"reason={h.finish_reason} match_sequential={same}")
    ttfts = [h.result().ttft_wall for h in handles]
    print(f"sequential wall: {seq_wall:6.2f}s   "
          f"streamed wall: {batch_wall:6.2f}s "
          f"({seq_wall / max(batch_wall, 1e-9):.2f}x)")
    print(f"streamed TTFT: {percentile_report(ttfts)}  "
          f"mean decode batch: {np.mean(eng.decode_batch_hist):.2f}")
    assert ok, "streamed tokens diverged from sequential"

    # [cancellation] a fresh batch; one request is cancelled after its
    # second token — its KV slot and expert budget free immediately, the
    # survivor's tokens stay bit-identical to its sequential run. Needs
    # two prompts and enough decode steps for a mid-flight cancel.
    if args.requests < 2 or args.max_new < 2:
        print("cancellation demo skipped (needs --requests >= 2 and "
              "--max-new >= 2)")
    else:
        eng2 = BatchedServingEngine(cfg, params, policy=args.policy,
                                    max_batch=2, max_seq=64,
                                    prefill_budget=parse_prefill_budget(
                                        args.prefill_budget),
                                    tbt_slo=args.tbt_slo, temperature=0.0)
        fe2 = ServingFrontend(eng2)
        survivor = fe2.submit(GenerationRequest(
            prompt=prompts[0],
            params=SamplingParams(max_new_tokens=args.max_new)))
        victim = fe2.submit(GenerationRequest(
            prompt=prompts[1],
            params=SamplingParams(max_new_tokens=args.max_new)))
        while len(victim.tokens) < 2 and not victim.done:
            fe2.poll()
        t_req = time.perf_counter()
        assert victim.cancel()
        t_cancel = victim.events[-1].t - t_req
        fe2.drain()
        surv_ok = bool(np.array_equal(survivor.result().tokens,
                                      seq_results[0].tokens))
        print(f"cancellation demo: victim cancelled after "
              f"{len(victim.tokens)} tokens in {t_cancel * 1e3:.2f}ms "
              f"(slot freed: {victim.req.slot in eng2._free}); "
              f"survivor bit-exact: {surv_ok}")
        assert surv_ok, "cancellation perturbed the surviving request"
        assert victim.finish_reason == "cancelled"

    # [SLO shedding] two layers close the QoS loop:
    #  * admission: a pessimistic cost model + tight deadline -> the queue
    #    rejects the request before it ever takes a KV slot;
    #  * QosAutopilot (serving/cluster.py): requests that WERE admitted but
    #    whose deadline becomes unmeetable mid-flight are shed
    #    automatically with reason="slo_shed" — no hand-rolled
    #    deadline-watching + h.cancel() loop in caller code anymore.
    queue = RequestQueue(AdmissionController(
        LatencyModel(prefill_per_token=10.0), default_ttft_slo=1.0))
    shed = BatchedServingEngine(cfg, params, policy=args.policy,
                                max_batch=2, max_seq=64, queue=queue,
                                temperature=0.0)
    fe3 = ServingFrontend(shed)
    doomed = fe3.submit(GenerationRequest(
        prompt=prompts[0], params=SamplingParams(max_new_tokens=2)))
    fe3.poll()
    print(f"SLO demo: {len(queue.rejected)} request(s) shed at admission "
          f"(predicted TTFT over a 1s deadline); handle status: "
          f"{doomed.status}")

    fe4 = ServingFrontend(BatchedServingEngine(
        cfg, params, policy=args.policy, max_batch=2, max_seq=64,
        temperature=0.0))
    autopilot = QosAutopilot(fe4)
    laggard = fe4.submit(GenerationRequest(
        prompt=prompts[0], params=SamplingParams(max_new_tokens=16),
        tbt_slo=0.3))
    while len(laggard.tokens) < 2 and not laggard.done:
        fe4.poll()
    # scan with a clock far past the next token's 300ms deadline — in a
    # real deployment the poll loop's own wall clock does this
    fe4.poll(time.perf_counter() + 100.0)
    print(f"autopilot demo: laggard shed mid-decode after "
          f"{len(laggard.tokens)} tokens (reason={laggard.finish_reason}, "
          f"shed counts={autopilot.by_reason}, engine n_slo_shed="
          f"{fe4.engine.n_slo_shed})")

    if args.smoke:
        assert doomed.finish_reason == "rejected"
        assert laggard.finish_reason == "slo_shed"
        assert autopilot.n_shed == 1
        assert laggard.req.slot in fe4.engine._free
        print("serve_concurrent smoke OK")


if __name__ == "__main__":
    main()
