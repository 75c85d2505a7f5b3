"""Cross-request prefix/KV reuse benchmark (core/prefix.py, ISSUE 7).

Production traffic is dominated by SHARED prefixes — system prompts,
few-shot templates, multi-turn history — so this benchmark offers a
shared-prefix trace (a few long templates, each instantiated with short
unique suffixes, arriving by the benchmarks.common arrival processes) and
measures what the PrefixTree buys at each layer:

  * Engine — warm (prefix_cache=True) vs cold engine over the same trace:
    TTFT p50/p99, the hit-token fraction (tokens served from cache /
    offered prompt tokens), and `prefilled_tokens` (the un-hit work the
    engine actually ran). Tokens are asserted bit-exact vs the cold run at
    temperature 0 — reuse must be invisible in the output stream.
  * Cluster — 2 replicas, `prefix_affinity` vs `round_robin` on the same
    trace: the affinity router lands matching requests on the warm replica
    (overload-gated), so its TTFT tail shrinks while round_robin keeps
    re-prefilling templates on whichever replica the cursor hits.
    Template arrivals come in back-to-back pairs (AABB...), the pattern a
    blind cursor always splits across both replicas.
  * Disagg handoff — a 1-prefill + 1-decode pool with prefix caching: the
    second request of each template ships only its unique tail
    (`handoff_bytes_saved`, `n_tail_handoffs`).
  * Expert HBM — the per-replica residency bound must be untouched by KV
    reuse (`device_bytes == pool_capacity * bytes_per_expert`, zero
    regrows), checked on every pool.

``--smoke`` (CI) shrinks the trace and asserts the acceptance criteria:
(a) warm-vs-cold bit-exactness, (b) hit-token fraction > 0 on the shared
trace, (c) `prefix_affinity` beats `round_robin` on p99 TTFT at 2
replicas, (d) the per-replica expert-HBM bound holds — plus the tail-only
handoff strictly reducing the disagg pool's host KV bytes.

  PYTHONPATH=src python -m benchmarks.bench_prefix \
      --requests 16 --templates 2 --template-len 48 --suffix-len 4 \
      --arrival bursty [--smoke]
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
from benchmarks.bench_cluster import hbm_report  # noqa: E402

from repro.configs.base import get_config, reduced  # noqa: E402
from repro.core.qos import percentile_report  # noqa: E402
from repro.serving.api import (GenerationRequest,  # noqa: E402
                               SamplingParams)
from repro.serving.batching import (BatchedServingEngine,  # noqa: E402
                                    kv_row_bytes, parse_prefill_budget)
from repro.serving.cluster import (ClusterFrontend,  # noqa: E402
                                   ReplicaPool)
from repro.serving.frontend import ServingFrontend  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def make_shared_prefix_prompts(n: int, n_templates: int, template_len: int,
                               suffix_len: int, vocab: int, seed: int = 11):
    """The shared-prefix trace: `n_templates` long templates, each request
    = one template + a short unique suffix. Requests come in back-to-back
    same-template PAIRS (AABB...) — the arrival pattern a round-robin
    cursor always splits across replicas, while every non-leading request
    of a template is a prefix hit for whoever cached it."""
    rng = np.random.default_rng(seed)
    templates = [rng.integers(0, vocab, size=template_len).astype(np.int32)
                 for _ in range(n_templates)]
    # make templates diverge at position 0 so cross-template hits are 0
    for i, t in enumerate(templates):
        t[0] = i % vocab
    prompts = []
    for i in range(n):
        t = templates[(i // 2) % n_templates]
        sfx = rng.integers(0, vocab, size=suffix_len).astype(np.int32)
        prompts.append(np.concatenate([t, sfx]))
    return prompts


def warm_pool(pool: ReplicaPool, prompts, vocab: int, max_new: int) -> None:
    """Compile each replica's kernels outside the measurement window with
    workload-shaped RANDOM prompts (they seed the tree too, but tree-owned
    slots are reclaimed on demand — the measured trace evicts them)."""
    rng = np.random.default_rng(999)
    shape = len(max(prompts, key=len))
    for fe in pool.frontends:
        hs = [fe.submit(GenerationRequest(
                  prompt=rng.integers(0, vocab, size=shape)
                  .astype(np.int32),
                  params=SamplingParams(max_new_tokens=max_new)))
              for _ in range(2)]
        fe.drain()
        assert all(h.done for h in hs)


def offer(fe, prompts, arrivals, max_new: int):
    """Drive the trace through a frontend on its arrival stamps."""
    pending = list(zip(arrivals, prompts))
    handles = []
    while pending or not fe.idle:
        now = time.perf_counter()
        while pending and pending[0][0] <= now:
            arr, p = pending.pop(0)
            handles.append(fe.submit(GenerationRequest(
                prompt=p, params=SamplingParams(max_new_tokens=max_new),
                arrival=arr)))
        ev = fe.poll(now)
        if not ev.did_work and pending:
            time.sleep(max(pending[0][0] - time.perf_counter(), 0.0))
    return handles


def _ttfts(handles):
    return [h.req.result().ttft_wall for h in handles]


# ---------------------------------------------------------------------------
# engine layer: warm vs cold, bit-exact
# ---------------------------------------------------------------------------


def run_engine(cfg, params, prompts, args, budget, *, prefix_cache):
    eng = BatchedServingEngine(
        cfg, params, policy=args.policy, max_batch=args.max_batch,
        max_seq=max(len(p) for p in prompts) + args.max_new + 2,
        prefill_budget=budget, temperature=0.0, prefix_cache=prefix_cache)
    fe = ServingFrontend(eng)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    arrivals = t0 + arrival_offsets(args.arrival, args.rate, len(prompts),
                                    rng)
    handles = offer(fe, prompts, arrivals, args.max_new)
    offered_tokens = sum(len(p) for p in prompts)
    tree = eng.prefix
    rec = {
        "prefix_cache": prefix_cache,
        "ttft": percentile_report(_ttfts(handles)),
        "offered_prompt_tokens": offered_tokens,
        "prefilled_tokens": int(eng.prefilled_tokens),
        "hit_tokens": int(tree.hit_tokens) if tree else 0,
        "hit_fraction": (tree.hit_tokens / offered_tokens) if tree else 0.0,
        "reclaimed_slots": int(tree.reclaimed_slots) if tree else 0,
    }
    return rec, [list(h.tokens) for h in handles]


# ---------------------------------------------------------------------------
# cluster layer: prefix_affinity vs round_robin
# ---------------------------------------------------------------------------


def run_cluster(cfg, params, prompts, args, budget, *, router):
    mb = args.cluster_max_batch or args.max_batch
    pool = ReplicaPool.build(
        cfg, params, 2, policy=args.policy, max_batch=mb,
        max_seq=max(len(p) for p in prompts) + args.max_new + 2,
        prefill_budget=budget, temperature=0.0, prefix_cache=True)
    warm_pool(pool, prompts, cfg.vocab, args.max_new)
    fe = ClusterFrontend(pool, router=router)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    arrivals = t0 + arrival_offsets(args.arrival, args.rate, len(prompts),
                                    rng)
    handles = offer(fe, prompts, arrivals, args.max_new)
    hit_tokens = sum(e.prefix.hit_tokens for e in pool.engines)
    offered_tokens = sum(len(p) for p in prompts)
    rec = {
        "router": router,
        "ttft": percentile_report(_ttfts(handles)),
        "hit_tokens": int(hit_tokens),
        "hit_fraction": hit_tokens / offered_tokens,
        "prefilled_tokens": int(sum(e.prefilled_tokens
                                    for e in pool.engines)),
        "balance": [sum(1 for h in handles if h.replica == i)
                    for i in range(2)],
        "per_replica_hbm": hbm_report(pool),
    }
    return rec, [list(h.tokens) for h in handles]


# ---------------------------------------------------------------------------
# disagg layer: tail-only handoff
# ---------------------------------------------------------------------------


def run_disagg(cfg, params, prompts, args, budget, *, prefix_cache):
    pool = ReplicaPool.build(
        cfg, params, policy=args.policy, max_batch=args.max_batch,
        max_seq=max(len(p) for p in prompts) + args.max_new + 2,
        prefill_budget=budget, temperature=0.0, prefix_cache=prefix_cache,
        overrides=[{"role": "prefill"}, {"role": "decode"}])
    fe = ClusterFrontend(pool, router="disagg")
    toks = []
    for p in prompts:        # sequential: later templates find a warm head
        h = fe.submit(GenerationRequest(
            prompt=p, params=SamplingParams(max_new_tokens=args.max_new)))
        fe.drain()
        toks.append(list(h.tokens))
    rec = {
        "prefix_cache": prefix_cache,
        "handoffs": int(pool.n_handoffs),
        "tail_handoffs": int(pool.n_tail_handoffs),
        "handoff_kv_bytes": int(pool.handoff_bytes),
        "handoff_kv_bytes_saved": int(pool.handoff_bytes_saved),
        "kv_row_bytes": kv_row_bytes(pool.engines[0]),
        "per_replica_hbm": hbm_report(pool),
    }
    return rec, toks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--templates", type=int, default=2)
    ap.add_argument("--template-len", type=int, default=48)
    ap.add_argument("--suffix-len", type=int, default=4)
    ap.add_argument("--arrival", default="bursty", choices=list(ARRIVALS))
    ap.add_argument("--rate", type=float, default=50.0,
                    help="mean offered load (req/s); bursty clumps it")
    ap.add_argument("--max-new", type=int, default=3)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cluster-max-batch", type=int, default=None,
                    help="per-replica KV slots for the 2-replica router "
                         "comparison (default: --max-batch)")
    ap.add_argument("--policy", default="duo")
    ap.add_argument("--prefill-budget", default="4")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI sweep asserting warm==cold tokens, hit "
                         "fraction > 0, a prefix_affinity p99-TTFT win "
                         "over round_robin at 2 replicas, the tail-only "
                         "handoff byte drop, and the per-replica "
                         "expert-HBM bound")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.requests, args.max_new = 12, 2
        args.template_len, args.suffix_len = 40, 4
        # ONE slot per replica: a replica serving a single template hits
        # on every follower (the retained slot's rows are copied out
        # before the follower evicts it), while a replica fed BOTH
        # templates by a blind cursor always finds the wrong template
        # cached — the regime where prefix-aware routing is the whole
        # game. The single-engine run keeps 4 slots.
        args.cluster_max_batch = 1

    cfg = reduced(get_config(args.arch))
    from repro.models.model import build
    params = build(cfg).init(jax.random.PRNGKey(0))
    budget = parse_prefill_budget(args.prefill_budget)
    prompts = make_shared_prefix_prompts(
        args.requests, args.templates, args.template_len, args.suffix_len,
        cfg.vocab)
    records = {}

    # -- engine: warm vs cold, bit-exact -----------------------------------
    cold, cold_toks = run_engine(cfg, params, prompts, args, budget,
                                 prefix_cache=False)
    warm, warm_toks = run_engine(cfg, params, prompts, args, budget,
                                 prefix_cache=True)
    assert warm_toks == cold_toks, \
        "prefix reuse changed the token stream (temp 0 must be bit-exact)"
    records["engine"] = [cold, warm]
    print("engine (warm vs cold, same trace, tokens bit-exact):")
    for r in records["engine"]:
        print(f"  prefix_cache={str(r['prefix_cache']):5s} "
              f"ttft_p50={r['ttft']['p50']:7.3f}s "
              f"ttft_p99={r['ttft']['p99']:7.3f}s "
              f"prefilled={r['prefilled_tokens']:5d}/"
              f"{r['offered_prompt_tokens']:5d} "
              f"hit_fraction={r['hit_fraction']:.2f}")
    assert warm["prefilled_tokens"] < cold["prefilled_tokens"], \
        "prefix cache did not reduce prefilled tokens"

    # -- cluster: prefix_affinity vs round_robin ---------------------------
    print("\ncluster (2 replicas, shared-prefix trace):")
    records["cluster"] = []
    for router in ("round_robin", "prefix_affinity"):
        rec, toks = run_cluster(cfg, params, prompts, args, budget,
                                router=router)
        assert toks == cold_toks, f"{router} diverged from cold reference"
        records["cluster"].append(rec)
        hbm_ok = all(h["ok"] for h in rec["per_replica_hbm"])
        print(f"  {router:>16s} ttft_p50={rec['ttft']['p50']:7.3f}s "
              f"ttft_p99={rec['ttft']['p99']:7.3f}s "
              f"hit_fraction={rec['hit_fraction']:.2f} "
              f"balance={rec['balance']} "
              f"hbm={'ok' if hbm_ok else 'VIOLATED'}")
        assert hbm_ok, f"expert-HBM bound violated: {rec['per_replica_hbm']}"

    # -- disagg: tail-only handoff -----------------------------------------
    print("\ndisagg 1p:1d (sequential trace, tail-only handoff):")
    records["disagg"] = []
    for pc in (False, True):
        rec, toks = run_disagg(cfg, params, prompts[:6], args, budget,
                               prefix_cache=pc)
        assert toks == cold_toks[:6], "disagg run diverged from reference"
        records["disagg"].append(rec)
        print(f"  prefix_cache={str(pc):5s} handoffs={rec['handoffs']:3d} "
              f"tail={rec['tail_handoffs']:3d} "
              f"moved={rec['handoff_kv_bytes'] / 2**10:8.1f}KB "
              f"saved={rec['handoff_kv_bytes_saved'] / 2**10:8.1f}KB")
    full, tail = records["disagg"]
    assert tail["handoff_kv_bytes"] < full["handoff_kv_bytes"], \
        "tail-only handoff did not reduce host KV bytes moved"
    assert tail["handoff_kv_bytes"] + tail["handoff_kv_bytes_saved"] \
        == full["handoff_kv_bytes"]

    if args.smoke:
        assert warm["hit_fraction"] > 0.0, "no prefix hits on shared trace"
        rr, pa = records["cluster"]
        assert pa["hit_fraction"] > rr["hit_fraction"], \
            "prefix_affinity did not raise the cluster hit fraction"
        assert pa["ttft"]["p99"] < rr["ttft"]["p99"], \
            (f"prefix_affinity p99 TTFT {pa['ttft']['p99']:.3f}s did not "
             f"beat round_robin {rr['ttft']['p99']:.3f}s")
        emit_bench_json("prefix", {
            "warm_hit_fraction": warm["hit_fraction"],
            "warm_prefilled_tokens": warm["prefilled_tokens"],
            "cold_prefilled_tokens": cold["prefilled_tokens"],
            "round_robin_ttft_p99_s": rr["ttft"]["p99"],
            "prefix_affinity_ttft_p99_s": pa["ttft"]["p99"],
            "tail_handoff_bytes_saved": tail["handoff_kv_bytes_saved"],
        })
        print("\nbench_prefix smoke OK: warm==cold bit-exact; hit fraction "
              f"{warm['hit_fraction']:.2f}; prefix_affinity p99 "
              f"{pa['ttft']['p99']:.3f}s < round_robin "
              f"{rr['ttft']['p99']:.3f}s; tail handoff saved "
              f"{tail['handoff_kv_bytes_saved']} bytes; per-replica "
              "expert HBM bounded")

    out = args.out
    if out is None:
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "prefix.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
