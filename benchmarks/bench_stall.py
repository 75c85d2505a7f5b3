"""Inter-token stall benchmark: chunked vs monolithic prefill (DUO engine).

The paper's phase-disparity argument says a uniform prefill policy inflates
tail latency for everyone else; the sharpest symptom in a continuous-batching
engine is the inter-token gap (TBT) of in-flight decoders while a long prompt
prefills. Monolithic prefill freezes every decoder for the full prefill wall
time; chunked prefill (``prefill_budget``) bounds the freeze to one chunk +
one batched decode step.

Protocol: N short-prompt decoders are submitted and warmed into steady-state
decode; one sacrificial long prompt is driven through first so both modes'
prefill kernels are compiled outside the measurement window; then the gap
ledger position is snapshotted and the measured long prompts arrive. We
report p50/p99/max inter-token gap over the decoders' tokens plus the long
prompts' TTFT (p50/p99 tails across the long arrivals), for monolithic
(prefill_budget=None) vs chunked runs of the same workload.

``--fairness both`` runs every chunked budget under head-of-line ("fifo")
AND round-robin ("rr") budget rotation; ``--fairness all`` adds
shortest-remaining-first ("srf"). The TTFT-tail story is the *straggler*:
a short prompt submitted right after the long ones. Under FIFO it waits
for every long prefill ahead of it to finish completely (TTFT ~ sum of
long prefills); under RR the per-step budget rotates, so the straggler
finishes after ~n_prefilling turns; under SRF the straggler — by
construction the shortest remaining — overtakes every long prefill
immediately, the best straggler TTFT of the three, while the LONG
prompts' TTFT tail pays for everyone that overtook them. For EQUAL-length
overlapping prompts RR is processor sharing — everyone finishes late
together — so the trade is reported, not assumed: per mode we print the
long prompts' TTFT p50/p99 AND the straggler's TTFT.

Every record also carries the expert-HBM accounting of the unified
ExpertResidency (device bytes vs the capacity bound); ``--smoke`` runs a
tiny workload and exits nonzero if the bound is ever exceeded (CI).

  PYTHONPATH=src python benchmarks/bench_stall.py \
      --budgets 4,8 --long-len 48 --n-long 2 [--policy duo] \
      [--fairness fifo|rr|both] [--smoke]
"""
import argparse
import json
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.common import emit_bench_json  # noqa: E402

from repro.configs.base import get_config, reduced  # noqa: E402
from repro.core.qos import TBTLedger, percentile_report
from repro.models.model import build
from repro.serving.batching import BatchedServingEngine
from repro.launch.compile_cache import enable_compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def run_stall(cfg, params, *, policy: str, prefill_budget, n_decoders: int,
              decoder_len: int, long_len: int, n_long: int,
              warm_steps: int, seed: int = 0,
              fairness: str = "rr") -> dict:
    """One workload pass; returns decoder-gap percentiles + long TTFTs."""
    rng = np.random.default_rng(seed)
    max_new = warm_steps + (n_long + 1) * (long_len + 4) + 12
    eng = BatchedServingEngine(
        cfg, params, policy=policy, max_batch=n_decoders + n_long + 1,
        max_seq=long_len + max_new + 2, prefill_budget=prefill_budget,
        prefill_fairness=fairness, temperature=0.0)
    # exact-measurement ledger: the serving default bounds by_rid at 1024
    # gaps per request, which would left-evict warm-phase samples under
    # long runs and silently shift the absolute `mark` indices below
    eng.tbt = TBTLedger(window=None, per_rid_window=None,
                        closed_window=None)
    decoders = [eng.submit(rng.integers(0, cfg.vocab, size=decoder_len)
                           .astype(np.int32), max_new=max_new)
                for _ in range(n_decoders)]
    for _ in range(warm_steps):
        eng.step()
    # sacrificial warm burst OUTSIDE the measurement window: compiles the
    # (monolithic or chunked) prefill kernels for both prompt lengths AND —
    # via staggered max_new retirement — every decode batch size the storm
    # can reach (each jitted decode step is shape-specialized on B; without
    # this, whichever mode ramps the batch higher eats multi-second compile
    # stalls inside the measurement and the gap comparison is meaningless)
    warms = [eng.submit(rng.integers(0, cfg.vocab, size=long_len)
                        .astype(np.int32), max_new=2 + i)
             for i in range(n_long)]
    warms.append(eng.submit(rng.integers(0, cfg.vocab, size=decoder_len)
                            .astype(np.int32), max_new=2 + n_long))
    while any(not r.done for r in warms):
        eng.step()
    assert all(r.state == "running" for r in decoders), \
        "decoders must be in steady-state decode before the long arrivals"
    # snapshot ledger position (NOT a reset: per-request baselines survive,
    # so the stall step itself still yields a gap sample)
    mark = {r.rid: len(eng.tbt.by_rid.get(r.rid, [])) for r in decoders}

    longs = [eng.submit(rng.integers(0, cfg.vocab, size=long_len)
                        .astype(np.int32), max_new=2)
             for _ in range(n_long)]
    # the straggler: a short prompt stuck behind the long arrivals — the
    # request whose TTFT fairness is supposed to rescue
    straggler = eng.submit(rng.integers(0, cfg.vocab, size=decoder_len)
                           .astype(np.int32), max_new=2)
    while any(not r.done for r in longs + [straggler]):
        eng.step()
    for _ in range(2):  # a couple of post-storm decode steps
        eng.step()

    gaps = [g for r in decoders
            for g in list(eng.tbt.by_rid.get(r.rid, []))[mark[r.rid]:]]
    rep = percentile_report(gaps)
    rep["max"] = max(gaps) if gaps else float("nan")
    res = eng.cache
    ttfts = [r.t_first - r.arrival for r in longs]
    return {
        "mode": ("monolithic" if prefill_budget is None
                 else f"chunked[{prefill_budget}]/{fairness}"),
        "policy": policy,
        "decoder_gap": rep,
        "n_gaps": len(gaps),
        "long_ttft": ttfts,
        "long_ttft_tail": percentile_report(ttfts),
        "straggler_ttft": straggler.t_first - straggler.arrival,
        "steps": eng.step_count,
        # unified-residency accounting: the fixed pool IS the footprint
        "expert_hbm_bytes": res.device_bytes,
        "expert_hbm_bound": res.capacity * res.bytes_per_expert,
        "expert_pool_regrows": res.regrow_events,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--policy", default="duo")
    ap.add_argument("--budgets", default="4,8",
                    help="comma list of chunk budgets (tokens/step)")
    ap.add_argument("--fairness", default="rr",
                    choices=["fifo", "rr", "srf", "both", "all"],
                    help="budget sharing across prefilling requests; "
                         "'both' compares fifo vs rr TTFT tails, 'all' "
                         "adds shortest-remaining-first")
    ap.add_argument("--decoders", type=int, default=2)
    ap.add_argument("--decoder-len", type=int, default=8)
    ap.add_argument("--long-len", type=int, default=48)
    ap.add_argument("--n-long", type=int, default=2)
    ap.add_argument("--warm-steps", type=int, default=6)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run; assert the expert-HBM bound and the "
                         "stall bound, exit nonzero on violation")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        args.budgets, args.decoders, args.n_long = "2", 1, 1
        args.long_len, args.decoder_len, args.warm_steps = 12, 6, 2

    cfg = reduced(get_config(args.arch))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))

    budgets = [None] + [int(b) for b in args.budgets.split(",")]
    fair_modes = {"both": ["fifo", "rr"],
                  "all": ["fifo", "rr", "srf"]}.get(args.fairness,
                                                    [args.fairness])
    print(f"{'mode':>18s} {'gap_p50':>9s} {'gap_p99':>9s} {'gap_max':>9s} "
          f"{'ttft_p50':>9s} {'ttft_p99':>9s} {'straggler':>10s}")
    records = []
    for budget in budgets:
        for fair in (fair_modes if budget is not None else fair_modes[:1]):
            rec = run_stall(cfg, params, policy=args.policy,
                            prefill_budget=budget,
                            n_decoders=args.decoders,
                            decoder_len=args.decoder_len,
                            long_len=args.long_len, n_long=args.n_long,
                            warm_steps=args.warm_steps, fairness=fair)
            records.append(rec)
            g, t = rec["decoder_gap"], rec["long_ttft_tail"]
            print(f"{rec['mode']:>18s} {g['p50']*1e3:8.1f}m "
                  f"{g['p99']*1e3:8.1f}m {g['max']*1e3:8.1f}m "
                  f"{t['p50']:8.2f}s {t['p99']:8.2f}s "
                  f"{rec['straggler_ttft']:9.2f}s")

    mono = records[0]["decoder_gap"]["max"]
    for rec in records[1:]:
        verdict = "LOWER" if rec["decoder_gap"]["max"] < mono else "NOT lower"
        print(f"{rec['mode']}: max gap {verdict} than monolithic "
              f"({rec['decoder_gap']['max']*1e3:.1f}ms vs {mono*1e3:.1f}ms)")

    ok = True
    for rec in records:
        if rec["expert_hbm_bytes"] > rec["expert_hbm_bound"] \
                or rec["expert_pool_regrows"]:
            ok = False
            print(f"HBM BOUND VIOLATED in {rec['mode']}: "
                  f"{rec['expert_hbm_bytes']} > {rec['expert_hbm_bound']} "
                  f"(regrows={rec['expert_pool_regrows']})")
    if ok:
        print("expert-HBM bound held for every mode "
              f"(<= capacity x bytes_per_expert = "
              f"{records[0]['expert_hbm_bound']} B)")

    if args.smoke:
        # the CI contract is the expert-HBM bound (deterministic); the gap
        # comparison is printed above but not asserted — at smoke sizes a
        # warm monolithic prefill is fast enough that wall-clock ordering
        # is noise on a shared runner (the real bound is measured by the
        # full bench and pinned structurally by
        # tests/test_serving_batch.py::test_chunked_interleaving_is_stall_free)
        assert ok, "expert-HBM bound violated"
        assert all(r["n_gaps"] > 0 for r in records), "no gaps measured"
        emit_bench_json("stall", {
            r["mode"].replace("/", "_"): {
                "gap_p50_ms": r["decoder_gap"]["p50"] * 1e3,
                "gap_max_ms": r["decoder_gap"]["max"] * 1e3,
                "n_gaps": r["n_gaps"],
                "long_ttft_p99_s": r["long_ttft_tail"]["p99"],
                "straggler_ttft_s": r["straggler_ttft"],
            } for r in records})
        print("bench_stall smoke OK")
        return

    out = args.out
    if out is None:
        os.makedirs(RESULTS, exist_ok=True)
        out = os.path.join(RESULTS, "stall.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
