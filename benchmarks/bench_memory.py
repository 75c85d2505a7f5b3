"""Table II reproduction: peak memory per (model, policy) + GPU-only
reference. Byte-accounted from policy residency (CacheState.peak_bytes) +
non-expert weights + KV cache, under the paper's quantization.

Since the unified ExpertResidency (core/cache.py), the simulator's ledger
peak is no longer an *estimate* of device behaviour — the engine's expert
HBM is a preallocated slot pool whose size IS the bound. ``--smoke`` runs
tiny real engines (single-request, batched, chunked) across policies and
asserts ``device_bytes == capacity * bytes_per_expert`` end-to-end, exiting
nonzero on violation (the CI bench-smoke job).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (POLICIES, build_artifacts, emit_bench_json,
                               replay)
from repro.configs.paper_models import PAPER_MODELS, QUANT_BYTES
from repro.core.simulator import ModelCosts


def gpu_only_bytes(model: str) -> float:
    cfg = PAPER_MODELS[model]
    q = QUANT_BYTES[model]
    costs = ModelCosts(cfg, quant_bytes=q)
    experts = cfg.n_layers * cfg.n_experts * costs.expert_bytes
    return experts + costs.nonexpert_resident_bytes()


def run(models=("mixtral-8x7b", "mixtral-8x22b", "qwen3-30b-a3b",
                "deepseekmoe-16b"), quick=False):
    rows = []
    for m in models:
        art = build_artifacts(m, "squad")
        for pol in POLICIES:
            sims = replay(art, pol)
            peak = float(np.max([s.peak_bytes for s in sims]))
            rows.append((f"memory/{m}/{pol}", peak / 1e6,
                         f"peak_gb={peak / 1e9:.2f}"))
        rows.append((f"memory/{m}/gpu_only", gpu_only_bytes(m) / 1e6,
                     f"peak_gb={gpu_only_bytes(m) / 1e9:.2f}"))
    return rows


def smoke() -> None:
    """Assert the expert-HBM bound on REAL engines with a tiny config."""
    import jax
    from repro.configs.base import get_config, reduced
    from repro.core.tracer import ExpertsTracer
    from repro.models.model import build
    from repro.serving.batching import BatchedServingEngine
    from repro.serving.engine import MoEServingEngine

    cfg = reduced(get_config("mixtral_8x7b"))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (10, 7)]
    tracer = ExpertsTracer(cfg.n_layers, cfg.n_experts, cfg.top_k)
    for _ in range(6):
        tracer.add_path(np.stack([
            rng.choice(cfg.n_experts, cfg.top_k, replace=False)
            for _ in range(cfg.n_layers)]))
    stats = tracer.stats()

    def check(tag, res):
        bound = res.capacity * res.bytes_per_expert
        ok = (res.device_bytes == res.pool_capacity * res.bytes_per_expert
              and res.pool_capacity == res.capacity
              and res.regrow_events == 0
              and set(res.slot_of) == set(res.resident))
        print(f"memory-smoke/{tag}: expert_hbm={res.device_bytes}B "
              f"bound={bound}B resident={len(res.resident)}"
              f"/{res.capacity} {'OK' if ok else 'VIOLATED'}")
        assert ok, f"{tag}: expert-HBM bound violated"

    record = {}
    for pol in ("odf", "lfp", "mif", "duo"):
        eng = MoEServingEngine(cfg, params, policy=pol, stats=stats,
                               temperature=0.0)
        for p in prompts:
            eng.serve(p, max_new=2)
        check(f"single/{pol}", eng.cache)

        beng = BatchedServingEngine(cfg, params, policy=pol, stats=stats,
                                    max_batch=2, max_seq=24,
                                    temperature=0.0, prefill_budget=3)
        for p in prompts:
            beng.submit(p, max_new=2)
        beng.run_until_drained()
        check(f"batched-chunked/{pol}", beng.cache)
        record[pol] = {"expert_hbm_bytes": int(beng.cache.device_bytes),
                       "bound_bytes": int(beng.cache.capacity
                                          * beng.cache.bytes_per_expert),
                       "regrow_events": int(beng.cache.regrow_events)}
    emit_bench_json("memory", record)
    print("bench_memory smoke OK: expert HBM bounded by "
          "capacity x bytes_per_expert for every policy and path")


if __name__ == "__main__":
    import argparse

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny real-engine run asserting the expert-HBM "
                         "bound (CI)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        for name, us, derived in run():
            print(f"{name},{us:.1f},{derived}")
