"""Fig. 7 reproduction: total throughput (tokens/s) vs batch size 1..12.

Batching model (paper §VI-B): per decode step the batch activates the UNION
of each request's routed experts per layer — densified activation — and each
expert processes all its assigned tokens. We merge B eval-request traces per
step/layer and replay through each policy."""
from __future__ import annotations

import numpy as np

from benchmarks.common import POLICIES, build_artifacts
from repro.configs.paper_models import QUANT_BYTES
from repro.core.scheduler import make_scheduler
from repro.core.simulator import HW, ModelCosts, StreamSim, _op_time, \
    _xfer_time
from repro.core.state import StateConstructor


def merged_step_experts(results, batch: int, step: int, layer: int):
    sel = []
    for r in results[:batch]:
        if step < r.decode_trace.shape[0]:
            sel.extend(int(e) for e in r.decode_trace[step, layer])
    return sorted(set(sel)), len(sel)


def simulate_batched(art, policy: str, batch: int, hw: HW, seq_len=512,
                     steps=10):
    cfg = art.cfg_full
    costs = ModelCosts(cfg, quant_bytes=QUANT_BYTES[art.model])
    sched = make_scheduler(policy, cfg.n_layers, cfg.n_experts, cfg.top_k,
                           int(costs.expert_bytes), stats=art.stats,
                           predictor=art.predictor,
                           state_constructor=StateConstructor(art.stats))
    sched.begin_request()
    results = art.eval_results[policy]
    # cycle eval requests to fill the batch
    results = (results * ((batch // len(results)) + 1))[:batch]
    sim = StreamSim()
    t_fx = _xfer_time(costs.expert_bytes, hw)
    done = 0.0
    total_tokens = 0
    # prefill (all B prompts; union per layer)
    for l in range(cfg.n_layers):
        active = sorted({e for r in results for e in r.prefill_active[l]})
        plan = sched.prefill_plan(l, active)
        t_attn = _op_time(costs.nonmoe_flops(seq_len * batch, seq_len),
                          costs.nonmoe_bytes_per_layer, hw)
        attn_end = sim.issue("comp", t_attn, [done])
        fx_end = attn_end if not plan.overlap_first else done
        for e in plan.fetches:
            fx_end = sim.issue("comm", t_fx, [fx_end])
        tok_e = max(batch * seq_len * cfg.top_k // max(len(active), 1), 1)
        cend = max(attn_end, fx_end if plan.prefetch_all_first else attn_end)
        for i, e in enumerate(plan.order):
            dep = [cend] if plan.prefetch_all_first else [max(cend, fx_end)]
            cend = sim.issue("comp",
                             _op_time(costs.expert_flops(tok_e),
                                      costs.expert_bytes, hw), dep)
        sched.end_layer(l)
        done = cend
    total_tokens += batch
    ttft = done

    from repro.core.scheduler import DuoServeScheduler
    for t in range(steps):
        if isinstance(sched, DuoServeScheduler):
            sched.begin_decode_step()
        for l in range(cfg.n_layers):
            union, n_assign = merged_step_experts(results, batch, t, l)
            if not union:
                continue
            t_attn = _op_time(costs.nonmoe_flops(batch, seq_len + t),
                              costs.nonmoe_bytes_per_layer
                              + batch * costs.kv_bytes(seq_len + t), hw)
            attn_end = sim.issue("comp", t_attn, [done])
            plan = sched.decode_plan(l, union)
            miss_end = attn_end
            for e in plan.misses:
                miss_end = sim.issue("comm", t_fx, [miss_end])
            cend = max(attn_end, miss_end)
            tok_e = max(n_assign // max(len(union), 1), 1)
            for e in plan.hits + plan.misses:
                cend = sim.issue("comp",
                                 _op_time(costs.expert_flops(tok_e),
                                          costs.expert_bytes, hw), [cend])
            if plan.prefetch_next:
                pdep = [attn_end]
                if sched.uses_predictor:
                    pdep = [sim.issue("pred", hw.pred_lat, [attn_end])]
                for e in plan.prefetch_next:
                    sim.issue("comm", t_fx, pdep)
            done = cend
        total_tokens += batch
    return total_tokens / done, ttft


def run(models=("mixtral-8x7b", "mixtral-8x22b", "qwen3-30b-a3b",
                "deepseekmoe-16b"), batches=(1, 2, 4, 8, 12), quick=False):
    hw = HW()
    rows = []
    if quick:
        models = models[:1]
        batches = (1, 4)
    for m in models:
        art = build_artifacts(m, "squad")
        for b in batches:
            for pol in POLICIES:
                tput, ttft = simulate_batched(art, pol, b, hw)
                rows.append((f"throughput/{m}/b{b}/{pol}", 1e6 / tput,
                             f"tokens_per_s={tput:.2f},ttft={ttft:.3f}s"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
