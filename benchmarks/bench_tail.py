"""Fig. 6 reproduction: P50/P95 end-to-end tail latency, representative
models (Mixtral-8x7B, Qwen3-30B-A3B) on the SQuAD-like workload."""
from __future__ import annotations

import numpy as np

from benchmarks.common import POLICIES, build_artifacts, replay
from repro.core.qos import summarize


def run(models=("mixtral-8x7b", "qwen3-30b-a3b"), quick=False):
    rows = []
    for m in models:
        art = build_artifacts(m, "squad")
        for pol in POLICIES:
            sims = replay(art, pol)
            q = summarize([s.ttft for s in sims], [s.e2e for s in sims],
                          total_tokens=sum(len(s.step_latencies)
                                           for s in sims))
            rows.append((f"tail/{m}/squad/{pol}",
                         q.p50_e2e * 1e6,
                         f"p50={q.p50_e2e:.3f}s,p95={q.p95_e2e:.3f}s,"
                         f"p99={q.p99_e2e:.3f}s"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
