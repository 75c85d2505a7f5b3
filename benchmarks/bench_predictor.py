"""Table III reproduction: expert-prediction accuracy — DuoServe's ExpertMLP
vs the MIF trace-prior — per (model, dataset). Metrics: Top-k (all routed
experts predicted) and At-Least-Half, measured on the held-out eval traces'
actual decode steps."""
from __future__ import annotations

import numpy as np

from benchmarks.common import DATASETS, build_artifacts
from repro.core.predictor import accuracy_metrics
from repro.core.state import StateConstructor


def eval_on_traces(art):
    """Per decode step: DuoServe predicts layer l from the step's own path
    prefix (cleared per step, as in the runtime); MIF 'predicts' the top-k
    popular experts."""
    sc = StateConstructor(art.stats)
    E, k = art.cfg_trace.n_experts, art.cfg_trace.top_k
    X, Y = [], []
    for r in art.eval_results["odf"]:
        for t in range(r.decode_trace.shape[0]):
            prefix = []
            for l in range(r.decode_trace.shape[1]):
                if l >= 1:
                    X.append(sc.features(prefix, l))
                    y = np.zeros(E, np.float32)
                    y[r.decode_trace[t, l]] = 1.0
                    Y.append(y)
                prefix.append(r.decode_trace[t, l])
    X, Y = np.stack(X), np.stack(Y)
    duo_logits = art.predictor.predict_logits(X)
    duo = accuracy_metrics(duo_logits, Y, k)
    # MIF prior: layer popularity (constant per layer)
    n_layers = art.cfg_trace.n_layers
    pop_logits = np.zeros_like(duo_logits)
    i = 0
    for _ in range(len(X) // (n_layers - 1)):
        for l in range(1, n_layers):
            pop_logits[i] = art.stats.popularity[l]
            i += 1
    mif = accuracy_metrics(pop_logits, Y, k)
    return duo, mif


def run(models=("mixtral-8x7b", "mixtral-8x22b", "qwen3-30b-a3b",
                "deepseekmoe-16b"), datasets=DATASETS, quick=False):
    rows = []
    for m in models:
        for d in datasets:
            art = build_artifacts(m, d)
            (duo_k, duo_h), (mif_k, mif_h) = eval_on_traces(art)
            rows.append((f"predictor/{m}/{d}", 0.0,
                         f"duo_topk={duo_k:.3f},duo_half={duo_h:.3f},"
                         f"mif_topk={mif_k:.3f},mif_half={mif_h:.3f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
