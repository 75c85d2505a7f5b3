"""One-chip smoke of the serving path at published widths.

Serves mixtral-8x7b (d_model 4096, d_expert 14336, 8 experts, top-2, 32
query heads over 8 KV heads, head_dim 128, vocab 32000) with only its depth
cut, through the launcher's own code (``repro.launch.serve``):
``ServingFrontend`` -> ``BatchedServingEngine`` under the ``duo`` policy,
stats and decode predictor from an offline trace, routed experts in host
RAM streaming into HBM pools, chunked prefill interleaved with batched
decode. Weights are random, made from ``--seed``.

It fails at once unless JAX's first device is a TPU, and it prints its
result line, the last line of its output, only after every check passed:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

  python chip_smoke.py                 # depth from host RAM, capped
  python chip_smoke.py --layers 2
  REPRO_OPT_GROUPED_FFN=1 python chip_smoke.py   # Pallas expert FFN
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
MAX_LAYERS = 4   # the depth a run's time allows (phase times in CHANGES.md)


def main(argv=None) -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import serve as S
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX's first device is "
                         f"{dev.platform} ({dev.device_kind})")
    cache_dir = enable_compile_cache()
    counts = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        name = event.rsplit("/", 1)[-1]
        if name in ("cache_hits", "cache_misses"):
            counts[name[6:]] += 1

    jax.monitoring.register_event_listener(on_event)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    ap = S.parser()
    ap.set_defaults(arch="mixtral-8x7b", policy="duo", max_new=16,
                    max_batch=4, prompt_len="128,256,384,512",
                    prefill_budget=128, max_layers=MAX_LAYERS)
    args = ap.parse_args(argv)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)",
          flush=True)

    run = S.launch(args)
    cfg, fe = run.cfg, run.frontend
    eng = fe.engine
    print(f"config: {cfg.name} d_model={cfg.d_model} d_expert={cfg.d_expert} "
          f"experts={cfg.n_experts} top_k={cfg.top_k} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} head_dim={cfg.hd} vocab={cfg.vocab} "
          f"layers={cfg.n_layers}")
    print(f"host MemAvailable at start: {run.mem_available / 2**30:.2f} GiB "
          f"-> depth {cfg.n_layers} (expert layer "
          f"{S.expert_layer_bytes(cfg) / 2**30:.2f} GiB, at most "
          f"{args.max_layers} for run time)")
    print("cuts: " + ("; ".join(run.cuts) or "none"))
    print("HBM plan (bytes): " + " ".join(f"{k}={v}"
                                          for k, v in run.hbm.items()))
    print("phase wall s, one run, not a benchmark: " + " ".join(
        f"{k}={v:.2f}" for k, v in run.phase_s.items()))
    for i, h in enumerate(run.handles):
        print(f"request {i}: prompt {len(run.prompts[i])} tokens, got "
              f"{len(h.tokens)} tokens, finish={h.finish_reason}: {h.tokens}")

    # determinism: prompt 0 alone at temperature 0 on the same engine
    t = time.perf_counter()
    again = S.serve(fe, run.prompts[:1], args.max_new)[0]
    reserve_s = time.perf_counter() - t
    # last-position logits of prompt 0 through the engine's prefill
    logits, *_ = eng.prefill_layers(run.prompts[0][None])
    logits = np.asarray(logits, np.float32)

    # one expert slab host->HBM, synchronous, from host RAM as served
    slab = eng.store.get((0, 0))[0]
    h2d_s = []
    for _ in range(3):
        t = time.perf_counter()
        jax.device_put(slab).block_until_ready()
        h2d_s.append(time.perf_counter() - t)

    res = eng.cache
    stats = dev.memory_stats() or {}
    print(f"re-serve of request 0 alone: {reserve_s:.2f} s wall, "
          f"tokens equal: {again.tokens == run.handles[0].tokens}")
    print(f"prefill logits: shape {logits.shape}, finite "
          f"{bool(np.isfinite(logits).all())}, argmax "
          f"{int(logits[0, :cfg.vocab].argmax())} (request 0's first token "
          f"{run.handles[0].tokens[0]})")
    print(f"residency: hits={res.hits} misses={res.misses} host->HBM "
          f"transfers={len(res.transfer_log)} "
          f"residency_device_bytes={res.device_bytes} "
          f"hbm_bound_ok={res.hbm_bound_ok}")
    print(f"host->HBM of one {slab.nbytes}-byte slab, one run's wall time: "
          + ", ".join(f"{t:.4f} s ({slab.nbytes / t / 1e9:.2f} GB/s)"
                      for t in h2d_s))
    print(f"decode batch sizes seen: {sorted(set(eng.decode_batch_hist))}; "
          f"prefilled tokens {eng.prefilled_tokens}")
    print(f"device memory: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use')} bytes_limit="
          f"{stats.get('bytes_limit')}")
    print(f"compile cache: {counts['hits']} hits, {counts['misses']} misses")

    for i, h in enumerate(run.handles + [again]):
        if h.finish_reason != "length":
            raise AssertionError(f"request {i} finished {h.finish_reason}")
        if len(h.tokens) != args.max_new + 1:
            raise AssertionError(f"request {i}: {len(h.tokens)} tokens")
        if not all(0 <= t < cfg.vocab for t in h.tokens):
            raise AssertionError(f"request {i}: token outside the vocab")
    if again.tokens != run.handles[0].tokens:
        raise AssertionError("re-serving prompt 0 gave different tokens")
    if not np.isfinite(logits).all():
        raise AssertionError("non-finite logits")
    if not res.hbm_bound_ok:
        raise AssertionError("expert pools regrew past their capacity")
    if not res.transfer_log:
        raise AssertionError("no host->HBM expert transfer happened")
    if max(eng.decode_batch_hist) < 2:
        raise AssertionError("no batched decode step with B > 1")
    if max(len(p) for p in run.prompts) <= args.prefill_budget:
        raise AssertionError("no prompt needed more than one prefill chunk")
    if eng.prefilled_tokens < sum(len(p) for p in run.prompts):
        raise AssertionError("not every prompt token was prefilled")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
