"""Serving launcher: DuoServe-MoE through ``ServingFrontend`` over one
continuous-batching ``BatchedServingEngine``.

By default the architecture runs at its published widths with only its
depth cut (``--layers``, default: the whole layers whose experts host RAM
holds). Routed experts are generated from the seed on the host, into one
``HostExpertStore`` that every engine of the process shares, and stream
host->HBM into the residency pools; only the non-expert weights are built
on the device. ``--reduced`` serves the CPU-sized variant
(``configs.base.reduced``) instead.

Phases, each timed (one run's wall time, not a benchmark):
  init       non-expert params on the device, expert store on the host;
  trace      stats and the decode predictor, built offline: an ODF engine
             serves a few prompts, the ExpertMLP trains on their routing
             paths, and that engine is dropped before the serving engine is
             built, so the device never holds two expert pools;
  warmup     the serving engine compiles its shapes on a few short requests;
  serve      the requests, all in flight at once (chunked prefill
             interleaved with batched decode).

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --layers 4
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced \
      --prompt-len 16,32 --prefill-budget 8
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.configs.base import ArchConfig, get_config, reduced
from repro.core.cache import HostExpertStore
from repro.core.predictor import train_predictor
from repro.core.state import StateConstructor
from repro.data.pipeline import PromptWorkload, squad_like
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_without_experts
from repro.serving.api import GenerationRequest, SamplingParams
from repro.serving.batching import BatchedServingEngine
from repro.serving.engine import collect_traces
from repro.serving.frontend import ServingFrontend

HOST_RESERVE_BYTES = 8 << 30   # JAX runtime, compiles, transfer staging


def mem_available_bytes() -> int:
    """Host RAM available to new allocations (``MemAvailable``)."""
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("/proc/meminfo has no MemAvailable line")


def expert_layer_bytes(cfg) -> int:
    """Host bytes of one layer's routed experts (bf16 w1, w3, w2)."""
    return cfg.n_experts * 3 * cfg.d_model * cfg.d_expert * 2


def choose_depth(cfg, mem_available: int, *, max_layers: Optional[int] = None,
                 reserve: int = HOST_RESERVE_BYTES) -> int:
    """Whole layers whose experts fit in ``mem_available - reserve`` bytes
    of host RAM, capped at ``max_layers`` and at the published depth."""
    fit = (mem_available - reserve) // expert_layer_bytes(cfg)
    depth = int(min(cfg.n_layers, fit, max_layers or cfg.n_layers))
    if depth < 2:
        raise RuntimeError(
            f"{mem_available / 2**30:.1f} GiB of host RAM holds {fit} layers "
            f"of {cfg.name} experts; the serving path needs at least 2")
    return depth


def serving_config(arch: str, *, reduced_widths: bool, layers: Optional[int]):
    """(config, cuts): the published config, or its reduced variant, with
    depth cut to ``layers``; ``cuts`` names every key changed from the
    published config."""
    pub = get_config(arch)
    cfg = reduced(pub) if reduced_widths else pub
    if layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    cuts = [f"{f.name}: {getattr(pub, f.name)} -> {getattr(cfg, f.name)}"
            for f in dataclasses.fields(pub)
            if f.name != "name" and getattr(pub, f.name) != getattr(cfg, f.name)]
    return cfg, cuts


def serving_weights(cfg, seed: int = 0):
    """(device params without routed experts, host expert store)."""
    params = init_without_experts(cfg)(jax.random.PRNGKey(seed))
    store = HostExpertStore.from_seed(cfg.n_layers, cfg.n_experts,
                                      cfg.d_model, cfg.d_expert, seed=seed)
    return jax.block_until_ready(params), store


def workload(cfg, lengths: Sequence[int], seed: int) -> List[np.ndarray]:
    """One SQuAD-like topic-cluster prompt per entry of ``lengths``."""
    top = max(lengths)
    spec = dataclasses.replace(squad_like(cfg.vocab), prompt_len=(top, top))
    return [p[:n] for (p, _), n in
            zip(PromptWorkload(spec, seed).prompts(len(lengths)), lengths)]


def build_predictor(cfg, params, store, policy: str,
                    prompts: Sequence[np.ndarray], max_new: int):
    """(stats, predictor) for ``policy``, from an offline ODF trace of
    ``prompts``. The trace engine is collected before this returns."""
    if policy not in ("mif", "duo", "duo+"):
        return None, None
    tracer, _ = collect_traces(cfg, params, prompts, max_new=max_new,
                               store=store)
    gc.collect()   # the engine's metric gauges hold it in a cycle
    stats = tracer.stats()
    if policy == "mif":
        return stats, None
    X, Y = StateConstructor(stats).build_dataset(tracer.as_array())
    predictor, _ = train_predictor(jax.random.PRNGKey(1), X, Y, cfg.top_k,
                                   epochs=5, batch=32)
    return stats, predictor


def hbm_plan(cfg, params, *, cache_capacity: int, max_batch: int,
             max_seq: int) -> Dict[str, int]:
    """Device bytes the serving engine holds or makes at its peak: params,
    the expert pools, the grouped FFN's gathered weights (one slab per
    distinct expert of a decode step) and the KV slots with each
    request's prefill carry."""
    expert = expert_layer_bytes(cfg) // cfg.n_experts
    kv = 2 * cfg.n_layers * max_seq * cfg.n_kv_heads * cfg.hd * 2
    plan = {
        "params": sum(a.nbytes for a in jax.tree.leaves(params)),
        "expert_pools": cache_capacity * expert,
        "grouped_gather": min(cfg.n_experts, max_batch * cfg.top_k) * expert,
        "kv": 2 * max_batch * kv,
    }
    plan["total"] = sum(plan.values())
    return plan


def serve(fe, prompts: Sequence[np.ndarray], max_new: int):
    """Submit every prompt at once and drive the frontend until idle;
    returns the request handles."""
    handles = [fe.submit(GenerationRequest(
        prompt=p, params=SamplingParams(max_new_tokens=max_new)))
        for p in prompts]
    fe.drain()
    return handles


@contextlib.contextmanager
def _phase(phase_s: Dict[str, float], name: str):
    """Time one phase into ``phase_s[name]`` and report it as it ends."""
    t = time.perf_counter()
    yield
    phase_s[name] = time.perf_counter() - t
    print(f"phase {name}: {phase_s[name]:.2f} s wall", flush=True)


@dataclasses.dataclass
class Launch:
    cfg: ArchConfig
    cuts: List[str]
    mem_available: int
    frontend: ServingFrontend
    prompts: List[np.ndarray]
    handles: list
    phase_s: Dict[str, float]
    hbm: Dict[str, int]


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", default="mixtral-8x7b")
    ap.add_argument("--policy", default="duo",
                    choices=["odf", "lfp", "mif", "duo", "duo+"])
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized widths (configs.base.reduced)")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth; default: the reduced depth, or at full "
                         "widths the whole layers host RAM holds, at most "
                         "--max-layers")
    ap.add_argument("--max-layers", type=int, default=None,
                    help="cap on the depth chosen from host RAM")
    ap.add_argument("--prompt-len", default="128,256,384,512",
                    help="comma list: one request per length")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prefill-budget", type=int, default=128,
                    help="prompt tokens of chunked prefill per engine step")
    ap.add_argument("--cache-capacity", type=int, default=None,
                    help="expert slots in HBM; default 2*top_k*max_batch "
                         "(the duo policy's need)")
    ap.add_argument("--trace-prompts", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def launch(args: argparse.Namespace) -> Launch:
    """Build the weights, predictor and serving frontend, warm it up, and
    serve one request per ``--prompt-len`` entry."""
    phase_s: Dict[str, float] = {}
    mem = mem_available_bytes()
    layers = args.layers
    if layers is None and not args.reduced:
        pub, _ = serving_config(args.arch, reduced_widths=False, layers=None)
        layers = choose_depth(pub, mem, max_layers=args.max_layers)
    cfg, cuts = serving_config(args.arch, reduced_widths=args.reduced,
                               layers=layers)
    lengths = [int(n) for n in args.prompt_len.split(",")]
    budget = args.prefill_budget
    max_seq = -(-(max(lengths) + args.max_new + 1) // 128) * 128
    cap = args.cache_capacity or 2 * cfg.top_k * args.max_batch

    with _phase(phase_s, "init"):
        params, store = serving_weights(cfg, args.seed)

    with _phase(phase_s, "trace"):
        trace = workload(cfg, [min(lengths)] * args.trace_prompts,
                         args.seed + 1)
        stats, predictor = build_predictor(cfg, params, store, args.policy,
                                           trace, max_new=args.max_new // 2)
        jax.block_until_ready(predictor.params if predictor else None)

    hbm = hbm_plan(cfg, params, cache_capacity=cap,
                   max_batch=args.max_batch, max_seq=max_seq)
    fe = ServingFrontend(BatchedServingEngine(
        cfg, params, policy=args.policy, store=store, stats=stats,
        predictor=predictor, max_batch=args.max_batch, max_seq=max_seq,
        prefill_budget=budget, cache_capacity=cap, temperature=0.0))
    with _phase(phase_s, "warmup"):
        # one-chunk prompts arriving together: decode batches of 1..max_batch
        serve(fe, workload(cfg, [budget] * args.max_batch, args.seed + 2),
              max_new=args.max_batch)

    prompts = workload(cfg, lengths, args.seed + 3)
    with _phase(phase_s, "serve"):
        handles = serve(fe, prompts, args.max_new)
    return Launch(cfg, cuts, mem, fe, prompts, handles, phase_s, hbm)


def main(argv=None) -> None:
    args = parser().parse_args(argv)
    enable_compile_cache()
    run = launch(args)
    eng = run.frontend.engine
    print(f"{run.cfg.name}: d_model={run.cfg.d_model} "
          f"d_expert={run.cfg.d_expert} E={run.cfg.n_experts} "
          f"k={run.cfg.top_k} layers={run.cfg.n_layers} policy={args.policy}")
    for i, h in enumerate(run.handles):
        print(f"req {i}: prompt={len(run.prompts[i])} tokens={h.tokens} "
              f"reason={h.finish_reason}")
    print(f"residency hits={eng.cache.hits} misses={eng.cache.misses} "
          f"transfers={len(eng.cache.transfer_log)}")
    print("phase wall s (one run): " + " ".join(
        f"{k}={v:.2f}" for k, v in run.phase_s.items()))


if __name__ == "__main__":
    main()
