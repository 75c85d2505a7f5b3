"""One run of one cell: set up the served path, drive its window through
``ServingFrontend``, measure from the client side, check the served tokens
against the plain reference, and print the result line.

Set-up (``setup_s``, from process start to the window's opening):
  weights    every weight made on the device from the configuration's
             weight seed; routed experts copied to host RAM (``weights``);
  predictor  stats and the ExpertMLP from ``repro.launch.serve
             .build_predictor`` on fixed trace prompts, pickled on a
             checkout's first run beside the compile cache and loaded after;
  engine     ``BatchedServingEngine`` (duo, temperature 0, chunked prefill
             under the mix's budget, rr fairness, grouped decode) behind a
             ``ServingFrontend``;
  warmup     one request per prefill chunk size the mix can make (the
             multiples of its prompt quantum up to the budget), then
             ``max_batch`` short requests together, and the grouped decode
             FFN at every shape a decode step can take.

The window lasts ``--seconds``. Each client submits its next request when
its last one finishes (closed loop); every token is stamped when the poll
that delivered it returns.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import os
import pickle
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import check, spec, traffic as T, weights as W

GIB = 1 << 30


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_start_wall() -> float:
    """Wall time at which this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """Compile-cache hits and misses, and the host seconds JAX spends
    tracing, lowering and compiling (a compile that the persistent cache
    answers is a load: ``backend_compile`` then includes
    ``cache_retrieval``), each with the host time it was recorded."""

    DURATIONS = {"jaxpr_trace_duration": "trace",
                 "jaxpr_to_mlir_module_duration": "lower",
                 "backend_compile_duration": "backend_compile",
                 "cache_retrieval_time_sec": "cache_retrieval"}

    def __init__(self):
        import jax
        self.events: List[tuple] = []

        def on_event(event, **_):
            name = event.rsplit("/", 1)[-1]
            if name in ("cache_hits", "cache_misses"):
                self.events.append((name, time.perf_counter(), 0.0))

        def on_duration(event, duration, **_):
            name = self.DURATIONS.get(event.rsplit("/", 1)[-1])
            if name:
                self.events.append((name, time.perf_counter(), duration))

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def count(self, name: str, t0: float = 0.0, t1: float = float("inf")):
        return sum(1 for n, t, _ in self.events if n == name and t0 <= t < t1)

    def seconds(self, name: str, t0: float = 0.0, t1: float = float("inf")):
        return sum(d for n, t, d in self.events if n == name and t0 <= t < t1)

    def summary(self, t0: float = 0.0, t1: float = float("inf")) -> str:
        return (f"{self.count('trace', t0, t1)} traced, "
                f"{self.count('cache_hits', t0, t1)} cache hits, "
                f"{self.count('cache_misses', t0, t1)} misses; "
                + ", ".join(f"{n} {self.seconds(n, t0, t1):.2f} s"
                            for n in self.DURATIONS.values()))


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache`` (a fixed path)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def src_hash() -> str:
    """Hash of the program's source (the imported ``repro`` package)."""
    import repro
    h = hashlib.sha256()
    src = Path(list(repro.__path__)[0]).resolve()
    for p in sorted(src.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(src)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()[:16]


def predictor_for(root: Path, cell, cfg, params, store, phases) -> tuple:
    """(stats, predictor): loaded from the checkout's artefact, or built
    from fixed trace prompts and saved there on the first run."""
    import jax
    import jax.numpy as jnp
    from repro.launch import serve as S

    pc = cell.config["predictor"]
    d = cell.dims
    path = (root / ".bench_cache" / "predictor"
            / f"{cell.config_name}-w{d.weight_seed}-{src_hash()}.pkl")
    if path.exists():
        with open(path, "rb") as f:
            stats, pred = pickle.load(f)
        phases["predictor_source"] = "loaded"
    else:
        prompts = [T.fixed_prompt(d.vocab, pc["trace_prompt_tokens"],
                                  pc["trace_seed"] * 1000 + i)
                   for i in range(pc["trace_prompts"])]
        stats, pred = S.build_predictor(cfg, params, store, "duo", prompts,
                                        max_new=pc["trace_max_new"])
        pred = dataclasses.replace(pred, params=jax.device_get(pred.params),
                                   bn_state=jax.device_get(pred.bn_state))
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        with open(tmp, "wb") as f:
            pickle.dump((stats, pred), f)
        os.replace(tmp, path)
        phases["predictor_source"] = "built"
    pred = dataclasses.replace(
        pred, params=jax.tree.map(jnp.asarray, pred.params),
        bn_state=jax.tree.map(jnp.asarray, pred.bn_state))
    return stats, pred


@dataclasses.dataclass
class Rec:
    """One request as the client sees it."""
    client: int
    prompt: np.ndarray
    handle: object
    t_submit: float
    times: List[float] = dataclasses.field(default_factory=list)


class Clients:
    """The closed-loop clients of one mix over one frontend."""

    def __init__(self, fe, mix_traffic: T.Traffic):
        self.fe = fe
        self.traffic = mix_traffic
        self.next_index = [0] * mix_traffic.clients
        self.active: List[Rec] = []
        self.done: List[Rec] = []

    def submit(self, client: int) -> Rec:
        import jax
        from repro.serving.api import GenerationRequest, SamplingParams

        req = self.traffic.request(client, self.next_index[client])
        self.next_index[client] += 1
        spec_ = GenerationRequest(prompt=req.prompt, params=SamplingParams(
            max_new_tokens=req.out_tokens - 1, temperature=0.0))
        with jax.profiler.TraceAnnotation("bench.submit"):
            t = time.perf_counter()
            h = self.fe.submit(spec_)
        rec = Rec(client, req.prompt, h, t)
        self.active.append(rec)
        return rec

    def poll(self, resubmit_before: Optional[float] = None) -> None:
        """One frontend poll; stamps what it delivered. A finished
        request's client submits its next one if ``resubmit_before`` is
        later than now."""
        import jax
        with jax.profiler.TraceAnnotation("bench.poll"):
            self.fe.poll()
        t = time.perf_counter()
        for rec in list(self.active):
            new = len(rec.handle.tokens) - len(rec.times)
            rec.times.extend([t] * new)
            if rec.handle.done:
                self.active.remove(rec)
                self.done.append(rec)
                if resubmit_before is not None and t < resubmit_before:
                    self.submit(rec.client)


def serve_alone(fe, prompts, max_new: List[int]) -> None:
    """Submit ``prompts`` together and poll until the frontend is idle."""
    from repro.serving.api import GenerationRequest, SamplingParams
    for p, m in zip(prompts, max_new):
        fe.submit(GenerationRequest(prompt=p, params=SamplingParams(
            max_new_tokens=m, temperature=0.0)))
    fe.drain()


def warm_up(fe, mix: dict, vocab: int) -> None:
    """Every shape the mix's window can use, with the fewest tokens."""
    q = mix["prompt_tokens"]["quantum"]
    budget = mix["prefill_budget"]
    for c in range(budget, 0, -q):            # each prefill chunk size
        serve_alone(fe, [T.fixed_prompt(vocab, c, 7_000 + c)], [1])
    b = mix["max_batch"]                      # each decode batch size
    steps = mix.get("warmup_decode_steps", 1)
    serve_alone(fe, [T.fixed_prompt(vocab, q, 8_000 + j) for j in range(b)],
                [steps + j for j in range(b)])
    warm_grouped_shapes(fe.engine, b)


def grouped_shapes(E: int, k: int, max_batch: int) -> List[tuple]:
    """(batch, distinct experts, rows per expert) of every grouped decode
    FFN launch a step of 1 to ``max_batch`` requests can make, as the
    engine's own ``group_by_expert`` pads them (its decode call's caps)."""
    from repro.serving.engine import group_by_expert
    out = set()
    for b in range(1, max_batch + 1):
        cap = min(E, b * k)
        for u in range(k, cap + 1):           # distinct experts of a layer
            for c in range(1, b + 1):         # rows of the fullest expert
                ids = np.tile(np.arange(k), (c, 1))
                disp = group_by_expert(ids, range(u), bucket_cap=b,
                                       u_bucket_cap=cap)
                out.add((b, *disp.row_idx.shape))
    return sorted(out)


def warm_grouped_shapes(eng, max_batch: int) -> None:
    """Compile the grouped decode FFN and its scatter-back gather at every
    shape of ``grouped_shapes``. Routing decides which shape a step meets,
    so traffic alone cannot reach them all before the window; this runs
    the engine's jitted program on zeros (slot 0 of its pools), moving no
    expert. The engine has no warm-up entry point (``PERF.md``)."""
    import jax.numpy as jnp
    w1, w3, w2 = eng.cache.pools
    for b, u, c in grouped_shapes(eng.E, eng.k, max_batch):
        xn = jnp.zeros((b, 1, eng.cfg.d_model), w1.dtype)
        raw = eng._grouped_raw(xn, jnp.zeros((u, c), jnp.int32),
                               w1, w3, w2, jnp.zeros(u, jnp.int32))
        idx = jnp.zeros(b, jnp.int32)
        raw[idx, idx].block_until_ready()


def counters(eng) -> Dict[str, float]:
    c = eng.cache
    return {"hits": c.hits, "misses": c.misses,
            "transfers": len(c.transfer_log),
            "prefilled": eng.prefilled_tokens,
            "prefill_ffn_launches": eng.perf.prefill_ffn_launches}


def percentile(xs: List[float], p: int) -> float:
    """The p-th percentile (Python's ``statistics.quantiles``, exclusive
    method; the one value itself for a single sample)."""
    if len(xs) == 1:
        return xs[0]
    return statistics.quantiles(xs, n=100)[p - 1]


def set_up(root: Path, cell, *, require_chip: bool = True,
           peaks: Optional[dict] = None,
           spans: bool = False) -> SimpleNamespace:
    """Device check, compile cache, weights, predictor, engine and warm-up:
    everything before a window. Raises ``NoChip`` off a TPU when
    ``require_chip``."""
    for k in [k for k in os.environ if k.startswith("REPRO_OPT_")]:
        del os.environ[k]                     # the default expert-FFN path
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {dev.platform} device(s) ({dev.device_kind})")
    if require_chip:
        peaks = spec.peaks_of(dev.device_kind, root)
    cache_dir = enable_compile_cache(root) if dev.platform != "cpu" else None
    clog = CompileLog()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; compile "
        f"cache {cache_dir}")

    from repro.core.cache import HostExpertStore
    from repro.models.model import init_without_experts
    from repro.serving.batching import BatchedServingEngine
    from repro.serving.frontend import ServingFrontend

    d, mix = cell.dims, cell.traffic
    cfg = spec.arch_config(d, cell.config.get("source", ""))
    phases: Dict[str, float] = {}
    t = time.perf_counter()
    dense = W.dense(d)
    expected = jax.eval_shape(init_without_experts(cfg),
                              jax.random.PRNGKey(0))
    params = jax.block_until_ready(W.program_params(d, dense, expected))
    del dense
    store = HostExpertStore(W.host_experts(d))
    phases["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    stats, pred = predictor_for(root, cell, cfg, params, store, phases)
    phases["predictor"] = time.perf_counter() - t

    t = time.perf_counter()
    eng = BatchedServingEngine(
        cfg, params, policy="duo", store=store, stats=stats, predictor=pred,
        max_batch=mix["max_batch"], max_seq=spec.max_seq_of(mix),
        prefill_budget=mix["prefill_budget"],
        prefill_fairness=mix["prefill_fairness"],
        cache_capacity=cell.config["deployment"]["expert_slots"],
        temperature=0.0, grouped_decode=True, spans=spans)
    fe = ServingFrontend(eng)
    phases["engine"] = time.perf_counter() - t

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        warm_up(fe, mix, d.vocab)
    phases["warmup"] = time.perf_counter() - t
    return SimpleNamespace(cell=cell, dims=d, mix=mix, dev=dev, devs=devs,
                           peaks=peaks, clog=clog, phases=phases, fe=fe,
                           eng=eng, bytes_per_expert=store.bytes_per_expert)


@dataclasses.dataclass
class Window:
    """What the clients saw in one measured window, and the engine's
    counters at its open and close."""
    recs: List[Rec]
    t_open: float                 # host perf_counter
    t_close: float
    wall_open: float              # host wall clock at the open
    c_open: Dict[str, float]
    c_close: Dict[str, float]


def run_window(su: SimpleNamespace, seed: int, seconds: float) -> Window:
    """The measured window: the mix's clients from ``seed`` submit their
    first requests at the open, and each its next one when its last
    finishes, until ``seconds`` have passed."""
    import jax
    clients = Clients(su.fe, T.Traffic(su.mix, su.dims.vocab, seed))
    c_open = counters(su.eng)
    with jax.profiler.TraceAnnotation("bench.window"):
        wall_open = time.time()
        t_open = time.perf_counter()
        t_close = t_open + seconds
        for c in range(clients.traffic.clients):
            clients.submit(c)
        while time.perf_counter() < t_close:
            clients.poll(resubmit_before=t_close)
    return Window(clients.done + clients.active, t_open, t_close, wall_open,
                  c_open, counters(su.eng))


def served_sample(win: Window, mix: dict, seed: int) -> list:
    """Once the window has closed: the requests still in flight cancelled,
    and the sample of (prompt, served tokens) that the check compares."""
    for r in win.recs:
        if not r.handle.done:
            r.handle.cancel()
    return check.sample([(r.prompt, list(r.handle.tokens)) for r in win.recs],
                        mix["check_requests"], seed)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             start_wall: Optional[float] = None, cell=None,
             peaks: Optional[dict] = None) -> dict:
    """One run; returns the result object the last line prints.

    Tests drive it off the chip with ``require_chip=False``, their own
    ``cell`` and ``peaks``."""
    start_wall = process_start_wall() if start_wall is None else start_wall
    cell = cell or spec.load_cell(root, workload)
    su = set_up(root, cell, require_chip=require_chip, peaks=peaks,
                spans=trace)
    import jax
    d, mix, dev, devs, clog, phases = (su.dims, su.mix, su.dev, su.devs,
                                       su.clog, su.phases)
    eng, peaks = su.eng, su.peaks
    setup_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1   # user annotations; device events unaffected
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = run_window(su, seed, seconds)
    if trace:
        jax.profiler.stop_trace()
    setup_s = win.wall_open - start_wall
    t_open, t_close = win.t_open, win.t_close
    stats_mem = dev.memory_stats() or {}
    peak = stats_mem.get("peak_bytes_in_use")
    log_lines = [f"phase {k}: {v:.3f} s" for k, v in phases.items()
                 if isinstance(v, float)]
    log_lines.append(f"predictor: {phases.get('predictor_source')}")
    log_lines.append(f"window: perf_counter {t_open:.3f} to {t_close:.3f}")
    log_lines.append(f"set-up compiles: {clog.summary(0.0, t_open)}")
    log_lines.append(f"window compiles: {clog.summary(t_open, t_close)}")
    if peak is not None and setup_peak is not None and peak <= setup_peak:
        log_lines.append(f"note: the device's peak ({peak} bytes) was "
                         f"reached in set-up, before the window")

    recs = win.recs
    in_window = [r for r in recs if r.t_submit < t_close]
    ttft = [r.times[0] - r.t_submit for r in recs
            if r.times and r.times[0] < t_close]
    gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
            if b < t_close]
    out_times = [t for r in recs for t in r.times if t < t_close]
    prefill_context = (sum(len(r.prompt) ** 2 / 2 for r in recs)
                       / max(1, sum(len(r.prompt) for r in recs)))
    transfers = sum(1 for _, tt in eng.cache.transfer_log
                    if t_open <= tt < t_close)
    attempted = len(in_window)
    failed = sum(1 for r in in_window
                 if r.handle.finish_reason in ("rejected", "slo_shed"))

    e2e = {
        "ttft_p50_s": (statistics.median(ttft), "s") if ttft else None,
        "tbt_p95_s": (percentile(gaps, 95), "s") if gaps else None,
        "tokens_per_s": (len(out_times) / seconds, "tokens/s"),
        "hbm_peak_gib": (peak / GIB, "GiB") if peak is not None else None,
        "setup_s": (setup_s, "s"),
    }
    log_lines.append("requests (client, prompt tokens, first token s): "
                     + json.dumps([[r.client, len(r.prompt),
                                    round(r.times[0] - r.t_submit, 4)
                                    if r.times else None] for r in recs]))
    log_lines.append(
        f"window: {seconds} s, {attempted} requests, {len(ttft)} first "
        f"tokens, {len(gaps)} gaps, {len(out_times)} tokens, {transfers} "
        f"expert transfers; set-up peak {setup_peak} bytes, run peak "
        f"{peak} bytes")

    # what the sample compares, then the program's state goes
    served = served_sample(win, mix, seed)
    ctx = SimpleNamespace(
        cell=cell.name, dims=d, peaks=peaks, window_s=float(seconds),
        open=win.c_open, close=win.c_close, transfers=transfers,
        bytes_per_expert=su.bytes_per_expert, out_tokens=len(out_times),
        prefill_context=prefill_context, trace=None)
    spans = ([(s.name, s.t0, s.t1) for s in eng.obs.spans()]
             if trace else [])
    del su, eng, win, recs, in_window
    gc.collect()

    t = time.perf_counter()
    cmp = check.compare(d, served, check.load_limits(spec.bench_dir(root),
                                                      cell.name))
    log_lines.append(f"check: {cmp.requests} requests, {cmp.tokens} served "
                     f"tokens compared with the reference in "
                     f"{time.perf_counter() - t:.2f} s: "
                     + json.dumps(cmp.readings()))

    result = {"correct": cmp.correct,
              "attempted": attempted, "failed": failed}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        from benchmarks.chip import trace as TR
        t = time.perf_counter()
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        summary = TR.reduce(str(path), host_spans=_on_trace_clock(
            spans, path, t_open))
        ctx.trace = summary
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops(),
                               "idle_gaps": [[n, s] for n, s in summary.gaps]}
        log_lines.append(f"trace: {path.stat().st_size} bytes, reduced in "
                         f"{time.perf_counter() - t:.2f} s")
        _rmtree(trace_dir)
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v[0]), "unit": v[1]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = cmp.checks()
    for line in log_lines:
        log(line)
    for k, v in cmp.checks().items():
        log(f"{k} {v['value']!r} limit {v['limit']!r}")
    return result


def _on_trace_clock(spans, xplane: Path, t_open: float):
    """The engine's spans (host ``perf_counter`` seconds) on the trace's
    nanosecond clock, anchored at the ``bench.window`` annotation that
    opened at ``t_open``."""
    if not spans:
        return []
    import jax
    data = jax.profiler.ProfileData.from_file(str(xplane))
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench.window":
                    off = int(ev.start_ns) - int(t_open * 1e9)
                    return [(n, int(a * 1e9) + off, int(b * 1e9) + off)
                            for n, a, b in spans]
    return []


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def main(argv=None, root: Optional[Path] = None) -> int:
    import argparse
    start_wall = process_start_wall()
    ap = argparse.ArgumentParser(description="One run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or Path(__file__).resolve().parents[2]
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), start_wall=start_wall)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0
