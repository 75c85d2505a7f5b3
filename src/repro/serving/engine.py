"""DuoServe-MoE serving engine — the live runtime (paper §V).

Executes a MoE decoder layer-by-layer so the Python-level Expert Dispatcher
can interleave host->device expert transfers with dispatched computation:

  * prefill: per layer — attention dispatched, gate read back, tokens grouped
    by expert, then the policy's PrefillPlan drives the fetch/compute
    pipeline. With JAX async dispatch, issuing `device_put(expert e+1)` after
    dispatching `compute(expert e)` overlaps them (two-stream analogue).
    Prefill is also available incrementally (``prefill_chunk``): a
    token-budget chunk attends over the request's already-written KV prefix
    and appends its own K/V, carrying per-layer KV state across chunks —
    the unit of work the stall-free continuous-batching front-end
    (``serving/batching.py``) interleaves with batched decode. Chunked and
    monolithic prefill are bit-identical at any chunk size.
    ``prefill_chunks`` runs the chunks of several requests in one sweep:
    attention per request, the MoE once per layer over all their tokens, so
    each active expert is copied once per sweep rather than once per chunk.
  * decode: per layer — gate result compared against prefetched experts
    (sync point #1); misses corrected with a blocking fetch; the ExpertMLP is
    dispatched on the "prediction stream" (async) to choose layer l+1's
    prefetch while layer l's experts compute.

Routed-expert weights live ONLY in the HostExpertStore (host RAM); the device
holds non-MoE weights + ONE ``ExpertResidency`` (core/cache.py) — a single
CacheState ledger fused with fixed slot-pool expert buffers, shared by
reference with the scheduling policy. Exactly one ledger exists per engine:
the scheduler's plan-time admits/evicts/unpins ARE the device slot
allocations/frees, so expert HBM is bounded by ``capacity *
bytes_per_expert`` at every step (no silently growing device dict), and the
jitted expert kernels read weights by slot index straight out of the pools.
The engine records routing traces + cache events; the simulator replays them
with hardware constants to produce the paper's latency/memory tables.

Sparse grouped expert execution (ROADMAP item 3): both phases can run each
layer's whole expert sweep as ONE launch instead of one launch per expert.
``group_by_expert`` builds the dispatch host-side from the already-synced
gate result: each distinct expert's selecting rows are gathered into a
``[U, C, d]`` block (C bucketed to powers of two so the jitted kernel sees
O(log B) shapes; padding rows repeat row 0 and are never read back), the
per-expert ``cache.slot`` host syncs collapse into one vectorized slot pass,
and the sweep runs as a single grouped einsum with numerics IDENTICAL per
row to the dense ``expert_raw`` (same dtypes, same contraction) — or, under
``REPRO_OPT_GROUPED_FFN``, as the Pallas ``expert_ffn_from_pool`` streaming
kernel straight off the residency pools. Accumulation-order contract: the
decode scatter-back walks j = 0..k-1 gathering every row's j-th choice from
its group, so each row accumulates in its OWN top-k order; the fused prefill
scatter-back adds per-expert contributions in PLAN order with gate weights
folded in (non-selecting tokens contribute exact zeros, as in the dense
path). Both disciplines are therefore bit-exact vs the per-expert loops at
temperature 0 (tests/test_serving_batch.py, tests/test_perf_opts.py).

The module is split into:

  * ``EngineCore`` — the shared execution substrate (host store, device
    residency split, jitted per-layer kernels, one scheduler + residency
    pair) plus the EVENT SINK every front-end shares: generated tokens are
    emitted as ``TokenEvent`` records (serving/api.py) and drained by the
    caller — ``MoEServingEngine.serve()`` assembles its RequestResult from
    the stream, ``BatchedServingEngine.step()`` returns it as StepEvents,
    and the ``ServingFrontend`` routes it to live RequestHandles. Kernels
    are written batch-agnostic: every decode-side op is row-wise
    deterministic, so a [B,1,d] batched step reproduces B independent
    [1,1,d] steps bit-exactly (the invariant the continuous-batching
    front-end in ``serving/batching.py`` is built on).
  * ``MoEServingEngine`` — the paper-scope single-request engine. Its
    ``serve()`` takes a ``SamplingParams`` (temperature, max_new_tokens,
    stop-token early termination, seed); the legacy ``max_new=`` kwarg is
    compat sugar.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.cache import ExpertResidency, HostExpertStore
from repro.core.scheduler import (DuoServeScheduler,
                                  default_capacity, make_scheduler)
from repro.core.state import StateConstructor
from repro.core.tracer import ExpertsTracer, TraceStats
from repro.kernels.expert_ffn import expert_ffn_from_pool
from repro.kernels.ops import default_interpret
from repro.models import layers as L
from repro.models import moe_layer as M
from repro.models import opt_flags
from repro.models.layers import PDT
from repro.models.model import EXPERT_KEYS, attn_dims
from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import SpanRecorder
from repro.serving.api import Event, SamplingParams, TokenEvent

_PERF_FIELDS = ("decode_rows_dense", "decode_rows_grouped",
                "decode_rows_launched", "decode_ffn_launches",
                "decode_layers", "prefill_ffn_launches",
                "prefill_moe_layers", "prefill_passes",
                "prefill_pass_chunks")
_PERF_MAX_FIELD = "max_prefill_launches_per_layer"


class PerfCounters:
    """Measured expert-execution work, filled by the serving engines.

    ``rows`` are (token, expert) FFN row evaluations — the unit expert-FLOP
    cost scales with (6 * d_model * d_expert FLOPs per row, see
    benchmarks/roofline.expert_flops_per_row). ``decode_rows_dense`` is what
    the dense full-batch discipline costs (U distinct experts x all B rows
    per layer — counted on BOTH paths, so a grouped engine reports the
    redundancy it removed); ``decode_rows_grouped`` counts only each
    expert's selecting rows (sum of per-expert group sizes);
    ``decode_rows_launched`` is what the engine's FFN launches actually
    computed (grouped: after Cmax bucketing, padding included; dense:
    U * B). ``*_ffn_launches`` count expert-FFN kernel dispatches — the
    fused prefill path must keep prefill_ffn_launches == prefill_moe_layers
    (exactly one launch per layer visit). ``prefill_passes`` counts chunked
    prefill sweeps through the layers (``EngineCore.prefill_chunks`` calls)
    and ``prefill_pass_chunks`` the request chunks they carried: their
    ratio is how many requests share one expert sweep.

    Since the repro.obs migration this is a thin VIEW over the engine's
    :class:`MetricsRegistry`: every field reads a registry instrument
    (``engine_<field>_total`` counters; the max is a max-tracking gauge)
    and mutation goes through ``inc``/``max_update`` only — direct field
    writes raise here and are rejected statically by the
    ``obs-discipline`` lint (repro.analysis)."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        reg = registry if registry is not None else MetricsRegistry()
        object.__setattr__(self, "_c", {
            f: reg.counter(f"engine_{f}_total",
                           "expert-execution work (PerfCounters view)")
            for f in _PERF_FIELDS})
        object.__setattr__(self, "_gmax", reg.gauge(
            f"engine_{_PERF_MAX_FIELD}",
            "largest per-layer prefill FFN launch count seen"))

    def inc(self, field: str, n: int = 1) -> None:
        self._c[field].inc(n)

    def max_update(self, field: str, v: int) -> None:
        assert field == _PERF_MAX_FIELD, f"not a max-tracking field: {field}"
        self._gmax.max_update(v)

    def __getattr__(self, name: str):
        c = self.__dict__.get("_c", {})
        if name in c:
            return int(c[name].value)
        if name == _PERF_MAX_FIELD:
            return int(self.__dict__["_gmax"].value)
        raise AttributeError(name)

    def __setattr__(self, name: str, value) -> None:
        raise AttributeError(
            f"PerfCounters.{name} is a registry view — mutate via "
            f"inc()/max_update()")


def _bucket(n: int, cap: int) -> int:
    """Next power of two >= n, clamped to cap — the padded group capacity.
    Bucketing keeps the jitted grouped FFN at O(log cap) compiled shapes
    instead of one compilation per distinct max-group-size."""
    return min(1 << max(0, n - 1).bit_length(), cap)


@dataclasses.dataclass
class GroupedDispatch:
    """Host-side segment-gather plan for one layer's expert sweep."""
    row_idx: np.ndarray   # [U, C] int32 token index per expert (0-padded)
    counts: List[int]     # per-expert selecting-row counts (<= C each)
    u_of: np.ndarray      # [T, k] int32: group of each row's j-th choice
    c_of: np.ndarray      # [T, k] int32: row's position inside that group
    n_rows: int           # sum(counts) — real rows the sweep computes
    n_launched: int       # U * C — rows launched after bucketing


def group_by_expert(ids_np: np.ndarray, union: Sequence[int],
                    bucket_cap: int,
                    u_bucket_cap: Optional[int] = None) -> GroupedDispatch:
    """Build the capacity-grouped dispatch for a [T, k] selection matrix.

    ``union`` must cover every expert id appearing in ``ids_np`` (decode:
    plan.hits + plan.misses; prefill: plan.order) and fixes the group
    order. Rows are gathered per distinct expert in first-appearance order;
    ``u_of``/``c_of`` invert the gather so scatter-back can walk each row's
    own top-k choices (a row selecting the same expert under two choices
    maps both to the one gathered copy).

    ``u_bucket_cap`` additionally pads the GROUP dimension (distinct-expert
    count U) to a power of two clamped to the cap, the same discipline as
    the per-group capacity C: without it the jitted grouped sweep recompiles
    once per distinct U value. Padding groups are all-zero rows (they gather
    token 0, are computed, and are never scattered back — ``counts``,
    ``u_of`` and ``c_of`` only cover real groups, so bit-exactness is
    untouched). None keeps the exact U (callers that index groups
    positionally, e.g. the raw-kernel tests, rely on that)."""
    T, k = ids_np.shape
    einv = {int(e): u for u, e in enumerate(union)}
    groups: List[List[int]] = [[] for _ in union]
    u_of = np.zeros((T, k), np.int32)
    c_of = np.zeros((T, k), np.int32)
    pos: Dict[Tuple[int, int], int] = {}
    for t in range(T):
        for j in range(k):
            u = einv[int(ids_np[t, j])]
            c = pos.get((u, t))
            if c is None:
                g = groups[u]
                c = len(g)
                g.append(t)
                pos[(u, t)] = c
            u_of[t, j] = u
            c_of[t, j] = c
    counts = [len(g) for g in groups]
    C = _bucket(max(counts), bucket_cap) if counts else 1
    U_rows = max(len(union), 1)
    if u_bucket_cap is not None:
        U_rows = max(U_rows, _bucket(U_rows, u_bucket_cap))
    row_idx = np.zeros((U_rows, C), np.int32)
    for u, g in enumerate(groups):
        row_idx[u, : len(g)] = g
    return GroupedDispatch(row_idx=row_idx, counts=counts, u_of=u_of,
                           c_of=c_of, n_rows=sum(counts),
                           n_launched=int(row_idx.size))


@dataclasses.dataclass
class PrefillChunk:
    """One request's share of a prefill sweep (``EngineCore.prefill_chunks``):
    prompt tokens [1, C] at absolute positions start..start+C-1, and the
    request's own per-layer KV carry buffers ``kc``/``vc`` ([1, W, Hkv, hd]
    each) and slot positions ``sp`` [1, W], which the sweep advances in
    place."""
    tokens: np.ndarray
    start: int
    kc: List[jax.Array]
    vc: List[jax.Array]
    sp: jax.Array
    need_logits: bool = True


@dataclasses.dataclass
class RequestResult:
    tokens: np.ndarray              # generated token ids [T]
    prefill_active: List[List[int]]  # union of experts per layer
    decode_trace: np.ndarray        # [T, L, k]
    pred_trace: np.ndarray          # [T, L, k] DuoServe predictions (-1 pad)
    ttft_wall: float
    e2e_wall: float
    hits: int
    misses: int
    finish_reason: str = "length"   # length | stop_token | cancelled


class EngineCore:
    """Shared serving substrate for dense-family MoE configs.

    Owns the host expert store, the device-resident non-expert weights, the
    jitted per-layer kernels, and one scheduler + device expert cache pair.
    Subclasses add a request-execution discipline on top (single-request
    serve() here; continuous batching in serving/batching.py).
    """

    def __init__(self, cfg: ArchConfig, params, policy: str = "duo", *,
                 stats: Optional[TraceStats] = None, predictor=None,
                 cache_capacity: Optional[int] = None,
                 temperature: float = 0.8, sample_seed: int = 0,
                 sched_batch: int = 1, prefill_chunk: Optional[int] = None,
                 fused_prefill: Optional[bool] = None,
                 spans: Union[bool, SpanRecorder] = False,
                 store: Optional[HostExpertStore] = None):
        assert cfg.is_moe and cfg.family in ("moe", "dense"), \
            "engine schedules experts; use bundle.decode for non-MoE archs"
        assert cfg.n_dense_layers == 0, "engine assumes uniform MoE stack"
        self.cfg = cfg
        self.L = cfg.n_layers
        self.E = cfg.n_experts
        self.k = cfg.top_k
        self.vp = L.vocab_pad_of(cfg.vocab)

        lp = params["layers"]
        # routed experts live in host RAM: a caller-built store (shared by
        # every engine of the process, its params then carry no expert
        # stacks) or a host copy of the stacks in `params`
        if store is None:
            store = HostExpertStore.from_params(lp["moe"], self.L, self.E)
        elif set(store.weights) != {(l, e) for l in range(self.L)
                                    for e in range(self.E)}:
            raise ValueError(f"expert store does not hold {self.L} layers "
                             f"x {self.E} experts of {cfg.name}")
        self.store = store
        # device-resident: everything except routed expert weights
        moe_dev = {k: v for k, v in lp["moe"].items()
                   if k not in EXPERT_KEYS}
        self.dev = {
            "embed": params["embed"], "ln_f": params["ln_f"],
            "layers": {k: v for k, v in lp.items() if k != "moe"},
            "moe": moe_dev,
        }
        self.temperature = temperature
        self.prefill_chunk_size = prefill_chunk
        # sparse grouped execution: fused_prefill=None defers to the
        # REPRO_OPT_GROUPED_FFN opt flag, which also selects the Pallas
        # pool-kernel backend for every grouped sweep (resolved once here)
        self.fused_prefill = (opt_flags.grouped_ffn() if fused_prefill
                              is None else bool(fused_prefill))
        self._grouped_pallas = opt_flags.grouped_ffn()
        # observability spine (repro.obs): ONE registry per engine is the
        # home of every number this engine tracks; the span recorder is off
        # by default (spans=True — or a pre-built SpanRecorder, e.g. with a
        # sampling rate — turns the lifecycle/phase timeline on)
        self.metrics = MetricsRegistry()
        self.obs = (spans if isinstance(spans, SpanRecorder)
                    else SpanRecorder(enabled=bool(spans)))
        self.perf = PerfCounters(self.metrics)
        self._rng = np.random.default_rng(sample_seed)
        # event sink: every generated token is emitted as a TokenEvent; the
        # front-ends (serve(), BatchedServingEngine.step()) assemble their
        # outputs from this stream rather than from side-channel state
        self._events: List[Event] = []
        sc = StateConstructor(stats) if stats is not None else None
        # ONE ledger per engine: the residency is built first, then the
        # scheduler shares it by reference (sched.cache IS self.cache).
        # Capacity covers the policy default AND the largest must-have
        # (pinned) set a single prefill plan can create — every expert the
        # chunk's tokens activate stays pinned until end_layer — so the
        # all-pinned growth branch (and a pool regrow) never fires and
        # expert HBM is a hard capacity*bytes_per_expert bound.
        pin_bound = self.E if prefill_chunk is None \
            else min(self.E, prefill_chunk * self.k)
        cap = cache_capacity or max(
            default_capacity(policy, self.L, self.E, self.k,
                             batch=sched_batch), pin_bound)
        self.cache = ExpertResidency(self.store, capacity=cap, obs=self.obs,
                                     metrics=self.metrics)
        # residency counts surface as PULL gauges — evaluated at snapshot
        # time off the one ledger, so the cache hot path stays untouched
        self.metrics.gauge("residency_hits", "expert-cache hits (lifetime)",
                           fn=lambda: self.cache.hits)
        self.metrics.gauge("residency_misses",
                           "expert-cache misses (lifetime)",
                           fn=lambda: self.cache.misses)
        self.metrics.gauge("residency_evictions",
                           "expert slots evicted (lifetime)",
                           fn=lambda: sum(1 for e in self.cache.events
                                          if e.kind == "evict"))
        self.metrics.gauge("residency_device_bytes",
                           "expert weight bytes resident in HBM",
                           fn=lambda: self.cache.device_bytes)
        self.sched = make_scheduler(
            policy, self.L, self.E, self.k, self.store.bytes_per_expert,
            stats=stats, predictor=predictor, state_constructor=sc,
            capacity=cap, batch=sched_batch, state=self.cache)
        assert self.sched.cache is self.cache, "ledger must be shared"
        for name, fn in self._jit_fns(cfg).items():
            setattr(self, name, fn)

    # -- jitted per-layer kernels (compiled once; reused for every layer) ----
    @staticmethod
    def _jit_fns(cfg: ArchConfig) -> Dict[str, Callable]:
        """The engine's jitted per-layer kernels for ``cfg``, keyed by the
        attribute each engine binds them to. A function of the config
        alone, so they can also be lowered for a described chip without
        building an engine (tests/test_tpu_compile.py)."""
        dims = attn_dims(cfg)
        eps = cfg.rms_eps
        n_experts, top_k = cfg.n_experts, cfg.top_k
        vp = L.vocab_pad_of(cfg.vocab)

        @jax.jit
        def attn_prefill(lp, x):
            h, (k, v) = L.self_attn_full(L.rms_norm(x, lp["ln1"], eps),
                                         lp["attn"], dims)
            return x + h, k, v

        @jax.jit
        def attn_prefill_chunk(lp, x, ck, cv, sp, start):
            h, ck, cv, sp = L.self_attn_prefill_chunk(
                L.rms_norm(x, lp["ln1"], eps), lp["attn"], dims,
                ck, cv, sp, start)
            return x + h, ck, cv, sp

        @jax.jit
        def attn_decode(lp, x, ck, cv, sp, slot, pos):
            h, ck, cv = L.self_attn_decode(
                L.rms_norm(x, lp["ln1"], eps), lp["attn"], dims,
                ck, cv, sp, slot, pos)
            return x + h, ck, cv

        @jax.jit
        def attn_decode_batched(lp, x, ck, cv, sp, slot, pos):
            h, ck, cv = L.self_attn_decode_batched(
                L.rms_norm(x, lp["ln1"], eps), lp["attn"], dims,
                ck, cv, sp, slot, pos)
            return x + h, ck, cv

        @jax.jit
        def gate(moe_dev, lp, x):
            xn = L.rms_norm(x, lp["ln2"], eps)
            x2 = xn.reshape(-1, xn.shape[-1])
            w, ids, probs = M.route(x2, moe_dev["router"], n_experts, top_k)
            return xn, w, ids

        @jax.jit
        def expert_raw(xn, w1p, w3p, w2p, slot):
            """Pre-gate expert output in f32: [T, d]. Weights are read by
            slot index out of the residency's fixed [capacity, ...] pools
            (the slot arrives as a traced jnp scalar, so one compilation
            serves every slot)."""
            x2 = xn.reshape(-1, xn.shape[-1])
            w1 = jax.lax.dynamic_index_in_dim(w1p, slot, keepdims=False)
            w3 = jax.lax.dynamic_index_in_dim(w3p, slot, keepdims=False)
            w2 = jax.lax.dynamic_index_in_dim(w2p, slot, keepdims=False)
            h = jax.nn.silu(x2 @ w1) * (x2 @ w3)
            return (h @ w2).astype(jnp.float32)

        @jax.jit
        def grouped_raw(xn, row_idx, w1p, w3p, w2p, slots):
            """Segment-gathered expert sweep in ONE launch: row_idx [U, C]
            indexes each expert's selecting rows into the flattened tokens
            (padding rows repeat row 0 — computed and never read back) and
            slots [U] reads each expert's slab out of the residency pools.
            Per-row numerics are IDENTICAL to expert_raw — same dtypes,
            same contraction order, f32 cast after the down-projection —
            so every gathered row is bit-equal to the dense full-batch
            output for that (row, expert)."""
            x2 = xn.reshape(-1, xn.shape[-1])
            xg = x2[row_idx]                        # [U, C, d]
            w1 = w1p[slots]
            w3 = w3p[slots]
            w2 = w2p[slots]
            h = jax.nn.silu(jnp.einsum("ucd,udf->ucf", xg, w1)) \
                * jnp.einsum("ucd,udf->ucf", xg, w3)
            return jnp.einsum("ucf,ufd->ucd", h, w2).astype(jnp.float32)

        @jax.jit
        def expert_apply(xn, w1p, w3p, w2p, slot, gate_w):
            return (expert_raw(xn, w1p, w3p, w2p, slot)
                    * gate_w[:, None]).astype(xn.dtype)

        @jax.jit
        def shared_apply(moe_dev, xn):
            if "sw1" not in moe_dev:
                return jnp.zeros_like(xn.reshape(-1, xn.shape[-1]))
            x2 = xn.reshape(-1, xn.shape[-1])
            h = jax.nn.silu(x2 @ moe_dev["sw1"]) * (x2 @ moe_dev["sw3"])
            return h @ moe_dev["sw2"]

        @jax.jit
        def head(p_lnf, embed, x_last):
            x = L.rms_norm(x_last, p_lnf, eps)
            lg = x @ embed.T.astype(x.dtype)
            mask = jnp.arange(vp) < cfg.vocab
            return jnp.where(mask, lg.astype(jnp.float32), -1e9)

        @jax.jit
        def concat_rows(*xs):
            """The chunks of one prefill sweep, [1, C_i, d] each, side by
            side as one [1, sum C_i, d] token block for the layer's MoE."""
            return jnp.concatenate(xs, axis=1)

        @functools.partial(jax.jit, static_argnums=1)
        def split_rows(x, sizes):
            """Inverse of concat_rows: the chunks' own rows back, one
            program per tuple of chunk sizes."""
            return tuple(jnp.split(x, np.cumsum(sizes)[:-1].tolist(), axis=1))

        return {
            "_attn_prefill": attn_prefill,
            "_attn_prefill_chunk": attn_prefill_chunk,
            "_attn_decode": attn_decode,
            "_attn_decode_batched": attn_decode_batched,
            "_gate": gate,
            "_expert_raw": expert_raw,
            "_grouped_raw": grouped_raw,
            "_expert": expert_apply,
            "_shared": shared_apply,
            "_head": head,
            "_concat_rows": concat_rows,
            "_split_rows": split_rows,
        }

    def _layer(self, l: int):
        return jax.tree.map(lambda a: a[l], self.dev["layers"])

    def _moe_dev(self, l: int):
        return jax.tree.map(lambda a: a[l], self.dev["moe"])

    def _grouped_ffn_raw(self, l: int, union: Sequence[int], xn,
                         row_idx: np.ndarray):
        """ONE FFN launch for a whole layer's expert sweep, reading weights
        by slot out of the residency pools. The per-expert host syncs of
        the dense path collapse into one vectorized slot pass (single
        host walk over the union, single int32 transfer); pools are read
        AFTER the pass, so pending transfers' fresh array objects are
        picked up. Backend: the engine grouped einsum (bit-exact vs
        expert_raw) or, under REPRO_OPT_GROUPED_FFN, the Pallas
        ``expert_ffn_from_pool`` streaming kernel. Returns f32 [U, C, d]."""
        slots = np.fromiter((self.cache.slot((l, e)) for e in union),
                            np.int32, count=len(union))
        if row_idx.shape[0] > slots.size:
            # U-bucketed dispatch: padding groups read slab 0 (always a
            # valid slot) and their output is never scattered back
            slots = np.pad(slots, (0, row_idx.shape[0] - slots.size))
        jslots = jnp.asarray(slots)
        jrows = jnp.asarray(row_idx)
        if self._grouped_pallas:
            x2 = xn.reshape(-1, xn.shape[-1])
            out = expert_ffn_from_pool(x2[jrows], *self.cache.pools, jslots,
                                       interpret=default_interpret())
            return out.astype(jnp.float32)
        return self._grouped_raw(xn, jrows, *self.cache.pools, jslots)

    def _run_experts_prefill(self, l, xn, w, ids, plan, ids_np=None):
        """Execute the PrefillPlan: grouped per-expert compute with the
        policy's fetch schedule. The plan already admitted its fetches into
        the shared ledger (slots reserved); `prefetch` here issues the
        actual host->device copies between compute dispatches, preserving
        the two-stream overlap, and `slot` is the use-time sync point.
        With ``fused_prefill`` (and the gate's host-side ids available) the
        per-expert sweep collapses into ONE grouped FFN launch instead —
        same fetch schedule, same bits (see _run_experts_prefill_fused)."""
        acc = self._shared(self._moe_dev(l), xn)
        order = plan.order
        if order:
            self.perf.inc("prefill_moe_layers")
        if self.fused_prefill and order and ids_np is not None:
            return self._run_experts_prefill_fused(l, xn, w, ids, plan,
                                                   ids_np, acc)
        if order:
            self.perf.inc("prefill_ffn_launches", len(order))
            self.perf.max_update("max_prefill_launches_per_layer",
                                 len(order))
        # stage fetches according to the plan
        if plan.prefetch_all_first:
            for e in plan.fetches:
                self.cache.prefetch((l, e))
        elif plan.overlap_first and order:
            self.cache.prefetch((l, order[0]))
        for i, e in enumerate(order):
            if not plan.prefetch_all_first:
                if plan.pipelined and i + 1 < len(order):
                    # comm stream: next expert streams while e computes
                    self.cache.prefetch((l, order[i + 1]))
                elif not plan.pipelined:
                    self.cache.prefetch((l, e))
            eslot = jnp.int32(self.cache.slot((l, e)))
            gate_w = (w * (ids == e)).sum(-1).reshape(-1)
            acc = acc + self._expert(xn, *self.cache.pools, eslot, gate_w)
        return acc.reshape(xn.shape)

    def _run_experts_prefill_fused(self, l, xn, w, ids, plan, ids_np, acc):
        """Fused PrefillPlan execution: the per-expert sweep is ONE grouped
        FFN launch off the residency pools. The plan's fetch schedule is
        preserved verbatim — the same `prefetch` calls are issued in the
        same order (all ahead of the single launch, the degenerate form of
        "between compute dispatches"), then one vectorized slot pass is the
        use-time sync point. Gate weights are folded in on scatter-back,
        one expert at a time IN PLAN ORDER, so the accumulation order — and
        with it every output bit — matches the unfused loop (non-selecting
        tokens contribute exact zeros on both paths)."""
        order = plan.order
        if plan.prefetch_all_first:
            for e in plan.fetches:
                self.cache.prefetch((l, e))
        elif plan.overlap_first:
            self.cache.prefetch((l, order[0]))
        for i, e in enumerate(order):
            if not plan.prefetch_all_first:
                if plan.pipelined and i + 1 < len(order):
                    self.cache.prefetch((l, order[i + 1]))
                elif not plan.pipelined:
                    self.cache.prefetch((l, e))
        T = ids_np.shape[0]
        disp = group_by_expert(ids_np, order, bucket_cap=T,
                               u_bucket_cap=min(self.E, T * self.k))
        raw = self._grouped_ffn_raw(l, order, xn, disp.row_idx)  # [U, C, d]
        self.perf.inc("prefill_ffn_launches")
        self.perf.max_update("max_prefill_launches_per_layer", 1)
        # scatter-back as one fixed-shape gather per expert: token t reads
        # its row of the expert's group, or an appended zero row where it
        # did not select the expert — the same bits as scattering the
        # group into zeros, without a program per group size
        U, C, d = raw.shape
        flat = jnp.concatenate([raw.reshape(U * C, d),
                                jnp.zeros((1, d), raw.dtype)])
        src = np.full((len(order), T), U * C, np.int32)
        for j in range(self.k):
            src[disp.u_of[:, j], np.arange(T)] = (disp.u_of[:, j] * C
                                                  + disp.c_of[:, j])
        for u, e in enumerate(order):
            gate_w = (w * (ids == e)).sum(-1).reshape(-1)
            y = flat[jnp.asarray(src[u])]
            acc = acc + (y * gate_w[:, None]).astype(acc.dtype)
        return acc.reshape(xn.shape)

    def _prefill_moe(self, l: int, lp, x):
        """Shared per-layer MoE body of both prefill paths: gate, dispatch
        the policy's PrefillPlan, add the expert output, unpin the layer.
        Returns (x_out, per-token ids [T, k] np, sorted active experts)."""
        self.cache.phase = "prefill"
        rt = self.obs.begin("moe.route", lane="prefill", layer=l)
        xn, w, ids = self._gate(self._moe_dev(l), lp, x)
        ids_np = np.asarray(ids)  # sync: gate result needed by dispatcher
        act = sorted(set(int(e) for e in ids_np.ravel()))
        plan = self.sched.prefill_plan(l, act)
        self.obs.end(rt)
        ct = self.obs.begin("moe.combine", lane="prefill", layer=l)
        y = self._run_experts_prefill(l, xn, w, ids, plan,
                                      ids_np=ids_np.reshape(-1, self.k))
        x = x + y
        self.obs.end(ct)
        self.sched.end_layer(l)
        return x, ids_np.reshape(-1, self.k), act

    def prefill_chunk(self, chunk: np.ndarray, start: int,
                      kc: List[jax.Array], vc: List[jax.Array],
                      sp: jax.Array, *, need_logits: bool = True):
        """Run ONE prefill chunk [1, C] through all layers incrementally.

        The unit of prefill work for chunked/stall-free serving: the chunk's
        queries attend over the KV prefix written by earlier chunks (slots
        0..start-1 of the per-layer buffers kc/vc, [1, W, Hkv, hd]) plus
        themselves, and append their K/V at slots start..start+C-1. Expert
        scheduling goes through the SAME per-layer `prefill_plan` path as
        monolithic prefill, so the policy's fetch pipeline and cache ledger
        see each chunk as a (smaller) prefill.

        Returns (logits [1, Vp] of the chunk's last position — or None when
        need_logits=False — kc, vc, sp, active_per_layer for this chunk,
        per-token paths [C, L, k]).
        """
        c = PrefillChunk(chunk, start, kc, vc, sp, need_logits)
        (logits, active, paths), = self.prefill_chunks([c])
        return logits, c.kc, c.vc, c.sp, active, paths

    def prefill_chunks(self, chunks: Sequence[PrefillChunk]):
        """Run the prefill chunks of several requests through all layers in
        ONE sweep.

        Per layer, each chunk's attention runs on its own KV exactly as a
        lone ``prefill_chunk`` would run it; then the MoE runs once over
        the chunks' tokens side by side: one gate, one ``prefill_plan``
        over the union of their experts, one expert sweep that copies each
        active expert at most once, one ``end_layer``. Each chunk gets its
        own rows back. Every MoE op is row-wise and every plan orders the
        experts the same way whatever else is active, so each request's
        bits are those of its chunk run alone; a single chunk takes exactly
        the launches of the one-request sweep.

        Updates each chunk's kc/vc/sp in place and returns, per chunk,
        (logits [1, Vp] of its last position or None, active_per_layer,
        per-token paths [C, L, k]).
        """
        n = len(chunks)
        sizes = tuple(int(c.tokens.shape[1]) for c in chunks)
        bounds = np.cumsum((0,) + sizes)
        self.perf.inc("prefill_passes")
        self.perf.inc("prefill_pass_chunks", n)
        xs = [self.dev["embed"].at[jnp.asarray(c.tokens)].get(mode="clip")
              for c in chunks]
        starts = [jnp.int32(c.start) for c in chunks]
        active: List[List[List[int]]] = [[] for _ in chunks]
        paths = [np.zeros((C, self.L, self.k), np.int32) for C in sizes]
        for l in range(self.L):
            at = self.obs.begin("attention", lane="prefill", layer=l)
            lp = self._layer(l)
            for i, c in enumerate(chunks):
                xs[i], c.kc[l], c.vc[l], c.sp = self._attn_prefill_chunk(
                    lp, xs[i], c.kc[l], c.vc[l], c.sp, starts[i])
            self.obs.end(at)
            x = xs[0] if n == 1 else self._concat_rows(*xs)
            x, ids_np, _ = self._prefill_moe(l, lp, x)
            xs = [x] if n == 1 else list(self._split_rows(x, sizes))
            for i in range(n):
                rows = ids_np[bounds[i]:bounds[i + 1]]
                paths[i][:, l] = rows
                active[i].append(sorted(set(int(e) for e in rows.ravel())))
        return [((self._head(self.dev["ln_f"], self.dev["embed"], x[:, -1])
                  if c.need_logits else None), active[i], paths[i])
                for i, (c, x) in enumerate(zip(chunks, xs))]

    def prefill_layers(self, tokens: np.ndarray,
                       chunk_size: Optional[int] = None):
        """Run the layer-by-layer prefill pipeline on tokens [1, S].

        chunk_size (default: the engine's `prefill_chunk_size`): None runs
        the
        whole prompt monolithically via `self_attn_full`; an int >= 1 runs
        it as a sequence of `prefill_chunk` calls over token-budget chunks.
        Both paths produce bit-identical results (tests/test_serving_batch).

        Returns (last_logits [1, Vp], (kc, vc), active_per_layer,
        per-token paths [S, L, k]). Sampling is left to the caller so both
        the single-request and the batched front-end can share this path.
        """
        if chunk_size is None:
            chunk_size = self.prefill_chunk_size
        S = tokens.shape[1]
        if chunk_size is not None:
            # always the incremental path when a chunk size is set — with
            # chunk_size >= S that is one whole-prompt chunk, so the
            # prefill_chunk kernel itself is exercised at every size
            return self._prefill_layers_chunked(tokens, chunk_size)
        x = self.dev["embed"].at[jnp.asarray(tokens)].get(mode="clip")
        kc, vc = [], []
        active: List[List[int]] = []
        paths = np.zeros((S, self.L, self.k), np.int32)
        for l in range(self.L):
            at = self.obs.begin("attention", lane="prefill", layer=l)
            lp = self._layer(l)
            x, k_, v_ = self._attn_prefill(lp, x)
            self.obs.end(at)
            x, ids_np, act = self._prefill_moe(l, lp, x)
            paths[:, l] = ids_np
            kc.append(k_)
            vc.append(v_)
            active.append(act)
        logits = self._head(self.dev["ln_f"], self.dev["embed"], x[:, -1])
        return logits, (kc, vc), active, paths

    def _prefill_layers_chunked(self, tokens: np.ndarray, chunk_size: int):
        """Chunked drop-in for `prefill_layers`: same return contract, the
        prompt processed `chunk_size` tokens at a time through
        `prefill_chunk` (per-layer KV buffers sized to the prompt)."""
        assert chunk_size >= 1
        S = tokens.shape[1]
        hkv, hd = self.cfg.n_kv_heads, self.cfg.hd
        kc = [jnp.zeros((1, S, hkv, hd), PDT) for _ in range(self.L)]
        vc = [jnp.zeros_like(kc[l]) for l in range(self.L)]
        sp = jnp.full((1, S), -1, jnp.int32)
        active_sets = [set() for _ in range(self.L)]
        paths = np.zeros((S, self.L, self.k), np.int32)
        logits = None
        for start in range(0, S, chunk_size):
            stop = min(start + chunk_size, S)
            logits, kc, vc, sp, act, cpaths = self.prefill_chunk(
                tokens[:, start:stop], start, kc, vc, sp,
                need_logits=(stop == S))
            paths[start:stop] = cpaths
            for l in range(self.L):
                active_sets[l].update(act[l])
        active = [sorted(s) for s in active_sets]
        return logits, (kc, vc), active, paths

    # -- event stream --------------------------------------------------------
    def _emit(self, ev: Event) -> None:
        self._events.append(ev)

    def drain_events(self) -> List[Event]:
        """Take (and clear) every event emitted since the last drain.
        `BatchedServingEngine.step()` drains into its StepEvents return;
        `MoEServingEngine.serve()` drains to assemble its RequestResult;
        the ServingFrontend drains at cancellation sites."""
        evs, self._events = self._events, []
        return evs

    def _sample(self, logits) -> int:
        return self.sample_row(np.asarray(logits, np.float64)[0],
                               self.temperature, self._rng)

    @staticmethod
    def sample_row(lg: np.ndarray, temperature: float, rng) -> int:
        """Sample one token id from a f64 logits row (greedy at temp<=0)."""
        if temperature <= 0:
            return int(lg.argmax())
        lg = lg / temperature
        lg = lg - lg.max()
        p = np.exp(lg)
        p /= p.sum()
        return int(rng.choice(len(p), p=p))


class MoEServingEngine(EngineCore):
    """Single-request engine (paper scope): one prompt at a time, KV cache
    private to the request, decode loop runs the full dual-phase schedule.
    Tokens flow through the EngineCore event sink: ``decode`` emits a
    TokenEvent per step and ``serve`` assembles its RequestResult from the
    drained stream (the same records the batched front-end emits)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._serve_rid = 0   # event-stream rid per serve() call

    def prefill(self, tokens: np.ndarray):
        """tokens: [1, S]. Returns (next_token, kv_caches, active_per_layer,
        per-token paths [S, L, k])."""
        logits, kv, active, paths = self.prefill_layers(tokens)
        return self._sample(logits), kv, active, paths

    def decode(self, first_token: int, kv, prompt_len: int, max_new: int, *,
               stop_ids: Sequence[int] = (), rid: int = 0,
               temperature: Optional[float] = None, rng=None):
        """Decode up to `max_new` tokens after `first_token`, emitting a
        TokenEvent per token; a token in `stop_ids` terminates the loop
        early (the stop token itself is still emitted). Returns
        (tokens [T<=max_new], trace [T, L, k], pred_trace [T, L, k])."""
        temp = self.temperature if temperature is None else temperature
        rng = self._rng if rng is None else rng
        kc, vc = kv
        cap = prompt_len + max_new + 1
        Wpad = cap
        kc = [jnp.pad(k, ((0, 0), (0, Wpad - k.shape[1]), (0, 0), (0, 0)))
              for k in kc]
        vc = [jnp.pad(v, ((0, 0), (0, Wpad - v.shape[1]), (0, 0), (0, 0)))
              for v in vc]
        sp = jnp.pad(jnp.arange(prompt_len, dtype=jnp.int32),
                     (0, Wpad - prompt_len), constant_values=-1)
        out = [first_token]
        trace = np.zeros((max_new, self.L, self.k), np.int32)
        pred_trace = np.full((max_new, self.L, self.k), -1, np.int32)
        n_dec = 0
        self.cache.phase = "decode"
        for t in range(max_new):
            st = self.obs.begin("decode.step", lane="decode", rid=rid)
            tok = jnp.asarray([[out[-1]]], jnp.int32)
            x = self.dev["embed"].at[tok].get(mode="clip")
            pos = jnp.int32(prompt_len + t)
            slot = int(prompt_len + t) % Wpad
            sp = sp.at[slot].set(prompt_len + t)
            if isinstance(self.sched, DuoServeScheduler):
                self.sched.begin_decode_step()
            for l in range(self.L):
                lp = self._layer(l)
                x, kc[l], vc[l] = self._attn_decode(lp, x, kc[l], vc[l], sp,
                                                    slot, pos)
                xn, w, ids = self._gate(self._moe_dev(l), lp, x)
                sel = [int(e) for e in np.asarray(ids).ravel()[: self.k]]
                trace[t, l] = sel
                plan = self.sched.decode_plan(l, sel)
                np_pred = plan.predicted[: self.k]
                pred_trace[t, l, : len(np_pred)] = np_pred
                # correction fetches for misses (sync point #1)
                if plan.misses:
                    pt = self.obs.begin("prefetch.correction",
                                        lane="prefetch", rid=rid, layer=l,
                                        n=len(plan.misses))
                    for e in plan.misses:
                        self.cache.prefetch((l, e))
                        self.cache.wait((l, e))
                    self.obs.end(pt)
                acc = self._shared(self._moe_dev(l), xn)
                for e in sel:
                    eslot = jnp.int32(self.cache.slot((l, e)))
                    gate_w = (w * (ids == e)).sum(-1).reshape(-1)
                    acc = acc + self._expert(xn, *self.cache.pools, eslot,
                                             gate_w)
                x = x + acc.reshape(x.shape)
                # prediction stream: prefetch next layer's predicted experts
                if plan.prefetch_next:
                    self.obs.instant("prefetch.dispatch", lane="prefetch",
                                     rid=rid, layer=l,
                                     n=len(plan.prefetch_next))
                for e in plan.prefetch_next:
                    self.cache.prefetch((l + 1, e))
            # the policies end_layer(l) when planning l+1; the LAST layer has
            # no successor, so unpin it here or its pins outlive the step and
            # accumulate until the ledger's all-pinned growth branch fires
            self.sched.end_layer(self.L - 1)
            logits = self._head(self.dev["ln_f"], self.dev["embed"], x[:, -1])
            tok = self.sample_row(np.asarray(logits, np.float64)[0], temp,
                                  rng)
            out.append(tok)
            n_dec = t + 1
            self._emit_token(rid, tok, n_dec)
            self.obs.end(st, token_id=tok)
            if tok in stop_ids:
                break
        return (np.asarray(out[1:]), trace[:n_dec], pred_trace[:n_dec])

    def _emit_token(self, rid: int, token: int, index: int, *,
                    first: bool = False) -> None:
        """The single-request engine's token sink (mirror of
        BatchedServingEngine._emit_token): every streamed token funnels
        through one place so cancellation/TBT accounting — and the
        emit-discipline lint — hold engine-wide."""
        self._emit(TokenEvent(rid=rid, token=token, index=index,
                              t=time.perf_counter(), first=first))

    def serve(self, prompt: np.ndarray, max_new: int = 16, *,
              params: Optional[SamplingParams] = None) -> RequestResult:
        """Serve one prompt end to end — a thin wrapper over the event
        stream: prefill and decode emit TokenEvents through the engine
        sink, and the returned RequestResult's token array is assembled
        from the drained stream. Legacy `max_new=` is compat sugar for
        `params=SamplingParams(max_new_tokens=...)` (which also carries
        temperature, stop_token_ids, and seed)."""
        if params is None:
            params = SamplingParams(max_new_tokens=max_new)
        temp = (self.temperature if params.temperature is None
                else params.temperature)
        rng = (np.random.default_rng(params.seed)
               if params.seed is not None else self._rng)
        rid = self._serve_rid
        self._serve_rid += 1
        self.sched.begin_request()
        h0, m0 = self.sched.cache.hits, self.sched.cache.misses
        self.drain_events()
        t0 = time.perf_counter()
        prompt = np.asarray(prompt, np.int32).reshape(1, -1)
        logits, kv, active, _ = self.prefill_layers(prompt)
        first = self.sample_row(np.asarray(logits, np.float64)[0], temp, rng)
        t1 = time.perf_counter()
        self._emit_token(rid, first, 0, first=True)
        if first in params.stop_token_ids:
            trace = np.zeros((0, self.L, self.k), np.int32)
            pred = np.full((0, self.L, self.k), -1, np.int32)
        else:
            _, trace, pred = self.decode(
                first, kv, prompt.shape[1], params.max_new_tokens,
                stop_ids=params.stop_token_ids, rid=rid,
                temperature=temp, rng=rng)
        t2 = time.perf_counter()
        events = self.drain_events()
        tokens = np.asarray([e.token for e in events
                             if isinstance(e, TokenEvent)], np.int64)
        reason = ("stop_token" if params.stop_token_ids and tokens.size
                  and int(tokens[-1]) in params.stop_token_ids else "length")
        # no FinishEvent here: serve() is synchronous, so completion is the
        # return itself (finish_reason below) — an emitted event could never
        # be observed before this same method drained it
        return RequestResult(
            tokens=tokens,
            prefill_active=active, decode_trace=trace, pred_trace=pred,
            ttft_wall=t1 - t0, e2e_wall=t2 - t0,
            hits=self.sched.cache.hits - h0,
            misses=self.sched.cache.misses - m0,
            finish_reason=reason)


def collect_traces(cfg: ArchConfig, params, prompts: Sequence[np.ndarray],
                   max_new: int = 8, *, store: Optional[HostExpertStore] = None
                   ) -> Tuple[ExpertsTracer, List[RequestResult]]:
    """Offline preprocess (paper §IV-A): run an ODF-scheduled engine over a
    small dataset slice and record per-token activation paths. ``store``:
    the host expert store the serving engine will share (see EngineCore)."""
    engine = MoEServingEngine(cfg, params, policy="odf", store=store)
    tracer = ExpertsTracer(cfg.n_layers, cfg.n_experts, cfg.top_k)
    results = []
    for p in prompts:
        r = engine.serve(p, max_new=max_new)
        results.append(r)
        tracer.add_paths(r.decode_trace)
    return tracer, results
