"""Continuous-batching serving engine (ROADMAP: multi-request QoS).

Generalizes the paper's single-request dual-phase runtime to concurrent
load, the regime its TTFT/E2E SLO claims actually target. The public
surface is typed and event-driven (``serving/api.py`` +
``serving/frontend.py``): callers describe a request as a
``GenerationRequest`` (prompt + frozen ``SamplingParams`` + QoS targets +
priority), ``step()`` emits a ``StepEvents`` list of
``TokenEvent``/``FinishEvent``/``RejectEvent`` records, and requests can be
cancelled mid-prefill or mid-decode with their KV slot, expert-residency
contributions, and TBT-ledger entry reclaimed within the same call.

  * ``RequestQueue`` — arrival queue with SLO-aware admission: predicted
    TTFT (EWMA cost model, ``core/qos.py``) is checked against each
    request's deadline, folding in the remaining prefill backlog AND the
    running batch's decode interference; requests whose deadline is already
    unmeetable are shed instead of poisoning the batch. Per-request
    ``tbt_slo`` targets that are structurally unmeetable are shed too.
    Candidates are considered in (priority desc, arrival) order — stable,
    so equal priorities keep FIFO.
  * ``BatchedServingEngine`` — continuous batching over the layer-by-layer
    engine core: requests are admitted mid-flight; each scheduler iteration
    spends at most ``prefill_budget`` prompt tokens of (chunked) prefill
    work, then runs ONE batched decode step for every in-flight request.
    KV lives in a slot pool (one slot per in-flight request, per-request
    write positions, ring invariant slot == pos % W), so sequences at
    different positions decode together via ``self_attn_decode_batched``.
  * Chunked, stall-free prefill (paper §III phase disparity): a long prompt
    no longer freezes in-flight decoders for its whole prefill. Admitted
    requests sit in state ``prefilling``; each iteration spends the step's
    token budget on chunks through ``EngineCore.prefill_chunks``, so
    inter-token gaps for decoders stay bounded by one chunk + one decode
    step instead of a full prefill. The budget is shared FAIRLY:
    ``prefill_fairness="rr"`` (default) rotates the per-step budget across
    ALL prefilling requests so one long prompt cannot starve later
    arrivals' TTFT; ``"srf"`` serves shortest-remaining-first (short
    prompts overtake long backlogs — best straggler TTFT, long prompts pay);
    ``"fifo"`` restores the head-of-line discipline. A step's chunks share
    one sweep through the layers: each request's attention runs on its own
    KV, the MoE once over all the step's tokens, so each active expert is
    copied once per step however many requests the budget spans.
    ``prefill_budget="auto"`` derives the budget each step from the live
    ``LatencyModel`` so one chunk + one batched decode step fits the
    TIGHTEST inter-token-gap target in flight (the engine ``tbt_slo`` and
    every in-flight request's own ``tbt_slo``; core/qos.py
    ``suggest_chunk``). ``prefill_budget=None`` preserves the monolithic
    behaviour. The ``TBTLedger`` (core/qos.py) records per-request
    inter-token gaps; ``benchmarks/bench_stall.py`` measures the bound.
  * Decode-phase expert scheduling is shared: per-step, per-layer expert
    selections of all B requests are unioned (first-appearance order) and
    handed to ONE scheduler/ExpertResidency ledger (paper §V generalized to
    B>1) — each distinct expert is fetched at most once per step.
  * Cancellation (``cancel``): a queued request is dequeued; a prefilling or
    running request is removed from its phase list, its KV slot returns to
    the free pool, its expert-residency contributions are dropped from the
    shared ledger (only entries no OTHER in-flight request also touched —
    surviving rows keep their working set), and its ``TBTLedger`` entry is
    closed. The request emits one final ``FinishEvent("cancelled")`` and
    never emits again. Survivors are bit-unaffected: every decode kernel is
    row-wise deterministic, so shrinking the batch never changes their
    tokens (tests/test_frontend.py).

Exactness invariant: every decode-side kernel is row-wise deterministic,
per-row accumulation follows each request's own top-k order, and chunked
prefill's valid-key sets/per-token expert order match monolithic prefill
row-wise — so at temperature 0 a batched step reproduces the
single-request engine's tokens bit-exactly for EVERY chunk size, fairness
mode, and poll() schedule (tests/test_serving_batch.py,
tests/test_frontend.py).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional, Set, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core.cache import ExpertKey
from repro.core.prefix import PrefixTree
from repro.core.qos import (Admission, AdmissionController, ReplicaLoad,
                            TBTLedger)
from repro.core.scheduler import DuoServeScheduler
from repro.models.layers import PDT
from repro.obs.spans import SpanRecorder, monotonic
from repro.serving.api import (FinishEvent, GenerationRequest, RejectEvent,
                               RequestSnapshot, SamplingParams, StepEvents,
                               TokenEvent)
from repro.serving.engine import (EngineCore, PrefillChunk, RequestResult,
                                  group_by_expert)


@dataclasses.dataclass
class Request:
    """One request's RUNTIME state inside the engine (the engine-internal
    counterpart of the immutable ``GenerationRequest`` spec)."""
    rid: int
    prompt: np.ndarray               # [S] int32
    params: SamplingParams
    arrival: float
    ttft_slo: Optional[float] = None
    tbt_slo: Optional[float] = None
    priority: int = 0
    # runtime state ---------------------------------------------------------
    # queued|prefilling|running|held|done|rejected|cancelled|paused:
    # 'held' = prefill complete on a role='prefill' replica, awaiting KV
    # handoff; 'paused' = snapshot taken, the request lives HOST-side in a
    # RequestSnapshot (this engine holds nothing for it any more)
    state: str = "queued"
    finish_reason: Optional[str] = None  # length|stop_token|cancelled|slo_shed
    slot: int = -1
    prefill_pos: int = 0             # prompt tokens already prefilled
    prefix_len: int = 0              # leading tokens seeded from PrefixTree
    tokens: List[int] = dataclasses.field(default_factory=list)
    prefill_active: List[List[int]] = dataclasses.field(default_factory=list)
    active_sets: Optional[List[set]] = None   # accumulating, chunked prefill
    # per-layer [1, W] KV carried across prefill chunks; scattered into the
    # engine's slot pool ONCE when the final chunk completes (so a chunk
    # never round-trips the whole [max_batch, W] pool)
    pf_k: Optional[List] = None
    pf_v: Optional[List] = None
    pf_sp: Optional[object] = None
    trace: List[np.ndarray] = dataclasses.field(default_factory=list)
    pred: List[np.ndarray] = dataclasses.field(default_factory=list)
    hits: int = 0
    misses: int = 0
    t_start: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    rng: Optional[np.random.Generator] = None

    @property
    def max_new(self) -> int:
        return self.params.max_new_tokens

    @property
    def temperature(self) -> Optional[float]:
        return self.params.temperature   # None = engine default

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def prefill_remaining(self) -> int:
        return self.prompt_len - self.prefill_pos

    @property
    def pos(self) -> int:
        """Absolute position of the NEXT token to decode."""
        return self.prompt_len + len(self.tokens) - 1

    @property
    def done(self) -> bool:
        # first token + max_new decode steps, or an early finish
        # (stop token / cancellation) recorded in finish_reason
        return (self.finish_reason is not None
                or len(self.tokens) >= self.max_new + 1)

    def result(self) -> RequestResult:
        T = len(self.trace)
        L_k = self.trace[0].shape if T else (0, 0)
        return RequestResult(
            tokens=np.asarray(self.tokens, np.int64),
            prefill_active=self.prefill_active,
            decode_trace=(np.stack(self.trace) if T
                          else np.zeros((0,) + L_k, np.int32)),
            pred_trace=(np.stack(self.pred) if T
                        else np.zeros((0,) + L_k, np.int32)),
            # cancelled before the first token: no TTFT exists (t_first
            # still holds its 0.0 sentinel — not a real timestamp)
            ttft_wall=(self.t_first - self.arrival if self.t_first
                       else float("nan")),
            e2e_wall=self.t_done - self.arrival,
            hits=self.hits, misses=self.misses,
            finish_reason=self.finish_reason or "length")


def kv_row_bytes(engine: "BatchedServingEngine") -> int:
    """Host bytes one KV row (one position: K+V across all layers)
    occupies — the unit ``ReplicaPool.handoff_bytes_saved`` counts when a
    tail-only snapshot skips shipping the shared head."""
    return int(2 * engine.L * engine.cfg.n_kv_heads * engine.cfg.hd
               * np.dtype(PDT).itemsize)


def _nan_to_zero(fn):
    """Wrap a pull-gauge callback so an empty sketch's NaN reads as 0.0
    (a JSON metrics snapshot must stay finite)."""
    def g() -> float:
        v = float(fn())
        return v if v == v else 0.0
    return g


def parse_prefill_budget(v: Union[int, str, None]) -> Union[int, str, None]:
    """CLI-string form of `prefill_budget`: int tokens, "auto"
    (LatencyModel-tuned, needs tbt_slo), or None/"none" for monolithic.
    Shared by the benchmark/example drivers so the syntax stays in one
    place."""
    if v is None or v == "none":
        return None
    if v == "auto":
        return "auto"
    return int(v)


class RequestQueue:
    """Arrival queue with SLO-aware admission (core/qos.py).

    `pop_admissible` hands back up to `limit` requests whose predicted TTFT
    fits their deadline; breached requests are shed (state='rejected') so a
    doomed prompt never occupies a KV slot another request could meet its
    SLO with, and requests whose per-request `tbt_slo` is structurally
    unmeetable (steady per-step gap over target, core/qos.py
    `predict_tbt`) are shed too. Candidates are considered in
    (priority desc, arrival) order — the sort is stable over the FIFO
    deque, so equal priorities preserve arrival order and the historical
    all-priority-0 behaviour is unchanged. The TTFT prediction folds in the
    prefill backlog already admitted (`backlog_tokens`) and the running
    batch's decode interference (`running_batch`).
    """

    def __init__(self, admission: Optional[AdmissionController] = None):
        self.admission = admission or AdmissionController()
        self.pending: Deque[Request] = collections.deque()
        self.rejected: List[Request] = []

    def __len__(self) -> int:
        return len(self.pending)

    def submit(self, req: Request) -> None:
        self.pending.append(req)

    def remove(self, req: Request) -> bool:
        """Withdraw a still-queued request (cancellation before admission)."""
        try:
            self.pending.remove(req)
            return True
        except ValueError:
            return False

    def queued_tokens(self) -> int:
        return sum(r.prompt_len for r in self.pending)

    def pop_admissible(self, now: float, limit: int, *,
                       backlog_tokens: int = 0, running_batch: int = 0,
                       chunk_budget: Optional[int] = None,
                       chunk_adaptive: bool = False,
                       hit_fn=None) -> List[Request]:
        out: List[Request] = []
        ahead = backlog_tokens
        taken: List[Request] = []
        # stable priority-then-arrival order: GenerationRequest.priority is
        # load-bearing — a high-priority late arrival is considered first
        for req in sorted(self.pending, key=lambda r: -r.priority):
            if len(out) >= limit:
                break
            # prefix-cache-aware charging: `hit_fn` (the engine's read-only
            # PrefixTree peek) reports how many leading prompt tokens are
            # already cached, so the TTFT prediction and the backlog each
            # admitted request contributes charge only the un-hit suffix
            hit = hit_fn(req) if hit_fn is not None else 0
            verdict = self.admission.decide(
                now, req.arrival, req.prompt_len - hit, ahead, req.ttft_slo,
                running_batch=running_batch, chunk_budget=chunk_budget,
                tbt_slo=req.tbt_slo, chunk_adaptive=chunk_adaptive)
            if verdict is Admission.QUEUE:
                # deadline still reachable once the backlog drains: keep the
                # request where it is and stop admitting this round (a
                # lower-priority request must not jump past a queued one)
                break
            taken.append(req)
            if verdict is Admission.REJECT:
                req.state = "rejected"
                self.rejected.append(req)
                continue
            ahead += req.prompt_len - hit
            out.append(req)
        for req in taken:
            self.pending.remove(req)
        return out


class BatchedServingEngine(EngineCore):
    """Continuous-batching engine: slot-pool KV + shared expert scheduling.

    max_batch: concurrent in-flight requests (= KV slots).
    max_seq:   per-slot KV capacity W (prompt + generated tokens must fit).
    prefill_budget: max prompt tokens of prefill work per step(); admitted
        requests prefill in chunks under this budget (state 'prefilling'),
        interleaved with the batched decode step so decoder inter-token
        gaps stay bounded. None = monolithic (each admitted request
        prefills fully inside the step that admits it). "auto" = derive
        the budget each step from the live LatencyModel so one chunk + one
        batched decode step fits the tightest in-flight TBT target
        (requires tbt_slo as the fallback when no request carries one).
    prefill_fairness: "rr" (default) rotates the per-step budget across
        all prefilling requests (one chunk shape, fair progress over
        steps); "srf" serves shortest-remaining-first; "fifo" always
        spends the budget head-of-line.
    prefix_cache: enable cross-request prefix/KV reuse (core/prefix.py):
        on admit the engine matches the prompt against a radix tree over
        the slot pool, copies the longest cached prefix's KV rows into the
        new request's buffers, and prefills only the un-hit suffix
        (admission charges only that suffix too). Completed prompts are
        offered back to the tree; a retiring request's slot is RETAINED as
        tree-owned cache while nodes reference it and reclaimed LRU when a
        free slot is needed. Bit-exact vs cold prefill at temperature 0
        (the copied rows are exactly what prefill would recompute —
        tests/test_prefix.py).
    tbt_slo: engine-default inter-token-gap bound (seconds) for the auto
        budget; per-request `tbt_slo` values tighten it.
    finished_window: retain only the most recent N finished/cancelled
        requests (None = unbounded; set for long-running servers so full
        per-request traces don't accumulate forever).

    ``step()`` returns a ``StepEvents`` list (serving/api.py) — the event
    stream is the primary output; ``run_until_drained()`` is a thin compat
    wrapper that drives it and returns the finished-request records.
    """

    def __init__(self, cfg, params, policy: str = "duo", *,
                 max_batch: int = 4, max_seq: int = 128,
                 prefill_budget: Union[int, str, None] = None,
                 prefill_fairness: str = "rr",
                 tbt_slo: Optional[float] = None,
                 finished_window: Optional[int] = None,
                 tbt_window: Optional[int] = 8192,
                 queue: Optional[RequestQueue] = None,
                 role: str = "both",
                 prefix_cache: bool = False,
                 grouped_decode: bool = True,
                 fused_prefill: Optional[bool] = None,
                 stats=None, predictor=None, cache_capacity=None,
                 temperature: float = 0.0, sample_seed: int = 0,
                 spans: Union[bool, SpanRecorder] = False,
                 store=None):
        super().__init__(cfg, params, policy, stats=stats,
                         predictor=predictor, cache_capacity=cache_capacity,
                         temperature=temperature, sample_seed=sample_seed,
                         sched_batch=max_batch,
                         prefill_chunk=(prefill_budget
                                        if isinstance(prefill_budget, int)
                                        else None),
                         fused_prefill=fused_prefill, spans=spans,
                         store=store)
        # grouped_decode=True (default): the batched decode expert sweep is
        # segment-gathered — each distinct expert computes only its
        # selecting rows, one FFN launch per layer (bit-exact vs the dense
        # full-batch path, which False retains as the A/B baseline)
        self.grouped_decode = grouped_decode
        self.decode_step_wall: List[float] = []
        self.max_batch = max_batch
        self.W = max_seq
        if prefill_budget == "auto":
            assert tbt_slo is not None and tbt_slo > 0, \
                'prefill_budget="auto" needs a tbt_slo target'
        else:
            assert prefill_budget is None or prefill_budget >= 1, \
                "prefill_budget must be None, 'auto', or >= 1 token"
        assert prefill_fairness in ("rr", "fifo", "srf")
        self.prefill_budget = prefill_budget
        self.prefill_fairness = prefill_fairness
        self.tbt_slo = tbt_slo
        self.queue = RequestQueue() if queue is None else queue
        self.sample_seed = sample_seed
        hkv, hd = cfg.n_kv_heads, cfg.hd
        self._K = [jnp.zeros((max_batch, max_seq, hkv, hd), PDT)
                   for _ in range(self.L)]
        self._V = [jnp.zeros_like(self._K[l]) for l in range(self.L)]
        self._slot_pos = np.full((max_batch, max_seq), -1, np.int32)
        self._free: List[int] = list(range(max_batch))[::-1]
        # disaggregated-cluster role (serving/cluster.py): "both" serves
        # the full lifecycle; "prefill" HOLDS requests once their prefill
        # completes (state 'held', first token emitted, excluded from the
        # decode batch) until their KV snapshot is handed to a decode
        # replica; "decode" is a handoff TARGET — it can run the full
        # lifecycle if submitted to directly (warm-up), routers just never
        # send it fresh work.
        assert role in ("both", "prefill", "decode"), f"bad role {role!r}"
        self.role = role
        self.prefilling: List[Request] = []   # state='prefilling'
        self.running: List[Request] = []
        self.held: List[Request] = []         # state='held' (role=prefill)
        self.finished: Deque[Request] = collections.deque(
            maxlen=finished_window)
        self.cancelled: Deque[Request] = collections.deque(
            maxlen=finished_window)
        self.tbt = TBTLedger(window=tbt_window)
        # TBT aggregates as PULL gauges off the one ledger (NaN-safe: the
        # sketches report nan until their first gap, which a JSON snapshot
        # must not carry)
        for q, sk in self.tbt.sketches.items():
            self.metrics.gauge(f"tbt_gap_seconds_p{int(q)}_stream",
                               "streaming P2 inter-token-gap percentile",
                               fn=_nan_to_zero(sk.value))
        self.metrics.gauge("tbt_gap_seconds_max",
                           "lifetime maximum inter-token gap",
                           fn=self.tbt.max_gap)
        self.metrics.gauge("tbt_gaps_total",
                           "inter-token gaps observed (lifetime)",
                           fn=lambda: self.tbt.total_gaps)
        self._h_step = self.metrics.histogram(
            "decode_step_seconds", "batched decode step wall time")
        # cross-request prefix/KV reuse (core/prefix.py); prefilled_tokens
        # counts prompt tokens that actually ran through prefill kernels —
        # with hits it is strictly less than the sum of prompt lengths
        self.prefix = PrefixTree() if prefix_cache else None
        self._c_prefilled = self.metrics.counter(
            "engine_prefilled_tokens_total",
            "prompt tokens run through prefill kernels")
        self._next_rid = 0
        self._pf_rr = 0   # round-robin rotation cursor across steps
        self.step_count = 0
        self.decode_batch_hist: List[int] = []

    @property
    def chunked(self) -> bool:
        return self.prefill_budget is not None

    @property
    def idle(self) -> bool:
        """No queued, prefilling, running, or held requests — nothing a
        step() could advance (event consumers use this, not event
        emptiness: prefill-chunk work emits no token). Held requests count:
        they are waiting on an EXTERNAL actor (the cluster handoff loop),
        so a driver must keep polling until they move."""
        return not (self.running or self.prefilling or self.held
                    or len(self.queue))

    @property
    def prefilled_tokens(self) -> int:
        """Thin view over the registry counter (obs-discipline: mutation
        happens only through ``self._c_prefilled.inc``)."""
        return int(self._c_prefilled.value)

    def _current_budget(self) -> Optional[int]:
        """Resolve this step's prefill token budget. Auto mode consults the
        live EWMA cost model (core/qos.py LatencyModel.suggest_chunk)
        against the TIGHTEST in-flight TBT target: the engine default and
        every prefilling/running request's own tbt_slo."""
        if self.prefill_budget is None:
            return None
        if self.prefill_budget == "auto":
            slos = [r.tbt_slo for r in self.running + self.prefilling
                    if r.tbt_slo is not None]
            slos.append(self.tbt_slo)
            return self.queue.admission.model.suggest_chunk(min(slos))
        return self.prefill_budget

    # -- event sink (buffer + _emit/drain_events live in EngineCore) --------
    def _emit_token(self, req: Request, tok: int, t: float,
                    first: bool = False) -> None:
        """THE token sink: every generated token — monolithic prefill,
        final prefill chunk, batched decode — funnels through here, so the
        event stream and the request's token list can never diverge. Also
        the stop-token early-termination point."""
        req.tokens.append(tok)
        if first:
            req.t_first = t
        self.tbt.observe(req.rid, t)
        if req.finish_reason is None and req.params.stop_token_ids \
                and tok in req.params.stop_token_ids:
            req.finish_reason = "stop_token"
        self._emit(TokenEvent(rid=req.rid, token=tok,
                              index=len(req.tokens) - 1, t=t, first=first))

    # -- submission ---------------------------------------------------------
    def submit_request(self, spec: GenerationRequest) -> Request:
        """Submit a typed GenerationRequest; returns the engine's runtime
        Request record (wrap it in a ServingFrontend RequestHandle for the
        streaming/cancellation interface)."""
        prompt = np.asarray(spec.prompt, np.int32).reshape(-1)
        req = Request(rid=self._next_rid, prompt=prompt, params=spec.params,
                      arrival=(time.perf_counter() if spec.arrival is None
                               else spec.arrival),
                      ttft_slo=spec.ttft_slo, tbt_slo=spec.tbt_slo,
                      priority=spec.priority)
        seed = (spec.params.seed if spec.params.seed is not None
                else self.sample_seed + req.rid)
        req.rng = np.random.default_rng(seed)
        need = req.prompt_len + spec.params.max_new_tokens + 1
        assert need <= self.W, f"request needs {need} slots > W={self.W}"
        self._next_rid += 1
        self.queue.submit(req)
        self.obs.instant("request.queued", rid=req.rid,
                         prompt_len=req.prompt_len)
        return req

    def submit(self, prompt: np.ndarray,
               params: Optional[SamplingParams] = None, *,
               max_new: Optional[int] = None,
               arrival: Optional[float] = None,
               ttft_slo: Optional[float] = None,
               tbt_slo: Optional[float] = None,
               priority: int = 0,
               temperature: Optional[float] = None) -> Request:
        """Compat sugar over `submit_request`: legacy `max_new=` /
        `temperature=` kwargs are folded into a SamplingParams."""
        if params is None:
            params = SamplingParams(
                temperature=temperature,
                max_new_tokens=16 if max_new is None else max_new)
        else:
            assert max_new is None and temperature is None, \
                "pass sampling via params OR legacy kwargs, not both"
        return self.submit_request(GenerationRequest(
            prompt=np.asarray(prompt, np.int32).reshape(-1), params=params,
            ttft_slo=ttft_slo, tbt_slo=tbt_slo, priority=priority,
            arrival=arrival))

    # -- cancellation -------------------------------------------------------
    def cancel(self, req: Request, reason: str = "cancelled") -> bool:
        """Cancel a request mid-flight. Synchronous and idempotent: on the
        first call a queued request is dequeued; a prefilling/running one is
        removed from its phase list, its KV slot returns to the free pool,
        its expert-residency contributions are dropped from the shared
        ledger (entries no other in-flight request also touched), and its
        TBT-ledger entry closes. One final ``FinishEvent(reason)`` is
        emitted; the request NEVER emits again. Returns False if already
        terminal.

        reason: recorded as the request's finish_reason — "cancelled"
        (caller-initiated, the default) or "slo_shed" (the QosAutopilot
        shedding a request whose TTFT/TBT deadline is already unmeetable,
        serving/cluster.py). Reclamation is identical for both."""
        if req.state in ("done", "rejected", "cancelled"):
            return False
        if req.state == "paused":
            # the engine holds NOTHING for a paused request — its life is
            # in a host-side RequestSnapshot; the snapshot's owner (the
            # frontend/autopilot) terminates it
            return False
        if req.state == "queued":
            if not self.queue.remove(req):
                return False
        elif req.state in ("prefilling", "running", "held"):
            {"prefilling": self.prefilling, "running": self.running,
             "held": self.held}[req.state].remove(req)
            self._release_expert_contributions(req)
            self._release_slot(req)
        else:  # pragma: no cover - unknown state is a bug
            raise AssertionError(f"cancel from state {req.state!r}")
        req.state = "cancelled"
        req.finish_reason = reason
        req.t_done = time.perf_counter()
        req.pf_k = req.pf_v = req.pf_sp = None
        req.active_sets = None
        self.tbt.close(req.rid)
        self.cancelled.append(req)
        self.obs.terminal(req.rid, reason, n_tokens=len(req.tokens))
        self._emit(FinishEvent(rid=req.rid, reason=reason,
                               n_tokens=len(req.tokens), t=req.t_done))
        return True

    @property
    def n_slo_shed(self) -> int:
        """Requests the autopilot shed mid-flight (within the retained
        `cancelled` window) — the engine-side ledger count of
        FinishEvent(reason="slo_shed") terminations."""
        return sum(1 for r in self.cancelled
                   if r.finish_reason == "slo_shed")

    # -- load introspection (cluster routing, serving/cluster.py) -----------
    def load(self) -> ReplicaLoad:
        """Snapshot this engine's outstanding work as a ReplicaLoad
        (core/qos.py): what the routers rank replicas by. Decode backlog
        counts every token THIS engine is still committed to produce —
        running requests' remaining budget plus prefilling requests' full
        budget (their decode work hasn't started). On a role='prefill'
        replica the decode work happens elsewhere after handoff, so held
        requests and prefilling requests' decode budgets are excluded.
        Host-PAUSED requests released every resource here and appear in no
        field — load (and every headroom computed from it) never charges
        them."""
        dec = sum(r.max_new + 1 - len(r.tokens) for r in self.running)
        if self.role != "prefill":
            dec += sum(r.max_new + 1 for r in self.prefilling)
        return ReplicaLoad(
            queue_depth=len(self.queue),
            queued_tokens=self.queue.queued_tokens(),
            prefill_backlog=sum(r.prefill_remaining
                                for r in self.prefilling),
            running=len(self.running),
            decode_backlog=dec,
            free_slots=len(self._free) + (self.prefix.n_reclaimable()
                                          if self.prefix is not None else 0),
            held=len(self.held))

    @property
    def slot_available(self) -> bool:
        """A KV slot could be handed out right now — free, or reclaimable
        from tree-owned prefix cache by LRU eviction."""
        return bool(self._free) or (self.prefix is not None
                                    and self.prefix.n_reclaimable() > 0)

    def _acquire_slot(self) -> int:
        """Pop a free KV slot, evicting tree-owned cached prefixes (LRU)
        to reclaim one when the free list is empty."""
        if not self._free and self.prefix is not None:
            self._free.extend(self.prefix.evict_for(1))
        return self._free.pop()

    def _release_slot(self, req: Request) -> None:
        self._slot_pos[req.slot, :] = -1
        if self.prefix is not None:
            if self.prefix.slot_released(req.slot):
                # the tree still references this slot's rows: the slot
                # becomes tree-owned prefix cache instead of returning to
                # the free list (reclaimed by _acquire_slot's LRU eviction)
                return
        self._free.append(req.slot)

    # -- cross-request prefix/KV reuse (core/prefix.py) ----------------------
    def _prefix_peek(self, req: Request) -> int:
        """Read-only longest cached prefix usable by `req`, capped at
        prompt_len - 1 so the final prompt position always prefills (its
        logits produce the first token). Admission charges only the
        remainder."""
        if self.prefix is None or req.prompt_len < 2:
            return 0
        return self.prefix.peek(req.prompt, limit=req.prompt_len - 1)

    def _prefix_match(self, req: Request):
        """Acquire the longest cached prefix for an admitted request: pins
        the tree path (the caller releases it once the rows are copied
        out) and returns (n_hit, the (slot, lo, hi) row blocks to copy).
        ``req.prefix_len`` records the hit as a per-request stat."""
        if self.prefix is None or req.prompt_len < 2:
            return 0, []
        n_hit, blocks = self.prefix.match(req.prompt,
                                          limit=req.prompt_len - 1)
        req.prefix_len = n_hit
        return n_hit, blocks

    def _prefix_insert(self, req: Request) -> None:
        """Offer the request's full prompt KV (now resident in its slot,
        rows 0..S-1) to the tree — called at every point a prompt's KV
        lands in the slot pool: monolithic admit, final prefill chunk,
        and 'running' restore."""
        if self.prefix is not None and req.prompt_len:
            self.prefix.insert(req.prompt, req.slot)

    def _seeded_pf(self, n_hit: int, blocks):
        """Fresh per-request prefill carry buffers with rows [0, n_hit)
        seeded from the tree's slot-pool blocks. Slot row == absolute
        position (the ring never wraps), so the copy is row-for-row and
        bit-identical to what cold prefill would have written."""
        hkv, hd = self.cfg.n_kv_heads, self.cfg.hd
        pf_k = [jnp.zeros((1, self.W, hkv, hd), PDT) for _ in range(self.L)]
        pf_v = [jnp.zeros_like(pf_k[l]) for l in range(self.L)]
        for l in range(self.L):
            for s, a, b in blocks:
                pf_k[l] = pf_k[l].at[0, a:b].set(self._K[l][s, a:b])
                pf_v[l] = pf_v[l].at[0, a:b].set(self._V[l][s, a:b])
        sp = np.full((1, self.W), -1, np.int32)
        if n_hit:
            sp[0, :n_hit] = np.arange(n_hit, dtype=np.int32)
        return pf_k, pf_v, jnp.asarray(sp)

    def _release_expert_contributions(self, req: Request) -> None:
        """Drop the cancelled request's expert-residency contributions: the
        (layer, expert) entries ITS prefill chunks / last decode step
        touched, minus anything another in-flight request also touched (a
        survivor's working set must not be yanked — dropping it would only
        cost refetches, never correctness, but the point of cancelling is
        to FREE budget, not to churn it). Pins are step-scoped (every plan
        path end_layer()s before the step returns), so between steps —
        where cancellation runs — all of the request's entries are
        unpinned; the pinned check is defensive."""
        def touched(r: Request) -> Set[ExpertKey]:
            keys: Set[ExpertKey] = set()
            if r.active_sets is not None:          # mid-prefill
                for l, s in enumerate(r.active_sets):
                    keys |= {(l, int(e)) for e in s}
            for l, acts in enumerate(r.prefill_active):
                keys |= {(l, int(e)) for e in acts}
            if r.trace:                            # last decode step
                for l in range(self.L):
                    keys |= {(l, int(e)) for e in r.trace[-1][l]}
            return keys

        mine = touched(req)
        for other in self.prefilling + self.running + self.held:
            if other is not req:
                mine -= touched(other)
        for key in mine:
            if self.cache.contains(key) and not self.cache.resident[key]:
                self.cache.drop(key)

    # -- snapshot / restore (pause, handoff, migration primitive) -----------
    def find_request(self, rid: int) -> Optional[Request]:
        """The live (queued/prefilling/running/held) request with id `rid`,
        or None — terminal and paused requests are not live here."""
        for r in (list(self.queue.pending) + self.prefilling
                  + self.running + self.held):
            if r.rid == rid:
                return r
        return None

    def prefix_score(self, prompt, limit: Optional[int] = None) -> int:
        """Router scoring signal (cluster prefix_affinity): the longest
        leading run of `prompt` this engine could serve from cache BY THE
        TIME the request would prefill — the tree's current contents PLUS
        the prompts of every live request (queued/prefilling/running/held
        work is KV the tree will hold before a new arrival is admitted
        behind it). Read-only; 0 without a prefix tree."""
        if self.prefix is None:
            return 0
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        cap = (int(prompt.shape[0]) if limit is None
               else min(int(limit), int(prompt.shape[0])))
        if cap <= 0:
            return 0
        best = self.prefix.peek(prompt, limit=cap)
        for r in (list(self.queue.pending) + self.prefilling
                  + self.running + self.held):
            n = min(cap, r.prompt_len)
            if n <= best:
                continue
            neq = np.nonzero(r.prompt[:n] != prompt[:n])[0]
            best = max(best, int(neq[0]) if neq.size else n)
        return best

    def prefix_head_for(self, req: Request) -> int:
        """How many leading KV rows of `req` THIS engine could rebuild
        from its own prefix tree — the shared head a tail-only handoff
        (``other.snapshot(req, kv_start=head)`` -> ``self.restore``) need
        not ship host-side. Capped at the prompt region actually captured
        (the tree only caches prompt rows)."""
        if self.prefix is None or req.state == "queued":
            return 0
        have = (req.prefill_pos if req.state == "prefilling" else req.pos)
        cap = min(req.prompt_len, have)
        if cap <= 0:
            return 0
        return self.prefix.peek(req.prompt, limit=cap)

    def snapshot(self, req: Union[Request, int], *,
                 kv_start: int = 0) -> RequestSnapshot:
        """Pause a live request and capture it as a host-side, engine-
        portable ``RequestSnapshot`` (serving/api.py).

        The KV prefix is gathered host-side from the request's slot (or,
        mid-prefill, from its chunk-carry buffers) as a DENSE array — the
        ring never wraps (``need <= W`` is asserted at submission), so ring
        slot == absolute position and row p is position p. Resource
        reclamation is exactly ``cancel()``'s: the KV slot returns to the
        free pool, expert-residency contributions no other in-flight
        request touched are dropped, and the TBT-ledger entry closes (so
        paused wall time is never charged as an inter-token gap — see
        ``TBTLedger.reopen``). Unlike cancel, NO FinishEvent is emitted:
        the request is not terminal, it is host-side; its state becomes
        'paused' and this engine never references it again. A ``held``
        request snapshots with state='running' — prefill is complete, any
        decode-capable engine resumes it straight into its batch.

        ``kv_start`` > 0 makes the snapshot TAIL-ONLY: the dense KV arrays
        cover positions ``[kv_start, P)`` and the destination rebuilds the
        shared head ``[0, kv_start)`` from its OWN prefix tree at restore
        (``ReplicaPool.migrate`` picks kv_start via the destination's
        ``prefix_head_for``). The head must lie inside the prompt region —
        only prompt rows are reconstructible from a prefix tree."""
        if isinstance(req, int):
            found = self.find_request(req)
            assert found is not None, f"no live request with rid {req}"
            req = found
        assert req.state in ("queued", "prefilling", "running", "held"), \
            f"snapshot from state {req.state!r}"
        assert not req.done, "snapshot of a finished request"
        spec = GenerationRequest(
            prompt=req.prompt, params=req.params, ttft_slo=req.ttft_slo,
            tbt_slo=req.tbt_slo, priority=req.priority, arrival=req.arrival)
        kv_k: List[np.ndarray] = []
        kv_v: List[np.ndarray] = []
        if req.state == "queued":
            assert kv_start == 0, "a queued snapshot carries no KV"
            ok = self.queue.remove(req)
            assert ok, "queued request not in its queue"
            state = "queued"
        elif req.state == "prefilling":
            P = req.prefill_pos
            assert 0 <= kv_start <= min(P, req.prompt_len), \
                f"kv_start {kv_start} outside captured prompt region"
            for l in range(self.L):
                kv_k.append(np.asarray(req.pf_k[l][0, kv_start:P]))
                kv_v.append(np.asarray(req.pf_v[l][0, kv_start:P]))
            self.prefilling.remove(req)
            self._release_expert_contributions(req)
            self._release_slot(req)
            state = "prefilling"
        else:
            # running/held: positions 0..pos-1 are written (the latest
            # token's KV lands when IT is decoded, not when sampled)
            P = req.pos
            assert 0 <= kv_start <= min(P, req.prompt_len), \
                f"kv_start {kv_start} outside captured prompt region"
            for l in range(self.L):
                kv_k.append(np.asarray(self._K[l][req.slot, kv_start:P]))
                kv_v.append(np.asarray(self._V[l][req.slot, kv_start:P]))
            (self.running if req.state == "running"
             else self.held).remove(req)
            self._release_expert_contributions(req)
            self._release_slot(req)
            state = "running"
        snap = RequestSnapshot(
            spec=spec, state=state, tokens=list(req.tokens),
            kv_k=kv_k, kv_v=kv_v, prefill_pos=req.prefill_pos,
            active_sets=([sorted(int(e) for e in s)
                          for s in req.active_sets]
                         if req.active_sets is not None else None),
            prefill_active=[list(map(int, a)) for a in req.prefill_active],
            trace=list(req.trace), pred=list(req.pred),
            hits=req.hits, misses=req.misses,
            t_start=req.t_start, t_first=req.t_first,
            tbt_gaps=list(self.tbt.by_rid.get(req.rid, ())),
            rng_state=(req.rng.bit_generator.state
                       if req.rng is not None else None),
            # the obs monotonic clock — the SAME source the destination
            # stamps t_restore with (serving/frontend.py), so handoff
            # latency can never go negative under wall-clock adjustment
            source_rid=req.rid, t_snapshot=monotonic(),
            kv_start=kv_start)
        self.tbt.close(req.rid)
        self.obs.instant("request.paused", rid=req.rid,
                         kv_bytes=snap.kv_bytes, state=state)
        req.state = "paused"
        req.slot = -1
        req.pf_k = req.pf_v = req.pf_sp = None
        req.active_sets = None
        return snap

    def can_restore(self, snap: RequestSnapshot) -> bool:
        """Whether ``restore(snap)`` would succeed right now: the request
        fits a KV slot (always true for a still-queued snapshot), mid-
        prefill this engine can run chunked prefill, and a tail-only
        snapshot's shared head is present in this engine's prefix tree."""
        prompt = np.asarray(snap.spec.prompt).reshape(-1)
        need = int(prompt.shape[0]) + snap.spec.params.max_new_tokens + 1
        if need > self.W:
            return False
        if snap.kv_start and (
                self.prefix is None
                or self.prefix.peek(prompt,
                                    limit=snap.kv_start) < snap.kv_start):
            return False
        if snap.state == "queued":
            return True
        return self.slot_available and \
            (snap.state != "prefilling" or self.chunked)

    def restore(self, snap: RequestSnapshot) -> Request:
        """Resume a snapshot on THIS engine as a fresh request (new rid —
        rids stay engine-local and monotonic; cluster consumers track the
        HANDLE, which the frontend rebinds). The carried rng state, token
        list, and KV prefix make the continuation bit-exact: the dense KV
        rows scatter into a free slot at positions ``0..P-1`` and every
        later ring position stays -1, which the attention mask weights to
        exactly zero — stale slot contents cannot leak in. A 'running'
        snapshot joins the decode batch (or this replica's held list if it
        is itself role='prefill'); a 'prefilling' one resumes chunking from
        ``prefill_pos``; a 'queued' one simply re-enqueues. The TBT ledger
        reopens WITHOUT a baseline, so the pause is never charged as a
        gap."""
        spec = snap.spec
        req = Request(rid=self._next_rid,
                      prompt=np.asarray(spec.prompt, np.int32).reshape(-1),
                      params=spec.params, arrival=spec.arrival,
                      ttft_slo=spec.ttft_slo, tbt_slo=spec.tbt_slo,
                      priority=spec.priority)
        need = req.prompt_len + req.max_new + 1
        assert need <= self.W, \
            f"restored request needs {need} slots > W={self.W}"
        self._next_rid += 1
        req.rng = np.random.default_rng()
        if snap.rng_state is not None:
            req.rng.bit_generator.state = snap.rng_state
        req.tokens = list(snap.tokens)
        req.trace = list(snap.trace)
        req.pred = list(snap.pred)
        req.hits, req.misses = snap.hits, snap.misses
        req.t_start, req.t_first = snap.t_start, snap.t_first
        req.prefill_active = [list(a) for a in snap.prefill_active]
        if snap.state == "queued":
            req.state = "queued"
            self.queue.submit(req)
            self.obs.instant("request.restored", rid=req.rid,
                             source_rid=snap.source_rid, state="queued")
            return req
        # tail-only snapshot: rebuild the shared head [0, kv_start) from
        # THIS engine's prefix tree. Match (and pin) the head path BEFORE
        # acquiring a slot — _acquire_slot may evict tree-owned cache, and
        # a pinned path is never evicted from under us; the pin drops as
        # soon as the head rows are copied below.
        head = snap.kv_start
        blocks: List = []
        if head:
            assert self.prefix is not None, \
                "tail-only snapshot needs a prefix tree on the target"
            n_hit, blocks = self.prefix.match(req.prompt, limit=head)
            assert n_hit == head, \
                f"target lost the shared head: have {n_hit} of {head} rows"
            req.prefix_len = head
        assert self.slot_available, "no free KV slot to restore into"
        slot = self._acquire_slot()
        req.slot = slot
        self._slot_pos[slot, :] = -1
        if snap.state == "prefilling":
            assert self.chunked, "mid-prefill restore needs a chunked engine"
            P = snap.prefill_pos
            req.state = "prefilling"
            req.prefill_pos = P
            req.active_sets = [set(s) for s in snap.active_sets]
            req.pf_k, req.pf_v, _ = self._seeded_pf(head, blocks)
            for l in range(self.L):
                if P > head:
                    req.pf_k[l] = req.pf_k[l].at[0, head:P].set(
                        jnp.asarray(snap.kv_k[l], PDT))
                    req.pf_v[l] = req.pf_v[l].at[0, head:P].set(
                        jnp.asarray(snap.kv_v[l], PDT))
            sp = np.full((1, self.W), -1, np.int32)
            sp[0, :P] = np.arange(P, dtype=np.int32)
            req.pf_sp = jnp.asarray(sp)
            self.prefilling.append(req)
        else:
            assert snap.state == "running", f"bad state {snap.state!r}"
            P = req.pos
            for l in range(self.L):
                K, V = self._K[l], self._V[l]
                for s, a, b in blocks:
                    K = K.at[slot, a:b].set(self._K[l][s, a:b])
                    V = V.at[slot, a:b].set(self._V[l][s, a:b])
                if P > head:
                    K = K.at[slot, head:P].set(jnp.asarray(snap.kv_k[l],
                                                           PDT))
                    V = V.at[slot, head:P].set(jnp.asarray(snap.kv_v[l],
                                                           PDT))
                self._K[l], self._V[l] = K, V
            self._slot_pos[slot, :P] = np.arange(P, dtype=np.int32)
            req.prefill_pos = req.prompt_len
            self._prefix_insert(req)
            self._finish_prefill(req)   # running, or held on role='prefill'
        if head:
            self.prefix.release(req.prompt, head)   # head rows are copied
        self.tbt.reopen(req.rid, snap.tbt_gaps)
        self.obs.instant("request.restored", rid=req.rid,
                         source_rid=snap.source_rid, state=req.state)
        return req

    # -- prefill phase ------------------------------------------------------
    def _admit_and_prefill(self, now: float) -> List[Request]:
        """Admit queue arrivals into free KV slots.

        Monolithic mode (prefill_budget=None): each admitted request
        prefills fully, right here, exactly as before chunking existed.
        Chunked mode: the request only transitions to 'prefilling'; chunk
        execution happens in `_prefill_work` under the step's token budget.
        """
        n_rej = len(self.queue.rejected)
        backlog = sum(r.prefill_remaining for r in self.prefilling)
        free_now = len(self._free) + (self.prefix.n_reclaimable()
                                      if self.prefix is not None else 0)
        newly = self.queue.pop_admissible(
            now, limit=free_now, backlog_tokens=backlog,
            running_batch=len(self.running),
            chunk_budget=self._current_budget(),
            chunk_adaptive=self.prefill_budget == "auto",
            hit_fn=(self._prefix_peek if self.prefix is not None else None))
        for r in self.queue.rejected[n_rej:]:
            self.obs.terminal(r.rid, "rejected", reason_detail="slo")
            self._emit(RejectEvent(rid=r.rid, reason="slo",
                                   t=time.perf_counter()))
        for req in newly:
            req.t_start = now
            self.obs.instant("request.admitted", rid=req.rid)
            # longest cached prefix (capped at S-1): match pins the path
            # only while its rows are copied into fresh carry buffers —
            # once seeded, the pin drops so _acquire_slot below may evict
            # ANY tree-owned slot, including the donor's (at max_batch=1
            # the hit's own donor slot is exactly the one reclaimed)
            n_hit, blocks = self._prefix_match(req)
            pf_k = pf_v = pf_sp = None
            if n_hit:
                pf_k, pf_v, pf_sp = self._seeded_pf(n_hit, blocks)
                self.prefix.release(req.prompt, n_hit)
            slot = self._acquire_slot()
            req.slot = slot
            self._slot_pos[slot, :] = -1
            if self.chunked:
                req.state = "prefilling"
                req.prefill_pos = n_hit
                req.active_sets = [set() for _ in range(self.L)]
                if pf_k is None:
                    pf_k, pf_v, pf_sp = self._seeded_pf(0, [])
                req.pf_k, req.pf_v, req.pf_sp = pf_k, pf_v, pf_sp
                self.prefilling.append(req)
                continue
            req.state = "running"
            t0 = time.perf_counter()
            S = req.prompt_len
            pt = self.obs.begin("prefill", lane="prefill", rid=req.rid,
                                tokens=S - n_hit)
            if n_hit:
                # monolithic engine with a hit: run the un-hit suffix as
                # ONE whole chunk over the seeded carry buffers — the
                # chunked==monolithic exactness invariant makes the tokens
                # bit-identical to a cold whole-prompt prefill
                logits, pf_k, pf_v, pf_sp, active, _ = self.prefill_chunk(
                    req.prompt[None, n_hit:], n_hit, pf_k, pf_v, pf_sp,
                    need_logits=True)
                for l in range(self.L):
                    self._K[l] = self._K[l].at[slot, :S].set(pf_k[l][0, :S])
                    self._V[l] = self._V[l].at[slot, :S].set(pf_v[l][0, :S])
                active = [sorted(set(a)) for a in active]
            else:
                logits, (kc, vc), active, _ = self.prefill_layers(
                    req.prompt.reshape(1, -1))
                for l in range(self.L):
                    self._K[l] = self._K[l].at[slot, :S].set(kc[l][0])
                    self._V[l] = self._V[l].at[slot, :S].set(vc[l][0])
            self._slot_pos[slot, :S] = np.arange(S, dtype=np.int32)
            req.prefill_pos = S
            req.prefill_active = active
            self._c_prefilled.inc(S - n_hit)
            self._prefix_insert(req)
            st = self.obs.begin("sample", lane="prefill", rid=req.rid)
            tok = self._sample_req(req, logits[0])
            self.obs.end(st)
            self._emit_token(req, tok, time.perf_counter(), first=True)
            self.queue.admission.model.observe_prefill(S - n_hit,
                                                       req.t_first - t0)
            self.obs.end(pt)
            self._finish_prefill(req)
        return newly

    def _finish_prefill(self, req: Request) -> None:
        """Prefill done, first token emitted: a role='prefill' replica
        HOLDS the request (state 'held', out of the decode batch) until the
        cluster hands its KV snapshot to a decode replica; every other role
        joins this step's decode batch."""
        if self.role == "prefill":
            req.state = "held"
            self.held.append(req)
        else:
            req.state = "running"
            self.running.append(req)

    def _run_prefill_chunks(self, work: List[Tuple[Request, int]]) -> None:
        """Advance each 'prefilling' request of ``work`` by its C-token
        chunk, all of them in ONE sweep through the layers.

        The chunks run through `EngineCore.prefill_chunks` directly against
        each request's KV carry: a chunk attends over its request's
        already-written prefix and appends its own K/V, and the scheduler
        sees the step's chunks together through one per-layer
        `prefill_plan`, so each active expert is copied once per step, not
        once per request. When a request's final chunk completes, its first
        token is sampled — exactly the token monolithic prefill would have
        produced — and it joins this same step's decode batch (like a
        monolithically prefilled arrival).
        """
        t0 = time.perf_counter()
        tokens = sum(C for _, C in work)
        pt = self.obs.begin("prefill.chunk", lane="prefill",
                            rids=[req.rid for req, _ in work], tokens=tokens)
        chunks = []
        for req, C in work:
            start, stop = req.prefill_pos, req.prefill_pos + C
            chunks.append(PrefillChunk(
                req.prompt[None, start:stop], start, req.pf_k, req.pf_v,
                req.pf_sp, need_logits=stop == req.prompt_len))
        outs = self.prefill_chunks(chunks)
        self._c_prefilled.inc(tokens)
        self.queue.admission.model.observe_prefill(
            tokens, time.perf_counter() - t0)
        finals = []
        for (req, C), c, (logits, act, _) in zip(work, chunks, outs):
            req.pf_sp = c.sp   # its kc/vc lists were advanced in place
            for l in range(self.L):
                req.active_sets[l].update(act[l])
            req.prefill_pos += C
            if not c.need_logits:
                continue
            finals.append(req.rid)
            # one scatter into the slot pool for the whole prompt
            slot = req.slot
            self.obs.instant("kv.scatter", lane="prefill", rid=req.rid,
                             rows=req.prompt_len)
            for l in range(self.L):
                self._K[l] = self._K[l].at[slot].set(req.pf_k[l][0])
                self._V[l] = self._V[l].at[slot].set(req.pf_v[l][0])
            self._slot_pos[slot] = np.asarray(req.pf_sp[0])
            req.pf_k = req.pf_v = req.pf_sp = None
            req.prefill_active = [sorted(s) for s in req.active_sets]
            req.active_sets = None
            self._prefix_insert(req)
            st = self.obs.begin("sample", lane="prefill", rid=req.rid)
            tok = self._sample_req(req, logits[0])
            self.obs.end(st)
            self._emit_token(req, tok, time.perf_counter(), first=True)
            self.prefilling.remove(req)
            self._finish_prefill(req)
        self.obs.end(pt, finals=finals)

    def _prefill_work(self) -> int:
        """Spend up to this step's prefill budget advancing 'prefilling'
        requests (stall-free interleaving). Returns tokens of work done.

        Fairness: "fifo" always serves the head request (a long prompt
        monopolizes prefill until done — a short prompt behind it waits for
        EVERY earlier prefill to complete). "rr" rotates which prefilling
        request receives the step's budget, so overlapping prompts make
        interleaved progress and a short arrival's TTFT is bounded by
        ~n_prefilling * (len/budget) steps instead of the whole backlog.
        "srf" orders by `prefill_remaining` (shortest first, rid tiebreak):
        a short straggler overtakes every long backlog immediately — the
        best straggler TTFT of the three — while the longest prompt pays
        for everyone that overtook it (bench_stall --fairness compares all
        modes). In every mode the budget goes to one request at a time
        (spilling to the next in order when it finishes early) rather than
        being split — chunk shapes stay constant, so the chunked-prefill
        kernels compile once per budget, not once per (budget/n) share. The
        step's chunks then run as one sweep (`_run_prefill_chunks`)."""
        if not self.chunked:
            return 0  # monolithic mode: prefill happened at admission
        budget = self._current_budget()
        spent = 0
        if self.prefilling and self.prefill_fairness == "rr":
            rot = self._pf_rr % len(self.prefilling)
            self._pf_rr += 1
            order = self.prefilling[rot:] + self.prefilling[:rot]
        elif self.prefilling and self.prefill_fairness == "srf":
            # shortest-remaining-first: deterministic (rid tiebreak), and
            # re-sorted every step so progress keeps the order current
            order = sorted(self.prefilling,
                           key=lambda r: (r.prefill_remaining, r.rid))
        else:
            order = list(self.prefilling)  # fifo: head-of-line
        work: List[Tuple[Request, int]] = []
        for req in order:
            if budget <= 0:
                break
            C = min(budget, req.prefill_remaining)
            work.append((req, C))
            spent += C
            budget -= C
        if work:
            self._run_prefill_chunks(work)
        return spent

    def _sample_req(self, req: Request, logits_row) -> int:
        temp = (self.temperature if req.temperature is None
                else req.temperature)
        return self.sample_row(np.asarray(logits_row, np.float64), temp,
                               req.rng)

    # -- decode phase -------------------------------------------------------
    def _decode_step(self, batch: List[Request]) -> None:
        """One batched decode step: every request advances by one token.

        Per-row accumulation follows each request's own top-k order, so the
        result is bit-identical to B independent single-request steps —
        on BOTH expert-execution disciplines (grouped_decode segment-gather
        default, dense full-batch baseline).
        Output goes through the `_emit_token` event sink.
        """
        B = len(batch)
        t0 = time.perf_counter()
        dt = self.obs.begin("decode.step", lane="decode", batch=B)
        self.cache.phase = "decode"
        idx = np.asarray([r.slot for r in batch], np.int32)
        toks = np.asarray([[r.tokens[-1]] for r in batch], np.int32)
        pos_np = np.asarray([r.pos for r in batch], np.int32)
        slot_np = pos_np % self.W
        rows = np.arange(B)
        for b in range(B):
            self._slot_pos[idx[b], slot_np[b]] = pos_np[b]
        sp = jnp.asarray(self._slot_pos[idx])
        pos = jnp.asarray(pos_np)
        slot = jnp.asarray(slot_np)
        jidx = jnp.asarray(idx)

        x = self.dev["embed"].at[jnp.asarray(toks)].get(mode="clip")
        if isinstance(self.sched, DuoServeScheduler):
            self.sched.begin_decode_step()
        step_trace = np.zeros((B, self.L, self.k), np.int32)
        step_pred = np.full((B, self.L, self.k), -1, np.int32)
        for l in range(self.L):
            at = self.obs.begin("attention", lane="decode", layer=l)
            lp = self._layer(l)
            ck = self._K[l][jidx]
            cv = self._V[l][jidx]
            x, ck, cv = self._attn_decode_batched(lp, x, ck, cv, sp, slot,
                                                  pos)
            self._K[l] = self._K[l].at[jidx].set(ck)
            self._V[l] = self._V[l].at[jidx].set(cv)
            self.obs.end(at)
            rt = self.obs.begin("moe.route", lane="decode", layer=l)
            xn, w, ids = self._gate(self._moe_dev(l), lp, x)
            ids_np = np.asarray(ids).reshape(B, self.k)
            step_trace[:, l] = ids_np
            selections = [list(map(int, ids_np[b])) for b in range(B)]
            plan = self.sched.decode_plan(l, selections)
            self.obs.end(rt)
            # hits + misses together cover exactly the distinct selections
            union = plan.hits + plan.misses
            np_pred = plan.predicted[: self.k]
            step_pred[:, l, : len(np_pred)] = np_pred
            # correction fetches for misses (sync point #1), once per expert
            if plan.misses:
                ct = self.obs.begin("prefetch.correction", lane="prefetch",
                                    layer=l, n=len(plan.misses))
                for e in plan.misses:
                    self.cache.prefetch((l, e))
                    self.cache.wait((l, e))
                self.obs.end(ct)
            hit_set, miss_set = set(plan.hits), set(plan.misses)
            for b, r in enumerate(batch):
                r.hits += len(set(selections[b]) & hit_set)
                r.misses += len(set(selections[b]) & miss_set)
            self.perf.inc("decode_layers")
            self.perf.inc("decode_rows_dense", len(union) * B)
            ct = self.obs.begin("moe.combine", lane="decode", layer=l)
            acc = self._shared(self._moe_dev(l), xn)
            if union and self.grouped_decode:
                # segment-gathered sweep: ONE launch computes only each
                # expert's selecting rows ([U, C, d] instead of U x [B, d]),
                # slots resolved in one vectorized pass; the scatter-back
                # walks j = 0..k-1 so every row still accumulates in its
                # OWN top-k order — bit-identical to the dense path below
                disp = group_by_expert(ids_np, union, bucket_cap=B,
                                       u_bucket_cap=min(self.E, B * self.k))
                raw_g = self._grouped_ffn_raw(l, union, xn, disp.row_idx)
                self.perf.inc("decode_ffn_launches")
                self.perf.inc("decode_rows_grouped", disp.n_rows)
                self.perf.inc("decode_rows_launched", disp.n_launched)
                for j in range(self.k):
                    y = raw_g[jnp.asarray(disp.u_of[:, j]),
                              jnp.asarray(disp.c_of[:, j])]  # f32 [B, d]
                    acc = acc + (y * w[:, j, None]).astype(acc.dtype)
            elif union:
                # dense full-batch baseline: one pre-gate output per
                # DISTINCT expert, each over all B rows, read by slot index
                # out of the shared residency pools (pools re-read after
                # every slot(): a pending transfer swaps in a fresh pool
                # array object)
                raw: Dict[int, jnp.ndarray] = {}
                for e in union:
                    eslot = jnp.int32(self.cache.slot((l, e)))
                    raw[e] = self._expert_raw(xn, *self.cache.pools,
                                              eslot)  # f32 [B, d]
                self.perf.inc("decode_ffn_launches", len(union))
                self.perf.inc("decode_rows_launched", len(union) * B)
                stacked = jnp.stack([raw[e] for e in union])  # [U, B, d]
                inv = np.zeros(self.E, np.int32)
                for u, e in enumerate(union):
                    inv[e] = u
                for j in range(self.k):
                    # j-th choice of every row, in that row's own top-k order
                    y = stacked[jnp.asarray(inv[ids_np[:, j]]), rows]
                    acc = acc + (y * w[:, j, None]).astype(acc.dtype)
            x = x + acc.reshape(x.shape)
            self.obs.end(ct)
            # prediction stream: prefetch layer l+1's experts for the batch
            if plan.prefetch_next:
                self.obs.instant("prefetch.dispatch", lane="prefetch",
                                 layer=l, n=len(plan.prefetch_next))
            for e in plan.prefetch_next:
                self.cache.prefetch((l + 1, e))
        # unpin the successor-less last layer (see MoEServingEngine.decode):
        # without this, a continuously batching engine (which never calls
        # begin_request) accumulates pinned (L-1, e) entries forever
        self.sched.end_layer(self.L - 1)
        logits = self._head(self.dev["ln_f"], self.dev["embed"], x[:, -1])
        st = self.obs.begin("sample", lane="decode", batch=B)
        lg_np = np.asarray(logits, np.float64)
        t_tok = time.perf_counter()
        sampled = [self._sample_req(r, lg_np[b]) for b, r in enumerate(batch)]
        self.obs.end(st)
        for b, r in enumerate(batch):
            self._emit_token(r, sampled[b], t_tok)
            r.trace.append(step_trace[b])
            r.pred.append(step_pred[b])
        self.queue.admission.model.observe_decode_step(t_tok - t0)
        self.decode_step_wall.append(t_tok - t0)
        self.decode_batch_hist.append(B)
        self._h_step.observe(t_tok - t0)
        self.obs.end(dt, batch=B)

    # -- scheduler loop -----------------------------------------------------
    def step(self, now: Optional[float] = None) -> StepEvents:
        """One engine iteration: admit new arrivals, spend the prefill token
        budget on chunked prefill work (monolithic when prefill_budget is
        None), then one batched decode step for all in-flight requests.

        Returns the step's event stream (StepEvents): TokenEvents for every
        token generated this step, FinishEvents for requests retired this
        step (plus any cancellations since the last step), RejectEvents for
        admission sheds. `events.did_work` is True if any work was done —
        use it (not event-list truthiness) for idle detection."""
        now = time.perf_counter() if now is None else now
        admitted = self._admit_and_prefill(now)
        prefilled = self._prefill_work()
        batch = [r for r in self.running if not r.done]
        if batch:
            self._decode_step(batch)
        did_work = bool(admitted or prefilled or batch)
        self.step_count += 1
        # retire finished requests, free their slots (held requests can
        # finish at their FIRST token — stop token or max_new_tokens=0 —
        # without ever reaching a decode replica)
        self.running = [r for r in self.running if not self._retire(r)]
        self.held = [r for r in self.held if not self._retire(r)]
        return StepEvents(self.drain_events(), did_work)

    def _retire(self, r: Request) -> bool:
        if not r.done:
            return False
        r.state = "done"
        if r.finish_reason is None:
            r.finish_reason = "length"
        r.t_done = time.perf_counter()
        self._release_slot(r)
        self.finished.append(r)
        self.tbt.close(r.rid)
        self.obs.terminal(r.rid, r.finish_reason, n_tokens=len(r.tokens))
        self._emit(FinishEvent(rid=r.rid, reason=r.finish_reason,
                               n_tokens=len(r.tokens), t=r.t_done))
        return True

    def run_until_drained(self, max_steps: int = 10_000) -> Deque[Request]:
        """Thin compat wrapper over the event stream: drive step() until
        queue + prefilling + running are all empty, discarding the events
        (every token is still recorded on its Request), and return the
        finished-request records."""
        for _ in range(max_steps):
            self.step()
            if self.idle:
                break
        return self.finished
