"""Unified expert-residency subsystem: one ledger, fixed slot-pool buffers.

HostExpertStore — the "CPU expert cache" of the paper: all routed-expert
weights live in host RAM (numpy). ExpertResidency — the "GPU expert cache":
ONE CacheState ledger (shared by reference with the scheduling policy, see
core/scheduler.py `make_scheduler(state=...)`) fused with preallocated
slot-pool device buffers — stacked ``[pool_capacity, d, de]`` arrays for
w1/w3/w2 allocated once at engine construction. Every ledger decision is
applied symmetrically to device memory through the `_on_admit`/`_on_evict`
hooks: admission allocates a pool slot, eviction (LRU, shrink-on-unpin, or
ODF's free-after-forward `drop`) frees it. Expert HBM is therefore provably
``pool_capacity * bytes_per_expert`` — a fixed bound, not a high-water mark
of an ever-growing dict.

Transfers keep the paper's two-stream overlap: the ledger admits at *plan*
time (scheduler), but the host->device copy is issued at *dispatch* time by
the engine (`prefetch`): ``jax.device_put`` per slab feeding a
donated-buffer ``.at[slot].set`` so the write is in place (no allocator
churn) and, under JAX async dispatch, overlaps subsequently dispatched
compute the way the paper's communication stream does. Compute reads
weights by slot index straight out of the pools (see EngineCore._jit_fns).

Both the serving engine and the discrete-event simulator drive the same
CacheState logic (the simulator with a plain ledger-only CacheState), so
simulated peak memory and hit rates reflect exactly what the engine does.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import SpanRecorder

ExpertKey = Tuple[int, int]  # (layer, expert)

# The engine phase a residency operation belongs to (``CacheState.phase``).
PHASES = ("prefill", "decode")


class HostExpertStore:
    """Host-RAM store of per-expert FFN weights (w1, w3, w2)."""

    def __init__(self, weights: Dict[ExpertKey, Tuple[np.ndarray, ...]]):
        self.weights = weights
        any_w = next(iter(weights.values()))
        self.bytes_per_expert = sum(a.nbytes for a in any_w)

    @staticmethod
    def from_params(layer_moe_params, n_layers: int, n_experts: int
                    ) -> "HostExpertStore":
        """layer_moe_params: stacked MoE params {'w1': [L,E,d,de], ...}."""
        w = {}
        for l in range(n_layers):
            for e in range(n_experts):
                w[(l, e)] = (np.asarray(layer_moe_params["w1"][l, e]),
                             np.asarray(layer_moe_params["w3"][l, e]),
                             np.asarray(layer_moe_params["w2"][l, e]))
        return HostExpertStore(w)

    @staticmethod
    def from_seed(n_layers: int, n_experts: int, d_model: int,
                  d_expert: int, *, seed: int = 0, dtype=jnp.bfloat16,
                  workers: Optional[int] = None) -> "HostExpertStore":
        """Random routed-expert weights made on the host: no expert stack
        is ever built on the device. Expert (l, e) draws from its own
        generator, seeded by (seed, l, e), so the store does not depend on
        the number of worker threads (numpy releases the GIL while it
        fills arrays). Values are uniform with the fan-in-scaled standard
        deviation of ``models.moe_layer.moe_params`` (d**-0.5 for w1/w3,
        d_expert**-0.5 for w2)."""
        def draw(rng, shape, fan_in):
            a = rng.random(shape, dtype=np.float32)
            a -= 0.5
            a *= 2.0 * np.sqrt(3.0 / fan_in)  # U(-sqrt(3/n), sqrt(3/n))
            return a.astype(dtype)

        def one(key: ExpertKey):
            rng = np.random.default_rng([seed, *key])
            return key, (draw(rng, (d_model, d_expert), d_model),
                         draw(rng, (d_model, d_expert), d_model),
                         draw(rng, (d_expert, d_model), d_expert))

        keys = [(l, e) for l in range(n_layers) for e in range(n_experts)]
        with ThreadPoolExecutor(workers or os.cpu_count()) as ex:
            return HostExpertStore(dict(ex.map(one, keys)))

    def get(self, key: ExpertKey):
        return self.weights[key]


@dataclasses.dataclass
class CacheEvent:
    kind: str            # 'fetch' | 'hit' | 'evict'
    key: ExpertKey
    t_issue: float       # host wall-clock when issued (engine) / sim time
    bytes: int = 0


class CacheState:
    """Residency bookkeeping shared by engine + simulator.

    capacity: max resident experts (global across layers). Eviction is LRU
    among non-pinned entries; `pin`/`unpin` protect experts between prefetch
    and use (the paper's sync-point semantics).

    Every residency mutation funnels through `_on_admit`/`_on_evict` hooks
    (no-ops here) so a subclass can mirror the ledger into device memory —
    ExpertResidency maps admissions to pool-slot allocations and evictions
    to slot frees, keeping ledger and device buffers one mechanism.

    Invariant (tests/test_property.py): residency exceeds capacity ONLY
    while every resident entry is pinned — pinned must-have admissions may
    grow an all-pinned cache, speculative (unpinned) ones are declined, and
    unpinning shrinks an over-grown cache back to capacity.

    Lookups are tallied once, by ``phase`` (one of ``PHASES``, set by the
    engine as it enters a prefill layer or a decode step) and outcome;
    ``hits``/``misses`` sum over the phases.
    """

    def __init__(self, capacity: int, bytes_per_expert: int):
        self.capacity = capacity
        self.bytes_per_expert = bytes_per_expert
        self.resident: "collections.OrderedDict[ExpertKey, bool]" = \
            collections.OrderedDict()  # key -> pinned
        self.events: List[CacheEvent] = []
        self.peak_resident = 0
        self.phase = PHASES[0]
        # (phase, hit) -> lookups
        self.lookups: Dict[Tuple[str, bool], int] = collections.Counter()

    @property
    def hits(self) -> int:
        return sum(n for (_, hit), n in self.lookups.items() if hit)

    @property
    def misses(self) -> int:
        return sum(n for (_, hit), n in self.lookups.items() if not hit)

    # -- device-mirror hooks (overridden by ExpertResidency) -----------------
    def _on_admit(self, key: ExpertKey) -> None:
        """Called exactly once when `key` newly becomes resident."""

    def _on_evict(self, key: ExpertKey) -> None:
        """Called exactly once when `key` leaves residency (any path)."""

    def contains(self, key: ExpertKey) -> bool:
        return key in self.resident

    def touch(self, key: ExpertKey) -> None:
        self.resident.move_to_end(key)

    def residency_overlap(self, keys: Iterable[ExpertKey]) -> int:
        """How many of `keys` are resident right now. A read-only scoring
        probe — no LRU touch, no hit/miss accounting, no events — so the
        cluster router's expert-affinity policy can rank replicas by live
        cache overlap without perturbing the replayable event stream."""
        resident = self.resident
        return sum(1 for k in keys if k in resident)

    def lookup(self, key: ExpertKey, t: float = 0.0) -> bool:
        hit = key in self.resident
        self.lookups[self.phase, hit] += 1
        if hit:
            self.touch(key)
            self.events.append(CacheEvent("hit", key, t))
        return hit

    def admit(self, key: ExpertKey, t: float = 0.0, pinned: bool = True
              ) -> List[ExpertKey]:
        """Admit key, evicting LRU unpinned entries if needed.

        Invariant: residency exceeds capacity ONLY while every resident
        entry is pinned. A pinned (must-have) admission into an all-pinned
        full cache grows it — correctness requires the weights resident
        (engines size their residency so this never fires; ExpertResidency
        regrows its pool if it does). An unpinned (speculative) admission in
        the same situation is DECLINED instead: growing past capacity for a
        prefetch that itself would be the next victim is never worth it.
        Declined keys stay non-resident and record no fetch event; callers
        check `contains` after admit. Returns evicted keys.
        """
        evicted = []
        if key in self.resident:
            self.resident[key] = pinned or self.resident[key]
            self.touch(key)
            return evicted
        while len(self.resident) >= self.capacity:
            victim = None
            for k, pin in self.resident.items():
                if not pin:
                    victim = k
                    break
            if victim is None:  # everything pinned
                if not pinned:
                    return evicted  # decline the speculative admission
                break               # grow (sized engines never reach this)
            del self.resident[victim]
            self._on_evict(victim)
            self.events.append(CacheEvent("evict", victim, t))
            evicted.append(victim)
        self.resident[key] = pinned
        self._on_admit(key)
        self.events.append(
            CacheEvent("fetch", key, t, self.bytes_per_expert))
        self.peak_resident = max(self.peak_resident, len(self.resident))
        return evicted

    def drop(self, key: ExpertKey, t: float = 0.0) -> bool:
        """Remove `key` from residency without an evict event: the ODF
        free-after-forward semantics (HF-Accelerate releases offloaded
        module weights right after the module runs — not a capacity
        eviction). The device mirror still frees the slot."""
        if key in self.resident:
            del self.resident[key]
            self._on_evict(key)
            return True
        return False

    def unpin(self, key: ExpertKey, t: float = 0.0) -> List[ExpertKey]:
        """Unpin `key`; if the cache had grown past capacity while all
        entries were pinned, shrink back now that a victim exists.
        Returns keys evicted by the shrink."""
        if key in self.resident:
            self.resident[key] = False
            return self._shrink(t)
        return []

    def unpin_all(self, t: float = 0.0) -> List[ExpertKey]:
        for k in self.resident:
            self.resident[k] = False
        return self._shrink(t)

    def _shrink(self, t: float = 0.0) -> List[ExpertKey]:
        evicted = []
        while len(self.resident) > self.capacity:
            victim = None
            for k, pin in self.resident.items():
                if not pin:
                    victim = k
                    break
            if victim is None:
                break
            del self.resident[victim]
            self._on_evict(victim)
            self.events.append(CacheEvent("evict", victim, t))
            evicted.append(victim)
        return evicted

    def rescale(self, new_capacity: int) -> None:
        """Raise the residency bound (batch-size change, policy swap).
        Grow-only: shrinking would need an eviction sweep no caller wants
        implicitly; ExpertResidency also regrows its device pools here."""
        assert new_capacity >= self.capacity, \
            f"rescale is grow-only ({self.capacity} -> {new_capacity})"
        self.capacity = new_capacity

    @property
    def peak_bytes(self) -> int:
        return self.peak_resident * self.bytes_per_expert

    @property
    def hit_rate(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0


@functools.partial(jax.jit, donate_argnums=(0,))
def _pool_write(pool: jax.Array, slot: jax.Array, slab: jax.Array
                ) -> jax.Array:
    """In-place (donated) host->device write of one expert slab into its
    pool slot. Donation makes the at-set reuse the pool's buffer — no
    allocator churn per fetch — while async dispatch lets the copy overlap
    compute dispatched afterwards."""
    return pool.at[slot].set(slab)


class ExpertResidency(CacheState):
    """THE ledger fused with the device expert buffers (one mechanism).

    The scheduler shares this object by reference (`sched.cache is
    engine.cache`) and performs all plan-time ledger ops on it; the
    `_on_admit`/`_on_evict` overrides mirror every decision into a fixed
    slot pool:

      * pools: stacked ``w1/w3: [pool_capacity, d, de]``,
        ``w2: [pool_capacity, de, d]`` device arrays allocated ONCE.
      * slot_of: ExpertKey -> pool slot for every resident expert
        (invariant: ``set(slot_of) == set(resident)`` at all times).
      * admission pops a free slot; eviction pushes it back — O(1), no
        device allocation on the steady-state path.

    Transfer issuance is decoupled from admission so the engine keeps the
    paper's overlap structure: `prefetch(key)` performs the actual
    host->device copy for an already-admitted key at the point the engine
    dispatches it (between compute dispatches); `slot(key)` is the
    use-time sync point — it issues any still-pending copy and returns the
    slot index for the jitted slot-indexed expert kernels.

    If a must-have admission grows an all-pinned ledger past the pool (the
    engines size `capacity` so this never happens — asserted in
    tests/test_residency.py), the pool regrows rather than corrupting a
    live slot; `regrow_events` counts those.

    Observability: each copy is an ``expert.fetch`` span and each barrier
    an ``expert.wait`` span on ``obs`` (the engine's recorder), and
    ``metrics`` (the engine's registry) counts by phase the copies
    (``residency_fetches_total``), their bytes, all three slabs
    (``residency_fetch_bytes_total``), the copies evicted before any
    `slot` call read them (``residency_fetches_unused_total``) and the
    lookups by outcome (``residency_lookups_total``). ``transfer_log``
    keeps each copy's key and host time.
    """

    def __init__(self, store: HostExpertStore, capacity: int, *,
                 obs: Optional[SpanRecorder] = None,
                 metrics: Optional[MetricsRegistry] = None):
        super().__init__(capacity, store.bytes_per_expert)
        self.store = store
        w1, w3, w2 = next(iter(store.weights.values()))
        self.pool_capacity = capacity
        self._pools: Dict[str, jax.Array] = {
            "w1": jnp.zeros((capacity,) + w1.shape, w1.dtype),
            "w3": jnp.zeros((capacity,) + w3.shape, w3.dtype),
            "w2": jnp.zeros((capacity,) + w2.shape, w2.dtype),
        }
        self.slot_of: Dict[ExpertKey, int] = {}
        self._free: List[int] = list(range(capacity))[::-1]
        self._loaded: Set[ExpertKey] = set()
        self.transfer_log: List[Tuple[ExpertKey, float]] = []
        self.regrow_events = 0
        self.obs = obs if obs is not None else SpanRecorder()
        reg = metrics if metrics is not None else MetricsRegistry()
        self._c_fetches = {p: reg.counter(
            "residency_fetches_total", "expert copies host->HBM", phase=p)
            for p in PHASES}
        self._c_fetch_bytes = {p: reg.counter(
            "residency_fetch_bytes_total",
            "host->HBM bytes of expert copies (all three slabs)", phase=p)
            for p in PHASES}
        self._c_unused = {p: reg.counter(
            "residency_fetches_unused_total",
            "expert copies evicted before any launch read their slot",
            phase=p) for p in PHASES}
        for p in PHASES:
            for hit, outcome in ((True, "hit"), (False, "miss")):
                reg.gauge("residency_lookups_total",
                          "expert-cache lookups by phase and outcome",
                          fn=lambda k=(p, hit): self.lookups[k],
                          phase=p, outcome=outcome)
        # copied in, slot not yet read: key -> phase of the copy
        self._unread: Dict[ExpertKey, str] = {}

    # -- ledger -> device mirroring -----------------------------------------
    def _on_admit(self, key: ExpertKey) -> None:
        if not self._free:
            # all-pinned ledger growth: never corrupt a live slot
            self._regrow(self.pool_capacity + max(1, self.pool_capacity // 2))
        self.slot_of[key] = self._free.pop()

    def _on_evict(self, key: ExpertKey) -> None:
        slot = self.slot_of.pop(key, None)
        if slot is not None:
            self._free.append(slot)
            self._loaded.discard(key)
        phase = self._unread.pop(key, None)
        if phase is not None:
            self._c_unused[phase].inc()

    def _regrow(self, new_pool_capacity: int) -> None:
        grown = new_pool_capacity - self.pool_capacity
        for name, pool in self._pools.items():
            pad = jnp.zeros((grown,) + pool.shape[1:], pool.dtype)
            self._pools[name] = jnp.concatenate([pool, pad], axis=0)
        self._free.extend(range(self.pool_capacity, new_pool_capacity))
        self.pool_capacity = new_pool_capacity
        self.regrow_events += 1

    def rescale(self, new_capacity: int) -> None:
        super().rescale(new_capacity)
        if new_capacity > self.pool_capacity:
            self._regrow(new_capacity)
            self.regrow_events -= 1  # provisioning, not an overflow event

    # -- device transfers ----------------------------------------------------
    def prefetch(self, key: ExpertKey) -> bool:
        """Issue the host->device copy for an already-admitted key (async
        dispatch: returns immediately, the DMA overlaps compute dispatched
        after it — the TPU analogue of the paper's communication stream).
        Returns True if the key was already loaded; no-op (False) for keys
        the ledger declined (speculative admit into an all-pinned cache)."""
        slot = self.slot_of.get(key)
        if slot is None:
            return False
        if key in self._loaded:
            return True
        phase = self.phase
        span = self.obs.begin("expert.fetch", lane="prefetch", layer=key[0],
                              expert=key[1], phase=phase)
        w1, w3, w2 = self.store.get(key)
        s = jnp.int32(slot)
        self._pools["w1"] = _pool_write(self._pools["w1"], s,
                                        jax.device_put(w1))
        self._pools["w3"] = _pool_write(self._pools["w3"], s,
                                        jax.device_put(w3))
        self._pools["w2"] = _pool_write(self._pools["w2"], s,
                                        jax.device_put(w2))
        self.obs.end(span)
        self._loaded.add(key)
        self._unread[key] = phase
        self._c_fetches[phase].inc()
        self._c_fetch_bytes[phase].inc(self.bytes_per_expert)
        self.transfer_log.append((key, time.perf_counter()))
        return False

    def slot(self, key: ExpertKey) -> int:
        """Use-time access: slot index of a resident key, issuing its copy
        if still pending. A non-resident key is a scheduler/engine bug; the
        correction admit below records honest ledger events, so the
        engine-vs-simulator parity tests surface it loudly instead of a
        silent re-fetch masking it."""
        if key not in self.slot_of:
            self.admit(key, time.perf_counter(), pinned=True)
        self.prefetch(key)
        self._unread.pop(key, None)
        return self.slot_of[key]

    @property
    def pools(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """Current (w1, w3, w2) slot-pool arrays. Re-read after any
        prefetch/slot call: each write produces a fresh array object
        (donation reuses the buffer underneath)."""
        return self._pools["w1"], self._pools["w3"], self._pools["w2"]

    def get(self, key: ExpertKey) -> Tuple[jax.Array, ...]:
        """Slot-sliced (w1, w3, w2) views for one expert (compat/testing
        path; the engines pass pools + slot index into jitted kernels)."""
        s = self.slot(key)
        return tuple(self._pools[n][s] for n in ("w1", "w3", "w2"))

    def wait(self, key: ExpertKey) -> None:
        """Sync point: block until the expert's weights are on device."""
        self.slot(key)
        span = self.obs.begin("expert.wait", lane="prefetch", layer=key[0],
                              expert=key[1])
        for p in self._pools.values():
            p.block_until_ready()
        self.obs.end(span)

    @property
    def device_bytes(self) -> int:
        """Actual expert HBM footprint — the fixed pool allocation."""
        return sum(p.nbytes for p in self._pools.values())

    @property
    def hbm_bound_ok(self) -> bool:
        """THE expert-HBM bound predicate (one definition for tests,
        benches, and examples): device bytes equal the fixed
        ``capacity * bytes_per_expert`` allocation and the pool never
        regrew past the capacity it was sized with."""
        return (self.device_bytes
                == self.pool_capacity * self.bytes_per_expert
                and self.regrow_events == 0
                and self.pool_capacity == self.capacity)
