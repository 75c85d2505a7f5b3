"""Unified ExpertResidency invariants: ONE ledger per engine, slot-pool
device buffers mirroring it exactly, and a hard expert-HBM bound.

The tentpole contract (ISSUE 3):
  * exactly one CacheState exists per engine — the scheduler and the device
    buffers share the ExpertResidency by reference;
  * at every step, ``set(slot_of) == set(state.resident)`` and device expert
    bytes == ``pool_capacity * bytes_per_expert`` with ``pool_capacity ==
    capacity`` (the all-pinned growth branch never fires in a sized engine);
  * slot-pool weight reads are bit-exact vs the old dict path
    (``device_put`` per expert) at temperature 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core.cache import CacheState, ExpertResidency, HostExpertStore
from repro.core.tracer import ExpertsTracer
from repro.models.model import build
from repro.obs.metrics import MetricsRegistry
from repro.serving.batching import BatchedServingEngine
from repro.serving.engine import MoEServingEngine

POLICIES = ["odf", "lfp", "mif", "duo"]


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral_8x7b"))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (12, 9, 14)]
    tracer = ExpertsTracer(cfg.n_layers, cfg.n_experts, cfg.top_k)
    for _ in range(8):
        tracer.add_path(np.stack([
            rng.choice(cfg.n_experts, cfg.top_k, replace=False)
            for _ in range(cfg.n_layers)]))
    return cfg, params, prompts, tracer.stats()


def assert_residency_invariants(res: ExpertResidency):
    """The full slot-pool <-> ledger mirror contract, checked at a step
    boundary."""
    assert set(res.slot_of) == set(res.resident), \
        "slot map and ledger diverged"
    # HBM bound: the pool IS the footprint, and it never regrew (the
    # shared predicate first, then its pieces for sharper failures)
    assert res.hbm_bound_ok
    assert res.regrow_events == 0
    assert res.pool_capacity == res.capacity
    assert res.device_bytes == res.pool_capacity * res.bytes_per_expert
    assert len(res.resident) <= res.capacity
    assert res.peak_resident <= res.capacity
    # every slot is either free or mapped, never both
    assert len(res._free) + len(res.slot_of) == res.pool_capacity
    assert set(res._free).isdisjoint(res.slot_of.values())
    # loaded keys are a subset of mapped keys
    assert res._loaded <= set(res.slot_of)


@pytest.mark.parametrize("policy", POLICIES)
def test_single_engine_residency_parity(setup, policy):
    """One ledger per engine; slot map == residency after a request."""
    cfg, params, prompts, stats = setup
    eng = MoEServingEngine(cfg, params, policy=policy, stats=stats,
                           temperature=0.0)
    assert eng.cache is eng.sched.cache, "two ledgers exist"
    assert isinstance(eng.cache, ExpertResidency)
    for p in prompts:
        eng.serve(p, max_new=3)
        assert_residency_invariants(eng.cache)


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("budget", [None, 3])
def test_batched_residency_parity_per_step(setup, policy, budget):
    """After EVERY engine step (batched, monolithic AND chunked prefill):
    slot map == residency and expert HBM stays at the fixed bound."""
    cfg, params, prompts, stats = setup
    eng = BatchedServingEngine(cfg, params, policy=policy, stats=stats,
                               max_batch=2, max_seq=32, temperature=0.0,
                               prefill_budget=budget)
    assert eng.cache is eng.sched.cache
    for p in prompts:
        eng.submit(p, max_new=3)
    for _ in range(200):
        eng.step()
        assert_residency_invariants(eng.cache)
        if not eng.running and not eng.prefilling and not len(eng.queue):
            break
    assert len(eng.finished) == len(prompts)


def test_slot_pool_reads_bit_exact_vs_host(setup):
    """Every loaded pool slot holds exactly the host store's bytes."""
    cfg, params, prompts, stats = setup
    eng = MoEServingEngine(cfg, params, policy="duo", temperature=0.0)
    eng.serve(prompts[0], max_new=3)
    res = eng.cache
    assert res._loaded, "no experts loaded?"
    for key in res._loaded:
        s = res.slot_of[key]
        for pool, host in zip(res.pools, res.store.get(key)):
            np.testing.assert_array_equal(np.asarray(pool[s]), host)


def test_slot_path_matches_dict_path_bit_exact(setup):
    """The jitted slot-indexed expert kernel reproduces the old dict-cache
    path (device_put per expert, weights as plain jit args) bit-for-bit."""
    cfg, params, prompts, stats = setup
    eng = MoEServingEngine(cfg, params, policy="duo", temperature=0.0)
    eng.serve(prompts[0], max_new=2)
    res = eng.cache

    @jax.jit
    def raw_dict_path(xn, w1, w3, w2):
        x2 = xn.reshape(-1, xn.shape[-1])
        h = jax.nn.silu(x2 @ w1) * (x2 @ w3)
        return (h @ w2).astype(jnp.float32)

    rng = np.random.default_rng(0)
    xn = jnp.asarray(rng.standard_normal((1, 1, cfg.d_model)),
                     jnp.bfloat16)
    for key in sorted(res._loaded):
        s = jnp.int32(res.slot_of[key])
        got = np.asarray(eng._expert_raw(xn, *res.pools, s))
        w1, w3, w2 = [jax.device_put(a) for a in res.store.get(key)]
        want = np.asarray(raw_dict_path(xn, w1, w3, w2))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"slot path diverged for {key}")


def test_greedy_tokens_invariant_across_policies(setup):
    """Residency/scheduling must never change greedy outputs (the old
    dict-cache engines shared this invariant — pins no-drift through the
    refactor)."""
    cfg, params, prompts, stats = setup
    outs = {}
    for pol in POLICIES:
        eng = MoEServingEngine(cfg, params, policy=pol, stats=stats,
                               temperature=0.0)
        outs[pol] = eng.serve(prompts[1], max_new=4).tokens
    ref = outs[POLICIES[0]]
    for pol, toks in outs.items():
        np.testing.assert_array_equal(toks, ref, err_msg=f"{pol} diverged")


# ---------------------------------------------------------------------------
# unit-level: hooks, drop, regrow, rescale
# ---------------------------------------------------------------------------


def _tiny_store(n_layers=2, n_experts=3, d=4, de=2):
    rng = np.random.default_rng(0)
    w = {}
    for l in range(n_layers):
        for e in range(n_experts):
            w[(l, e)] = (rng.standard_normal((d, de)).astype(np.float32),
                         rng.standard_normal((d, de)).astype(np.float32),
                         rng.standard_normal((de, d)).astype(np.float32))
    return HostExpertStore(w)


def test_evict_frees_slot_and_admit_reuses_it():
    res = ExpertResidency(_tiny_store(), capacity=2)
    res.admit((0, 0), pinned=False)
    res.admit((0, 1), pinned=False)
    s0 = res.slot_of[(0, 0)]
    res.prefetch((0, 0))
    evicted = res.admit((0, 2), pinned=False)   # LRU evicts (0,0)
    assert evicted == [(0, 0)]
    assert (0, 0) not in res.slot_of and (0, 0) not in res._loaded
    assert res.slot_of[(0, 2)] == s0            # slot reused, not leaked
    # re-admitted key transfers fresh weights into its (new) slot
    res.admit((0, 0), pinned=True)
    res.prefetch((0, 0))
    s = res.slot_of[(0, 0)]
    np.testing.assert_array_equal(np.asarray(res.pools[0][s]),
                                  res.store.get((0, 0))[0])


def test_drop_frees_device_slot_without_evict_event():
    """ODF free-after-forward: drop releases the slot but records no evict
    event (parity with the simulator's ledger replay)."""
    res = ExpertResidency(_tiny_store(), capacity=4)
    res.admit((0, 0))
    res.prefetch((0, 0))
    n_events = len(res.events)
    assert res.drop((0, 0))
    assert (0, 0) not in res.slot_of
    assert len(res._free) == 4
    assert len(res.events) == n_events          # no evict event
    assert not res.drop((0, 0))                 # idempotent


def test_unpin_shrink_frees_slots():
    res = ExpertResidency(_tiny_store(), capacity=2)
    res.admit((0, 0), pinned=True)
    res.admit((0, 1), pinned=True)
    res.admit((0, 2), pinned=True)              # all-pinned growth
    assert len(res.resident) == 3
    assert res.pool_capacity >= 3               # pool regrew to cover it
    assert res.regrow_events == 1
    res.unpin((0, 0))                            # shrink-on-unpin
    assert (0, 0) not in res.slot_of
    assert len(res.resident) == 2
    assert len(res._free) + len(res.slot_of) == res.pool_capacity


def test_rescale_grows_pool_without_counting_overflow():
    res = ExpertResidency(_tiny_store(), capacity=2)
    res.admit((0, 0))
    res.prefetch((0, 0))
    before = np.asarray(res.pools[0][res.slot_of[(0, 0)]]).copy()
    res.rescale(5)
    assert res.capacity == 5 and res.pool_capacity == 5
    assert res.regrow_events == 0               # provisioning, not overflow
    assert res.device_bytes == 5 * res.bytes_per_expert
    # existing slot contents survive the regrow
    np.testing.assert_array_equal(
        np.asarray(res.pools[0][res.slot_of[(0, 0)]]), before)
    with pytest.raises(AssertionError):
        res.rescale(3)                           # grow-only


def test_shared_state_construction():
    """make_scheduler(state=...) drives the given ledger instead of a
    private one, rescaling it if the policy needs more room."""
    from repro.core.scheduler import default_capacity, make_scheduler
    store = _tiny_store()
    res = ExpertResidency(store, capacity=2)
    sched = make_scheduler("lfp", 2, 3, 1, store.bytes_per_expert,
                           state=res)
    assert sched.cache is res
    assert res.capacity == default_capacity("lfp", 2, 3, 1) == 6
    assert res.pool_capacity == 6
    # simulator path: no state -> a plain ledger-only CacheState
    sim = make_scheduler("lfp", 2, 3, 1, store.bytes_per_expert)
    assert isinstance(sim.cache, CacheState)
    assert not isinstance(sim.cache, ExpertResidency)


# ---------------------------------------------------------------------------
# counters by phase
# ---------------------------------------------------------------------------


def _by_phase(reg, name):
    return {dict(c.labels)["phase"]: c.value for c in reg.series(name)}


def test_fetch_counters_by_phase_and_unused_fetches():
    """A copy counts under the phase it was issued in, with all three
    slabs' bytes; a copy evicted before any ``slot`` call read it counts as
    unused, one that was read does not."""
    reg = MetricsRegistry()
    res = ExpertResidency(_tiny_store(), capacity=2, metrics=reg)
    res.admit((0, 0), pinned=False)
    res.prefetch((0, 0))
    res.slot((0, 0))                            # read by a launch
    res.phase = "decode"
    res.admit((0, 1), pinned=False)
    res.prefetch((0, 1))                        # never read
    assert res.admit((0, 2), pinned=False) == [(0, 0)]
    assert res.admit((1, 0), pinned=False) == [(0, 1)]
    res.prefetch((1, 0))
    fetches = _by_phase(reg, "residency_fetches_total")
    assert fetches == {"prefill": 1, "decode": 2}
    assert sum(fetches.values()) == len(res.transfer_log)
    assert _by_phase(reg, "residency_fetch_bytes_total") == {
        "prefill": res.bytes_per_expert, "decode": 2 * res.bytes_per_expert}
    assert _by_phase(reg, "residency_fetches_unused_total") == {
        "prefill": 0, "decode": 1}
    # lookups by phase and outcome, and the totals they sum to
    assert res.lookup((1, 0)) and not res.lookup((0, 0))
    res.phase = "prefill"
    assert res.lookup((0, 2))
    snap = reg.snapshot()
    assert snap['residency_lookups_total{outcome="hit",phase="decode"}'] == 1
    assert snap['residency_lookups_total{outcome="miss",phase="decode"}'] \
        == 1
    assert snap['residency_lookups_total{outcome="hit",phase="prefill"}'] \
        == 1
    assert (res.hits, res.misses) == (2, 1)


@pytest.mark.parametrize("budget", [None, 3])
def test_engine_fetch_counters_match_the_transfer_log(setup, budget):
    """In a served run every transfer-log entry is one counted fetch, and
    the decode lookups cover the scheduler's gate-time decode tally."""
    cfg, params, prompts, stats = setup
    # one layer's experts fit, so decode steps copy experts in too
    eng = BatchedServingEngine(cfg, params, policy="duo", stats=stats,
                               max_batch=2, max_seq=32, temperature=0.0,
                               prefill_budget=budget,
                               cache_capacity=cfg.n_experts)
    for p in prompts:
        eng.submit(p, max_new=3)
    eng.run_until_drained()
    fetches = _by_phase(eng.metrics, "residency_fetches_total")
    assert sum(fetches.values()) == len(eng.cache.transfer_log)
    assert fetches["prefill"] > 0 and fetches["decode"] > 0
    nbytes = _by_phase(eng.metrics, "residency_fetch_bytes_total")
    assert nbytes == {p: n * eng.cache.bytes_per_expert
                      for p, n in fetches.items()}
    unused = _by_phase(eng.metrics, "residency_fetches_unused_total")
    assert all(0 <= unused[p] <= fetches[p] for p in fetches)
    look = {(dict(g.labels)["phase"], dict(g.labels)["outcome"]): g.value
            for g in eng.metrics.series("residency_lookups_total")}
    assert sum(look.values()) == eng.cache.hits + eng.cache.misses
    assert look["decode", "hit"] >= eng.sched.decode_hits
    assert look["decode", "miss"] >= eng.sched.decode_misses
