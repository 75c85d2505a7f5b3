"""Continuous-batching correctness: batched decode must be bit-exact vs the
single-request engine at temperature 0, chunked prefill must be bit-exact vs
monolithic for EVERY chunk size (including mid-prefill decode interleaving),
and the shared-cache ledger must count distinct experts per step
(decode-plan union semantics). Also the typed-API layer: SamplingParams
plumbing, stop-token early termination, priority admission order, srf
prefill fairness, per-request tbt_slo admission, and the step() event
stream (streaming equivalence + cancellation live in test_frontend.py)."""
import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core.qos import (Admission, AdmissionController, LatencyModel,
                            TBTLedger)
from repro.core.scheduler import union_selection
from repro.models.model import build
from repro.serving.api import (FinishEvent, GenerationRequest, RejectEvent,
                               SamplingParams, TokenEvent)
from repro.serving.batching import (BatchedServingEngine, Request,
                                    RequestQueue)
from repro.serving.engine import MoEServingEngine

MAX_NEW = 4


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("mixtral_8x7b"))
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (12, 16, 9, 14)]
    seq = MoEServingEngine(cfg, params, policy="duo", temperature=0.0)
    refs = [seq.serve(p, max_new=MAX_NEW) for p in prompts]
    return cfg, params, prompts, refs


@pytest.mark.parametrize("B", [1, 2, 4])
def test_batched_matches_sequential(setup, B):
    """B concurrent requests produce exactly the tokens B sequential
    single-request serve() calls produce (greedy)."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=B,
                               max_seq=32, temperature=0.0)
    for p in prompts[:B]:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == B
    assert len(eng.decode_batch_hist) == MAX_NEW
    assert max(eng.decode_batch_hist) == B
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens,
                                      err_msg=f"request {i} diverged")


def test_midflight_admission_matches_sequential(setup):
    """More requests than KV slots: later arrivals are admitted as slots
    free up mid-flight, still bit-exact."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0)
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == len(prompts)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)


@pytest.mark.parametrize("policy", ["odf", "lfp", "duo", "duo+"])
def test_policies_identical_tokens_batched(setup, policy):
    """Scheduling policy must never change batched outputs either."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy=policy, max_batch=2,
                               max_seq=32, temperature=0.0)
    for p in prompts[:2]:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens,
                                      err_msg=f"{policy} diverged")


def test_shared_cache_accounting(setup):
    """Per step+layer the scheduler ledger counts each DISTINCT selected
    expert exactly once; per request every selected expert lands in exactly
    one of {hits, misses}."""
    cfg, params, prompts, _ = setup
    B = 4
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=B,
                               max_seq=32, temperature=0.0)
    for p in prompts[:B]:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)

    traces = [r.result().decode_trace for r in finished]  # [T, L, k] each
    expected = 0
    for t in range(MAX_NEW):
        for l in range(cfg.n_layers):
            union = set()
            for tr in traces:
                union.update(int(e) for e in tr[t, l])
            expected += len(union)
    assert eng.sched.decode_hits + eng.sched.decode_misses == expected
    # per-request attribution covers exactly its own selections
    for r in finished:
        assert r.hits + r.misses == MAX_NEW * cfg.n_layers * cfg.top_k
    # attribution can only multiply-count shared experts, never lose them
    assert sum(r.hits + r.misses for r in finished) >= expected
    # the batch-scaled cache capacity absorbs one step's churn without the
    # everything-pinned overflow branch silently growing the cache
    assert eng.sched.cache.capacity >= 2 * B * cfg.top_k
    assert eng.sched.cache.peak_resident <= eng.sched.cache.capacity


@pytest.mark.parametrize("chunk", [1, 3, "S"])
def test_chunked_prefill_bit_exact(setup, chunk):
    """Chunked prefill (any chunk size) yields bit-identical tokens AND
    identical per-layer active-expert sets vs monolithic prefill."""
    cfg, params, prompts, refs = setup
    for p, ref in zip(prompts[:2], refs[:2]):
        size = len(p) if chunk == "S" else chunk
        eng = MoEServingEngine(cfg, params, policy="duo", temperature=0.0,
                               prefill_chunk=size)
        r = eng.serve(p, max_new=MAX_NEW)
        np.testing.assert_array_equal(r.tokens, ref.tokens,
                                      err_msg=f"chunk={size} diverged")
        assert r.prefill_active == ref.prefill_active, \
            f"chunk={size}: per-layer active-expert sets differ"


def test_chunked_batched_bit_exact(setup):
    """The chunked continuous-batching pipeline (prefill_budget) produces
    the monolithic engine's tokens for every request."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0, prefill_budget=4)
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == len(prompts)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)
        assert r.prefill_active == refs[i].prefill_active


def test_rr_fairness_bit_exact_and_interleaved(setup):
    """Round-robin chunked prefill (default) still emits exactly the
    monolithic tokens/active-sets per request, and overlapping prefills
    make interleaved progress (the per-step budget rotates) instead of
    strict head-of-line."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0,
                               prefill_budget=4, prefill_fairness="rr")
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    eng.step()          # admit all 4; budget goes to request 0 this step
    assert {r.rid: r.prefill_pos for r in eng.prefilling} == \
        {0: 4, 1: 0, 2: 0, 3: 0}
    eng.step()          # rotation: request 1's turn
    assert {r.rid: r.prefill_pos for r in eng.prefilling} == \
        {0: 4, 1: 4, 2: 0, 3: 0}
    eng.step()          # request 2 (9 tokens remains prefilling at pos 4)
    assert {r.rid: r.prefill_pos for r in eng.prefilling} == \
        {0: 4, 1: 4, 2: 4, 3: 0}
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == len(prompts)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)
        assert r.prefill_active == refs[i].prefill_active


def test_fifo_fairness_head_of_line(setup):
    """prefill_fairness='fifo' restores the old discipline: the whole
    budget goes to the head request."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0,
                               prefill_budget=4, prefill_fairness="fifo")
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    eng.step()
    by_rid = {r.rid: r.prefill_pos for r in eng.prefilling}
    assert by_rid[0] == 4 and all(v == 0 for k, v in by_rid.items() if k)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)


def test_auto_budget_tracks_latency_model(setup):
    """prefill_budget='auto' sizes chunks from the live LatencyModel so one
    chunk + one decode step fits the TBT SLO — and stays bit-exact."""
    from repro.core.qos import LatencyModel
    m = LatencyModel(prefill_per_token=0.01, decode_step=0.05)
    assert m.suggest_chunk(0.25) == 20          # (0.25 - 0.05) / 0.01
    assert m.suggest_chunk(0.04) == 1           # unmeetable -> floor
    assert m.suggest_chunk(1e9, ceiling=64) == 64

    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0,
                               prefill_budget="auto", tbt_slo=0.5)
    assert eng.chunked
    assert eng._current_budget() >= 1
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == len(prompts)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)
        assert r.prefill_active == refs[i].prefill_active
    with pytest.raises(AssertionError):
        BatchedServingEngine(cfg, params, max_batch=2, max_seq=32,
                             prefill_budget="auto")   # no tbt_slo


def test_finished_window_bounds_retention(setup):
    """finished_window keeps only the most recent N request records."""
    cfg, params, prompts, _ = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0,
                               finished_window=2)
    for p in prompts:
        eng.submit(p, max_new=2)
    eng.run_until_drained()
    assert len(eng.finished) == 2
    assert [r.rid for r in eng.finished] == [2, 3]   # most recent survive


def test_chunked_interleaving_is_stall_free(setup):
    """While a long prompt prefills in chunks, an in-flight decoder keeps
    producing tokens every step — and both stay bit-exact."""
    cfg, params, prompts, refs = setup
    budget = 4
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0,
                               prefill_budget=budget)
    decoder = eng.submit(prompts[2], max_new=MAX_NEW)   # 9 tokens
    while decoder.state != "running":
        eng.step()
    long = eng.submit(prompts[1], max_new=MAX_NEW)      # 16 tokens
    chunk_steps = 0
    decoded_during_prefill = 0
    while long.state in ("queued", "prefilling"):
        before = len(decoder.tokens)
        eng.step()
        chunk_steps += 1
        if not decoder.done:
            assert len(decoder.tokens) == before + 1, \
                "decoder stalled during a prefill chunk step"
            decoded_during_prefill += 1
    # 16 tokens / budget 4 -> 4 chunk iterations, decode advancing in each
    assert chunk_steps == -(-long.prompt_len // budget)
    assert decoded_during_prefill >= 1
    eng.run_until_drained()
    np.testing.assert_array_equal(decoder.result().tokens, refs[2].tokens)
    np.testing.assert_array_equal(long.result().tokens, refs[1].tokens)
    assert long.prefill_active == refs[1].prefill_active


def _count_sweeps(eng):
    """Record how many request chunks each prefill sweep of ``eng`` carries
    (and whether the multi-chunk row block was built)."""
    seen, concat = [], []
    sweep, cat = eng.prefill_chunks, eng._concat_rows

    def spy_sweep(chunks):
        seen.append(len(chunks))
        return sweep(chunks)

    def spy_cat(*xs):
        concat.append(len(xs))
        return cat(*xs)

    eng.prefill_chunks, eng._concat_rows = spy_sweep, spy_cat
    return seen, concat


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("fairness", ["rr", "fifo"])
def test_multi_request_sweep_bit_exact(setup, fairness, fused):
    """A budget of 24 over prompts of 12, 16, 9 and 14 tokens makes steps
    whose chunks span 2, then 3, then 1 request under both rr and fifo;
    each step runs as one sweep, and every request still gets the
    monolithic engine's tokens and per-layer active experts."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0,
                               prefill_budget=24, prefill_fairness=fairness,
                               fused_prefill=fused)
    seen, concat = _count_sweeps(eng)
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert seen == [2, 3, 1]
    assert concat == [2] * cfg.n_layers + [3] * cfg.n_layers
    assert eng.perf.prefill_passes == 3
    assert eng.perf.prefill_pass_chunks == 6
    snap = eng.metrics.snapshot()
    assert snap["engine_prefill_passes_total"] == 3
    assert snap["engine_prefill_pass_chunks_total"] == 6
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens,
                                      err_msg=f"request {i} diverged")
        assert r.prefill_active == refs[i].prefill_active


def test_one_chunk_steps_count_one_pass_each(setup):
    """With one prefilling request at a time every step carries one chunk:
    passes equal chunks, and no row block is built."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=1,
                               max_seq=32, temperature=0.0, prefill_budget=4)
    seen, concat = _count_sweeps(eng)
    for p in prompts[:2]:
        eng.submit(p, max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    n_chunks = sum(-(-len(p) // 4) for p in prompts[:2])
    assert seen == [1] * n_chunks and concat == []
    assert eng.perf.prefill_passes == eng.perf.prefill_pass_chunks == n_chunks
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)


def test_one_chunk_step_matches_the_single_chunk_call(setup):
    """A step with one chunk runs what the single-chunk call runs: the same
    first token, active experts, expert-FFN launches and ledger events as
    `prefill_layers` over `prefill_chunk` calls of the same size."""
    cfg, params, prompts, _ = setup
    p = prompts[1]
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=1,
                               max_seq=32, temperature=0.0, prefill_budget=4)
    req = eng.submit(p, max_new=MAX_NEW)
    eng._admit_and_prefill(0.0)
    while req.state == "prefilling":
        eng._prefill_work()
    one = MoEServingEngine(cfg, params, policy="duo", temperature=0.0,
                           prefill_chunk=4)
    logits, _, active, _ = one.prefill_layers(p.reshape(1, -1))
    assert req.tokens == [int(np.asarray(logits)[0].argmax())]
    assert req.prefill_active == active
    for field in ("prefill_ffn_launches", "prefill_moe_layers",
                  "prefill_passes", "prefill_pass_chunks"):
        assert getattr(eng.perf, field) == getattr(one.perf, field), field
    assert [(e.kind, e.key) for e in eng.cache.events] == \
        [(e.kind, e.key) for e in one.cache.events]


def test_two_chunk_sweep_plans_and_copies_once_per_layer(setup):
    """One layer of a two-request sweep makes one `prefill_plan` call over
    the union of the chunks' experts and copies each active expert at most
    once; each chunk's logits, KV and routing equal its chunk run alone."""
    from repro.models.layers import PDT
    from repro.serving.engine import PrefillChunk
    cfg, params, prompts, _ = setup
    C, W = 8, 16
    hkv, hd = cfg.n_kv_heads, cfg.hd

    def fresh(tokens):
        kc = [jax.numpy.zeros((1, W, hkv, hd), PDT)
              for _ in range(cfg.n_layers)]
        return PrefillChunk(tokens[None, :C], 0, kc, list(kc),
                            jax.numpy.full((1, W), -1, jax.numpy.int32))

    eng = MoEServingEngine(cfg, params, policy="duo", temperature=0.0,
                           prefill_chunk=2 * C)
    plans = []
    plan = eng.sched.prefill_plan
    eng.sched.prefill_plan = lambda l, act: (plans.append((l, list(act))),
                                             plan(l, act))[1]
    chunks = [fresh(prompts[0]), fresh(prompts[1])]
    outs = eng.prefill_chunks(chunks)
    assert [l for l, _ in plans] == list(range(cfg.n_layers))
    for l, act in plans:
        assert act == sorted(set(outs[0][1][l]) | set(outs[1][1][l]))
        copied = [key for key, _ in eng.cache.transfer_log if key[0] == l]
        assert len(copied) == len(set(copied))
        assert {e for _, e in copied} <= set(act)
    for c, (logits, active, paths) in zip(chunks, outs):
        alone = MoEServingEngine(cfg, params, policy="duo", temperature=0.0,
                                 prefill_chunk=2 * C)
        ref = fresh(np.asarray(c.tokens[0]))
        (lg, act1, paths1), = alone.prefill_chunks([ref])
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(lg))
        np.testing.assert_array_equal(paths, paths1)
        assert active == act1
        for l in range(cfg.n_layers):
            np.testing.assert_array_equal(np.asarray(c.kc[l]),
                                          np.asarray(ref.kc[l]))


def test_tbt_ledger_gaps():
    led = TBTLedger()
    led.observe(0, 1.0)
    led.observe(0, 1.5)
    led.observe(1, 2.0)
    led.observe(0, 3.0)
    led.observe(1, 2.25)
    assert list(led.by_rid[0]) == [0.5, 1.5]
    assert list(led.by_rid[1]) == [0.25]
    assert led.max_gap() == 1.5
    led.close(0)
    led.observe(0, 9.0)       # fresh baseline after close: no gap recorded
    assert list(led.by_rid[0]) == [0.5, 1.5]
    rep = led.report()
    assert rep["max"] == 1.5 and rep["p50"] <= rep["p99"]
    assert rep["n"] == 3


def test_tbt_ledger_windowed_retention():
    """Raw samples are bounded by the window; lifetime max/count and the
    streaming sketches survive eviction (ROADMAP retention item)."""
    led = TBTLedger(window=8, per_rid_window=4)
    t = 0.0
    for i in range(100):
        t += 0.010 if i != 50 else 5.0    # one huge stall mid-stream
        led.observe(0, t)
    assert len(led.gaps) == 8             # bounded
    assert len(led.by_rid[0]) == 4
    assert led.total_gaps == 99
    assert led.max_gap() == 5.0           # lifetime max survived eviction
    rep = led.report()
    assert rep["n"] == 99
    # the windowed p50 only sees recent 10ms gaps; the stream sketch saw
    # everything and stays in the data's range
    assert rep["p50"] == pytest.approx(0.010, rel=1e-6)
    assert 0.0 < rep["p50_stream"] <= 5.0


def test_tbt_ledger_bounds_closed_request_dict():
    """close() enrolls requests in a bounded FIFO: the by_rid DICT itself
    cannot grow without bound as requests churn (the leak is per-request
    deques accumulating, not just samples within one deque)."""
    led = TBTLedger(closed_window=3)
    for rid in range(10):
        led.observe(rid, 0.0)
        led.observe(rid, 0.1)
        led.close(rid)
    assert len(led.by_rid) == 3
    assert sorted(led.by_rid) == [7, 8, 9]      # most recently closed kept
    assert led.total_gaps == 10                  # lifetime counters intact
    # closed_window=None keeps everything (benchmark mode)
    exact = TBTLedger(closed_window=None)
    for rid in range(5):
        exact.observe(rid, 0.0)
        exact.observe(rid, 0.1)
        exact.close(rid)
    assert len(exact.by_rid) == 5


def test_p2_sketch_tracks_percentiles():
    from repro.core.qos import P2Quantile
    rng = np.random.default_rng(0)
    xs = rng.exponential(1.0, size=20_000)
    p50, p99 = P2Quantile(0.5), P2Quantile(0.99)
    for x in xs:
        p50.update(float(x))
        p99.update(float(x))
    assert p50.value() == pytest.approx(np.percentile(xs, 50), rel=0.05)
    assert p99.value() == pytest.approx(np.percentile(xs, 99), rel=0.10)
    # tiny-sample fallback is the exact empirical percentile
    small = P2Quantile(0.5)
    for x in (3.0, 1.0, 2.0):
        small.update(x)
    assert small.value() == 2.0


def test_union_selection_shapes():
    assert union_selection([3, 1, 2]) == [3, 1, 2]
    assert union_selection([[3, 1], [1, 2]]) == [3, 1, 2]
    assert union_selection([np.array([5, 0]), [0, 5]]) == [5, 0]
    assert union_selection([]) == []


def test_admission_queue_verdict_keeps_fifo():
    """Backlog-only breach -> QUEUE: the request stays at the head instead
    of being shed, and admission stops for the round (FIFO preserved)."""
    from repro.serving.batching import Request
    ctl = AdmissionController(
        LatencyModel(prefill_per_token=0.1, decode_step=0.0),
        default_ttft_slo=2.0)
    assert ctl.decide(0.0, 0.0, 16, 0) is Admission.ADMIT
    assert ctl.decide(0.0, 0.0, 16, 16) is Admission.QUEUE   # backlog breach
    assert ctl.decide(0.0, 0.0, 40, 0) is Admission.REJECT   # hopeless

    q = RequestQueue(ctl)
    sp = SamplingParams(max_new_tokens=2)
    r0 = Request(rid=0, prompt=np.zeros(16, np.int32), params=sp, arrival=0.0)
    r1 = Request(rid=1, prompt=np.zeros(16, np.int32), params=sp, arrival=0.0)
    q.submit(r0)
    q.submit(r1)
    admitted = q.pop_admissible(now=0.0, limit=2)
    assert [r.rid for r in admitted] == [0]
    assert len(q.pending) == 1 and q.pending[0].rid == 1
    assert not q.rejected
    # backlog drained -> the queued request admits on the next round
    assert [r.rid for r in q.pop_admissible(now=0.0, limit=2)] == [1]


def test_admission_folds_decode_load():
    """A chunked engine interleaves one batched decode step per chunk
    iteration, so predicted TTFT charges decode interference per iteration
    when decoders are running — admission no longer under-predicts under
    high decode concurrency. Monolithic prefill runs all same-round admits
    inside ONE iteration, so it keeps the single drain step."""
    ctl = AdmissionController(
        LatencyModel(prefill_per_token=0.1, decode_step=0.5))
    base = ctl.predict_ttft(0.0, 0.0, 10, 0)
    assert base == pytest.approx(0.1 * 10 + 0.5)      # one drain step
    busy = ctl.predict_ttft(0.0, 0.0, 10, 0, running_batch=2, chunk_budget=5)
    assert busy == pytest.approx(0.1 * 10 + 2 * 0.5)  # ceil(10/5) iterations
    assert busy > base
    # monolithic: back-to-back prefills in one iteration — no per-request
    # interference term, whatever is queued ahead or running
    mono = ctl.predict_ttft(0.0, 0.0, 10, 30, running_batch=4)
    assert mono == pytest.approx(0.1 * 40 + 0.5)
    # an idle chunked engine has no decoders to interleave with either
    idle = ctl.predict_ttft(0.0, 0.0, 10, 0, running_batch=0, chunk_budget=5)
    assert idle == pytest.approx(base)
    # interference alone can now (correctly) push a request over its SLO
    tight = AdmissionController(
        LatencyModel(prefill_per_token=0.1, decode_step=1.0),
        default_ttft_slo=2.5)
    assert tight.decide(0.0, 0.0, 10, 0) is Admission.ADMIT
    assert tight.decide(0.0, 0.0, 10, 0, running_batch=1,
                        chunk_budget=5) is Admission.REJECT


def test_admission_controller_slo():
    slow = AdmissionController(LatencyModel(prefill_per_token=1.0),
                               default_ttft_slo=0.5)
    assert slow.decide(0.0, 0.0, 10, 0) is Admission.REJECT
    assert slow.n_rejected == 1
    fast = AdmissionController(LatencyModel(prefill_per_token=1e-6))
    # no SLO -> always admit
    assert fast.decide(0.0, 0.0, 10, 0) is Admission.ADMIT
    assert fast.decide(0.0, 0.0, 10, 10**6, ttft_slo=30.0) is Admission.ADMIT


def test_priority_orders_admission():
    """pop_admissible honors GenerationRequest.priority: candidates are
    considered in stable (priority desc, arrival) order, so a later
    high-priority arrival is admitted ahead of earlier low-priority ones
    and equal priorities keep FIFO."""
    q = RequestQueue(AdmissionController())     # no SLO: always admit
    sp = SamplingParams(max_new_tokens=2)
    for rid, prio in enumerate([0, 5, 0, 5, 1]):
        q.submit(Request(rid=rid, prompt=np.zeros(4, np.int32), params=sp,
                         arrival=float(rid), priority=prio))
    first = q.pop_admissible(now=10.0, limit=2)
    assert [r.rid for r in first] == [1, 3]     # prio 5, arrival order
    rest = q.pop_admissible(now=10.0, limit=5)
    assert [r.rid for r in rest] == [4, 0, 2]   # prio 1, then FIFO zeros
    assert not q.pending and not q.rejected


def test_priority_admission_end_to_end(setup):
    """A high-priority late submission wins the only free KV slot."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=1,
                               max_seq=32, temperature=0.0)
    lo = eng.submit(prompts[0], max_new=2, priority=0)
    hi = eng.submit(prompts[1], max_new=2, priority=3)
    eng.step()
    assert hi.state == "running" and lo.state == "queued"
    eng.run_until_drained()
    assert [r.rid for r in eng.finished] == [hi.rid, lo.rid]


def test_srf_fairness_shortest_first(setup):
    """prefill_fairness='srf' spends the budget on the request with the
    least prefill remaining — a short straggler overtakes long backlogs —
    and stays bit-exact. Prompt lengths: rid0=12, rid1=16, rid2=9, rid3=14.
    """
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0,
                               prefill_budget=4, prefill_fairness="srf")
    for p in prompts:
        eng.submit(p, max_new=MAX_NEW)
    eng.step()          # shortest (rid2, 9 tokens) gets the whole budget
    assert {r.rid: r.prefill_pos for r in eng.prefilling} == \
        {0: 0, 1: 0, 2: 4, 3: 0}
    eng.step()          # rid2 still shortest remaining (5)
    assert {r.rid: r.prefill_pos for r in eng.prefilling} == \
        {0: 0, 1: 0, 2: 8, 3: 0}
    eng.step()          # rid2 finishes (1 token), 3 spill to rid0 (12)
    assert {r.rid: r.prefill_pos for r in eng.prefilling} == \
        {0: 3, 1: 0, 3: 0}
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == len(prompts)
    for i, r in enumerate(finished):
        np.testing.assert_array_equal(r.result().tokens, refs[i].tokens)
        assert r.prefill_active == refs[i].prefill_active


def test_stop_token_early_termination(setup):
    """A token in SamplingParams.stop_token_ids terminates the request
    early — the stop token itself is still emitted (prefix bit-exact vs the
    un-stopped reference) — on BOTH the single-request and batched paths."""
    cfg, params, prompts, refs = setup
    stop = int(refs[0].tokens[2])
    sp = SamplingParams(max_new_tokens=MAX_NEW, stop_token_ids=(stop,))

    seq = MoEServingEngine(cfg, params, policy="duo", temperature=0.0)
    r = seq.serve(prompts[0], params=sp)
    assert r.finish_reason == "stop_token"
    np.testing.assert_array_equal(r.tokens, refs[0].tokens[:3])
    assert r.decode_trace.shape[0] == 2      # traces truncated with tokens

    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0)
    req = eng.submit(prompts[0], sp)
    other = eng.submit(prompts[1], max_new=MAX_NEW)
    eng.run_until_drained()
    assert req.finish_reason == "stop_token"
    np.testing.assert_array_equal(req.result().tokens, refs[0].tokens[:3])
    # the surviving row is untouched by its batchmate's early exit
    np.testing.assert_array_equal(other.result().tokens, refs[1].tokens)
    assert other.finish_reason == "length"


def test_step_event_stream(setup):
    """step() returns the per-step event stream: TokenEvents for every
    token (first= marks TTFT), FinishEvents at retirement, and did_work
    distinguishing real work from idle steps. The stream IS the output:
    tokens reassembled from events match the request records exactly."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0)
    r0 = eng.submit(prompts[0], max_new=MAX_NEW)
    r1 = eng.submit(prompts[1], max_new=MAX_NEW)
    ev = eng.step()
    assert ev.did_work
    firsts = [e for e in ev if isinstance(e, TokenEvent) and e.first]
    assert [(e.rid, e.index, e.first) for e in firsts] == \
        [(0, 0, True), (1, 0, True)]
    streams = {0: [], 1: []}
    finishes = {}
    for e in ev:
        if isinstance(e, TokenEvent):
            streams[e.rid].append(e.token)
    while not eng.idle:
        for e in eng.step():
            if isinstance(e, TokenEvent):
                assert not e.first
                streams[e.rid].append(e.token)
            elif isinstance(e, FinishEvent):
                finishes[e.rid] = e
    for rid, req in ((0, r0), (1, r1)):
        assert streams[rid] == req.tokens
        np.testing.assert_array_equal(np.asarray(streams[rid]),
                                      refs[rid].tokens)
        assert finishes[rid].reason == "length"
        assert finishes[rid].n_tokens == MAX_NEW + 1
    idle = eng.step()
    assert not idle.did_work and list(idle) == []


def test_reject_event_emitted(setup):
    """Admission sheds surface as RejectEvents in the step stream."""
    cfg, params, prompts, _ = setup
    queue = RequestQueue(AdmissionController(
        LatencyModel(prefill_per_token=100.0), default_ttft_slo=0.1))
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, queue=queue, temperature=0.0)
    doomed = eng.submit(prompts[0], max_new=2)
    ev = eng.step()
    rejects = [e for e in ev if isinstance(e, RejectEvent)]
    assert [e.rid for e in rejects] == [doomed.rid]
    assert doomed.state == "rejected"


def test_admission_rejects_unmeetable_tbt():
    """A per-request tbt_slo below the structurally achievable per-step gap
    is REJECTED outright (waiting never shrinks the steady-state gap); an
    achievable one admits. The chunk charged is what the engine would run:
    min(current budget, suggest_chunk(tbt_slo))."""
    ctl = AdmissionController(
        LatencyModel(prefill_per_token=0.1, decode_step=0.5))
    assert ctl.decide(0.0, 0.0, 8, 0, tbt_slo=0.4) is Admission.REJECT
    assert ctl.n_rejected == 1
    assert ctl.decide(0.0, 0.0, 8, 0, tbt_slo=0.6) is Admission.ADMIT
    # FIXED budget 10: the engine really runs 10-token chunks, so the gap
    # is 0.5 + 10*0.1 = 1.5s and a 1.0s target is structurally unmeetable
    assert ctl.decide(0.0, 0.0, 8, 0, chunk_budget=10,
                      tbt_slo=1.0) is Admission.REJECT
    # ADAPTIVE budget: the engine will shrink its chunk to this request's
    # tbt_slo (suggest_chunk(1.0) == 5), which fits exactly -> admit
    assert ctl.decide(0.0, 0.0, 8, 0, chunk_budget=10, tbt_slo=1.0,
                      chunk_adaptive=True) is Admission.ADMIT
    assert ctl.decide(0.0, 0.0, 8, 0, chunk_budget=10, tbt_slo=0.50,
                      chunk_adaptive=True) is Admission.REJECT  # floor busts
    assert ctl.model.predict_tbt(5) == pytest.approx(1.0)
    assert ctl.model.predict_tbt(None) == pytest.approx(0.5)


def test_auto_budget_respects_request_tbt_slo(setup):
    """prefill_budget='auto' tightens the chunk to the minimum tbt_slo
    across in-flight requests, not just the engine default."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0,
                               prefill_budget="auto", tbt_slo=100.0)
    m = eng.queue.admission.model
    assert eng._current_budget() == m.suggest_chunk(100.0)
    tight = eng.submit(prompts[0], max_new=2, tbt_slo=0.25)
    eng.step()
    assert tight.state in ("prefilling", "running")
    assert eng._current_budget() == \
        eng.queue.admission.model.suggest_chunk(0.25)
    eng.run_until_drained()
    np.testing.assert_array_equal(tight.result().tokens, refs[0].tokens[:3])


# -- sparse grouped-expert decode/prefill exactness battery ----------------

@pytest.mark.parametrize("B", [1, 2, 4, 8])
@pytest.mark.parametrize("budget", [None, 4])
def test_grouped_decode_exactness_battery(setup, B, budget):
    """Segment-gathered decode + fused prefill are bit-exact vs BOTH the
    dense full-batch discipline and the sequential reference, for every
    batch width x {monolithic, chunked} prefill. Eight requests share B KV
    slots, so every B < 8 exercises mid-flight admission; the request list
    repeats each prompt, so rows with duplicate expert selections coexist
    with divergent ones. The expert-HBM bound is asserted after EVERY
    step on the grouped engine."""
    from test_residency import assert_residency_invariants
    cfg, params, prompts, refs = setup
    reqs = prompts * 2

    def drain(grouped):
        eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=B,
                                   max_seq=32, temperature=0.0,
                                   prefill_budget=budget,
                                   grouped_decode=grouped,
                                   fused_prefill=grouped)
        for p in reqs:
            eng.submit(p, max_new=MAX_NEW)
        for _ in range(10_000):
            eng.step()
            assert_residency_invariants(eng.cache)
            if eng.idle:
                break
        return eng, sorted(eng.finished, key=lambda r: r.rid)

    grp_eng, grp = drain(True)
    dense_eng, dense = drain(False)
    assert len(grp) == len(dense) == len(reqs)
    for i, (g, d) in enumerate(zip(grp, dense)):
        np.testing.assert_array_equal(
            g.result().tokens, refs[i % len(prompts)].tokens,
            err_msg=f"request {i} diverged from sequential")
        np.testing.assert_array_equal(g.result().tokens, d.result().tokens)
        np.testing.assert_array_equal(g.result().decode_trace,
                                      d.result().decode_trace)
        assert g.result().prefill_active == d.result().prefill_active
    # sparse discipline: one FFN launch per decode layer, and never more
    # row evaluations than the dense path
    assert grp_eng.perf.decode_ffn_launches == grp_eng.perf.decode_layers
    assert grp_eng.perf.decode_rows_grouped <= grp_eng.perf.decode_rows_dense
    assert dense_eng.perf.decode_rows_launched == \
        dense_eng.perf.decode_rows_dense
    if budget is not None:
        assert grp_eng.perf.max_prefill_launches_per_layer == 1


def test_grouped_decode_identical_rows(setup):
    """Degenerate grouping: all rows are the SAME prompt, so every decode
    step selects identical experts across the whole batch (one maximal
    group per distinct expert, U == the row's own selection count) — the
    grouped path must still match the sequential reference bit-exactly."""
    cfg, params, prompts, refs = setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0)
    for _ in range(4):
        eng.submit(prompts[0], max_new=MAX_NEW)
    finished = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    assert len(finished) == 4
    for r in finished:
        np.testing.assert_array_equal(r.result().tokens, refs[0].tokens)
    # every step's groups cover all B rows per selected expert
    assert eng.perf.decode_rows_grouped == \
        eng.perf.decode_rows_dense


def test_queue_sheds_breached_requests(setup):
    """A pessimistic cost model + tight deadline: the queue rejects instead
    of wasting a KV slot on an unmeetable request."""
    cfg, params, prompts, _ = setup
    queue = RequestQueue(AdmissionController(
        LatencyModel(prefill_per_token=100.0), default_ttft_slo=0.1))
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, queue=queue, temperature=0.0)
    eng.submit(prompts[0], max_new=2)
    eng.submit(prompts[1], max_new=2, ttft_slo=1e6)  # generous deadline
    finished = eng.run_until_drained(max_steps=20)
    assert len(queue.rejected) == 1
    assert queue.rejected[0].state == "rejected"
    assert len(finished) == 1 and finished[0].rid == 1
