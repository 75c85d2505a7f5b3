"""Correctness of the §Perf optimization paths — each must be numerically
equivalent (or within capacity-drop semantics) to the baseline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, get_config, reduced
from repro.models import moe_layer as M
from repro.models.layers import (attention_chunked, attention_chunked_windowed,
                                 attention)


def test_windowed_chunked_matches_masked():
    B, S, H, Hkv, D, W = 2, 96, 4, 2, 32, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    got = attention_chunked_windowed(q, k, v, window=W, q_block=32,
                                     kv_block=16)
    want = attention(q, k, v, q_pos=jnp.arange(S)[None],
                     k_pos=jnp.arange(S)[None], window=W, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_windowed_chunked_window_larger_than_seq():
    B, S, H, D = 1, 40, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    got = attention_chunked_windowed(q, k, v, window=1024, q_block=16,
                                     kv_block=16)
    want = attention(q, k, v, q_pos=jnp.arange(S)[None],
                     k_pos=jnp.arange(S)[None], window=1024, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_bf16_tiles_close_to_f32():
    B, S, H, D = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
    base = attention_chunked(q, k, v, q_block=32, kv_block=32,
                             bf16_tiles=False)
    opt = attention_chunked(q, k, v, q_block=32, kv_block=32, bf16_tiles=True)
    np.testing.assert_allclose(np.asarray(base, np.float32),
                               np.asarray(opt, np.float32), rtol=3e-2,
                               atol=3e-2)


def _moe_cfg(E=16, k=2, d=32, de=16):
    return ArchConfig(name="t", family="moe", n_layers=1, d_model=d,
                      n_heads=2, n_kv_heads=2, d_ff=de, vocab=64,
                      n_experts=E, top_k=k, d_expert=de)


def test_active_gather_matches_dense_when_a_covers():
    """active_max >= #active experts => identical to dense dispatch."""
    cfg = _moe_cfg()
    p = M.moe_params(jax.random.PRNGKey(0), cfg, n_model=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, cfg.d_model)) * 0.5
    w, ids, _ = M.route(x, p["router"], cfg.n_experts, cfg.top_k)
    dense = M._dispatch_compute_combine(x, w, ids, p["w1"], p["w3"], p["w2"],
                                        capacity=12, e_start=0)
    # 6 tokens x k=2 -> at most 12 active experts; A=12 covers everything
    act = M._dispatch_compute_combine(x, w, ids, p["w1"], p["w3"], p["w2"],
                                      capacity=12, e_start=0, active_max=12)
    np.testing.assert_allclose(np.asarray(act), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_active_gather_drops_only_overflow():
    """With A smaller than active set, output differs only on tokens routed
    to the least-loaded (dropped) experts; finite everywhere."""
    cfg = _moe_cfg(E=8, k=1)
    p = M.moe_params(jax.random.PRNGKey(0), cfg, n_model=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model)) * 0.5
    w, ids, _ = M.route(x, p["router"], cfg.n_experts, cfg.top_k)
    act = M._dispatch_compute_combine(x, w, ids, p["w1"], p["w3"], p["w2"],
                                      capacity=16, e_start=0, active_max=4)
    assert np.isfinite(np.asarray(act)).all()


def test_active_gather_threshold():
    assert M.active_gather_max(4096, 8, 24, 384) is None  # large T: disabled
    import os
    os.environ["REPRO_OPT_ACTIVE_GATHER"] = "1"
    try:
        a = M.active_gather_max(8, 8, 24, 384)
        assert a is not None and 8 <= a <= 24
        assert M.active_gather_max(4096, 8, 24, 384) is None
    finally:
        os.environ["REPRO_OPT_ACTIVE_GATHER"] = "0"


def test_pattern_builder_matches_window_semantics():
    """Pattern-block gemma variant must produce the same logits as the
    standard scanned builder (same weights, different structure)."""
    from repro.models.model import build_dense, build_dense_pattern
    cfg = dataclasses.replace(reduced(get_config("gemma3_1b")), n_layers=4,
                              local_global_pattern=1, sliding_window=8)
    b1, b2 = build_dense(cfg), build_dense_pattern(cfg)
    p2 = b2.init(jax.random.PRNGKey(0))
    # remap pattern params [n_pat, per, ...] -> flat [L, ...]
    blocks = p2["blocks"]
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), blocks)
    p1 = {"embed": p2["embed"], "ln_f": p2["ln_f"], "layers": flat}
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    l1, _ = b1.forward(p1, {"tokens": toks})
    l2, _ = b2.forward(p2, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32), rtol=6e-2,
                               atol=6e-2)


def test_moe_dispatch_pallas_kernel_parity():
    """The Pallas expert_ffn kernel slot-in (REPRO_MOE_PALLAS) must match the
    einsum dispatch path bit-for-tolerance on the same capacity buffers
    (interpreted: the CPU backend decides interpret mode)."""
    cfg = _moe_cfg(E=4, k=2, d=64, de=128)
    p = M.moe_params(jax.random.PRNGKey(0), cfg, n_model=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.d_model)) * 0.3
    w, ids, _ = M.route(x, p["router"], cfg.n_experts, cfg.top_k)
    base = M._dispatch_compute_combine(x, w, ids, p["w1"], p["w3"], p["w2"],
                                       capacity=24, e_start=0)
    pk = M._dispatch_compute_combine(x, w, ids, p["w1"], p["w3"], p["w2"],
                                     capacity=24, e_start=0, use_pallas=True)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(base), rtol=2e-3,
                               atol=2e-3)


# -- sparse grouped-expert execution (serving engines) ---------------------

@pytest.fixture(scope="module")
def moe_serving_setup():
    from repro.models.model import build
    cfg = reduced(get_config("mixtral_8x7b"))
    bundle = build(cfg)
    return cfg, bundle.init(jax.random.PRNGKey(0))


def test_group_by_expert_patterns():
    """Host-side dispatch construction: duplicate-expert rows (all rows the
    same picks), fully disjoint picks, and Cmax bucketing."""
    from repro.serving.engine import group_by_expert
    # all rows pick the same two experts -> two maximal groups of size B
    ids = np.tile(np.array([[2, 5]], np.int32), (4, 1))
    d = group_by_expert(ids, [2, 5], bucket_cap=4)
    assert d.counts == [4, 4] and d.n_rows == 8 and d.n_launched == 8
    np.testing.assert_array_equal(d.row_idx, [[0, 1, 2, 3], [0, 1, 2, 3]])
    np.testing.assert_array_equal(d.u_of, [[0, 1]] * 4)
    np.testing.assert_array_equal(d.c_of, [[0, 0], [1, 1], [2, 2], [3, 3]])
    # fully disjoint picks -> 2B singleton groups, bucketed capacity 1
    ids = np.arange(8, dtype=np.int32).reshape(4, 2)
    d = group_by_expert(ids, list(range(8)), bucket_cap=4)
    assert d.counts == [1] * 8 and d.row_idx.shape == (8, 1)
    assert d.n_rows == 8 and d.n_launched == 8
    # mixed loads: Cmax=3 buckets up to 4 (power of two), padding rows 0
    ids = np.array([[0, 1], [0, 2], [0, 1], [3, 1]], np.int32)
    d = group_by_expert(ids, [0, 1, 2, 3], bucket_cap=4)
    assert d.counts == [3, 3, 1, 1]
    assert d.row_idx.shape == (4, 4) and d.n_rows == 8 and d.n_launched == 16
    # scatter inversion: row t's j-th choice lands at (u_of, c_of)
    for t in range(4):
        for j in range(2):
            u, c = d.u_of[t, j], d.c_of[t, j]
            assert d.row_idx[u, c] == t and c < d.counts[u]


def test_grouped_raw_bitexact_vs_dense(moe_serving_setup):
    """The one-launch grouped einsum must reproduce the dense full-batch
    expert_raw rows BIT-exactly (same dtypes, same per-row contraction) —
    the invariant the grouped decode path's exactness rests on."""
    from repro.serving.batching import BatchedServingEngine
    from repro.serving.engine import group_by_expert
    cfg, params = moe_serving_setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=4,
                               max_seq=32, temperature=0.0)
    B, d, de, cap = 4, cfg.d_model, cfg.d_expert, 6
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    xn = jax.random.normal(ks[0], (B, 1, d), jnp.bfloat16)
    w1p = jax.random.normal(ks[1], (cap, d, de), jnp.bfloat16) * 0.05
    w3p = jax.random.normal(ks[2], (cap, d, de), jnp.bfloat16) * 0.05
    w2p = jax.random.normal(ks[3], (cap, de, d), jnp.bfloat16) * 0.05
    ids = np.array([[0, 1], [0, 1], [2, 0], [1, 2]], np.int32)
    union = [0, 1, 2]   # "expert" e lives in pool slot e here
    disp = group_by_expert(ids, union, bucket_cap=B)
    grouped = np.asarray(eng._grouped_raw(
        xn, jnp.asarray(disp.row_idx), w1p, w3p, w2p,
        jnp.asarray(union, jnp.int32)))
    for u, e in enumerate(union):
        dense = np.asarray(eng._expert_raw(xn, w1p, w3p, w2p, jnp.int32(e)))
        for c in range(disp.counts[u]):
            np.testing.assert_array_equal(
                grouped[u, c], dense[disp.row_idx[u, c]],
                err_msg=f"group {u} row {c} not bit-equal to dense")


@pytest.mark.parametrize("chunk", [None, 3])
def test_fused_prefill_bit_exact_single_launch(moe_serving_setup, chunk):
    """fused_prefill=True: one grouped FFN launch per layer visit, tokens
    and per-layer active-expert sets bit-identical to the per-expert
    sweep — monolithic and chunked."""
    from repro.serving.engine import MoEServingEngine
    cfg, params = moe_serving_setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, size=13).astype(np.int32)
    base = MoEServingEngine(cfg, params, policy="duo", temperature=0.0,
                            prefill_chunk=chunk)
    fused = MoEServingEngine(cfg, params, policy="duo", temperature=0.0,
                             prefill_chunk=chunk, fused_prefill=True)
    rb = base.serve(prompt, max_new=4)
    rf = fused.serve(prompt, max_new=4)
    np.testing.assert_array_equal(rf.tokens, rb.tokens)
    assert rf.prefill_active == rb.prefill_active
    assert fused.perf.prefill_ffn_launches == fused.perf.prefill_moe_layers
    assert fused.perf.max_prefill_launches_per_layer == 1
    assert base.perf.max_prefill_launches_per_layer > 1


def test_grouped_ffn_pallas_backend_runs(monkeypatch, moe_serving_setup):
    """REPRO_OPT_GROUPED_FFN=1 routes both grouped sweeps through the
    Pallas pool kernel (interpret mode on CPU) and defaults fused prefill
    on: the engine must run end to end with one FFN launch per decode
    layer and per prefill layer under the expert-HBM bound. Numerics are
    kernel-grade (f32 accumulation — pinned by the interpret parity tests
    in test_kernels.py), so tokens are not compared bit-wise here."""
    monkeypatch.setenv("REPRO_OPT_GROUPED_FFN", "1")
    from repro.serving.batching import BatchedServingEngine
    cfg, params = moe_serving_setup
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=2,
                               max_seq=32, temperature=0.0, prefill_budget=4)
    assert eng.fused_prefill and eng._grouped_pallas
    rng = np.random.default_rng(5)
    for n in (9, 12):
        eng.submit(rng.integers(0, cfg.vocab, size=n).astype(np.int32),
                   max_new=3)
    finished = eng.run_until_drained()
    assert len(finished) == 2
    for r in finished:
        toks = r.result().tokens
        assert toks.shape == (4,) and np.isfinite(toks).all()
    assert eng.perf.decode_ffn_launches == eng.perf.decode_layers
    assert eng.perf.max_prefill_launches_per_layer == 1
    assert eng.cache.hbm_bound_ok


def test_decode_expert_flops_accounting():
    """benchmarks/roofline sparse accounting: grouped = sum of per-expert
    selecting-row counts, dense = distinct experts x full batch."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.roofline import decode_expert_flops, expert_flops_per_row
    cfg = _moe_cfg(E=8, k=2, d=32, de=16)
    sel = np.array([[0, 1], [0, 1], [2, 0], [3, 4]], np.int32)
    out = decode_expert_flops(cfg, sel)
    # distinct = {0,1,2,3,4} -> dense 5*4 rows; selecting rows: e0 in 3
    # rows, e1 in 2, e2/e3/e4 in 1 each -> 8 (== B*k, no within-row dups)
    assert out["dense_rows"] == 20 and out["grouped_rows"] == 8
    per = expert_flops_per_row(cfg)
    assert out["dense_flops"] == 20 * per
    assert out["grouped_flops"] == 8 * per
    # duplicate-heavy batch: all rows same picks -> dense == grouped
    sel = np.tile(np.array([[5, 6]], np.int32), (4, 1))
    out = decode_expert_flops(cfg, sel)
    assert out["dense_rows"] == out["grouped_rows"] == 8


def test_ring_cache_wraparound_exact():
    """Windowed ring-buffer decode must equal full-cache windowed attention
    even after the ring wraps several times (slot reuse + masking)."""
    import dataclasses as dc
    from repro.configs.base import get_config, reduced
    from repro.models.model import build, pad_cache
    cfg = dc.replace(reduced(get_config("zamba2_7b")), n_layers=2,
                     hybrid_attn_every=1, sliding_window=6)
    b = build(cfg)
    params = b.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 26), 0, cfg.vocab)
    # full teacher-forced forward (windowed masking, no ring buffer)
    full, _ = b.forward(params, {"tokens": toks})
    # prefill 6 then decode 20 steps through the W=6 ring (wraps 3x)
    _, cache = b.prefill(params, {"tokens": toks[:, :6]})
    logits = []
    for t in range(6, 26):
        lg, cache = b.decode_step(params, {"token": toks[:, t:t + 1]}, cache)
        logits.append(lg)
    got = np.stack([np.asarray(l, np.float32) for l in logits], 1)[0]
    want = np.asarray(full[0, 6:26], np.float32)
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=6e-2, atol=6e-2)


def test_group_by_expert_u_bucketing():
    """u_bucket_cap pads the GROUP dimension to the next power of two
    (clamped to the cap) without disturbing any real group's contents:
    padding rows gather row 0 and are never scattered back (counts, u_of,
    c_of cover only the real groups)."""
    from repro.serving.engine import group_by_expert
    ids = np.array([[0, 1], [0, 2], [0, 1], [2, 1]], np.int32)
    union = [0, 1, 2]  # 3 distinct experts -> pads to 4 groups
    exact = group_by_expert(ids, union, bucket_cap=4)
    padded = group_by_expert(ids, union, bucket_cap=4, u_bucket_cap=8)
    assert exact.row_idx.shape[0] == 3          # None keeps exact U
    assert padded.row_idx.shape[0] == 4         # next pow2 >= 3
    assert padded.counts == exact.counts        # real groups untouched
    np.testing.assert_array_equal(padded.row_idx[:3], exact.row_idx)
    np.testing.assert_array_equal(padded.u_of, exact.u_of)
    np.testing.assert_array_equal(padded.c_of, exact.c_of)
    np.testing.assert_array_equal(padded.row_idx[3], 0)  # pad gathers row 0
    assert padded.n_rows == exact.n_rows        # accounting excludes pads
    # cap clamps below the next power of two
    clamped = group_by_expert(ids, union, bucket_cap=4, u_bucket_cap=3)
    assert clamped.row_idx.shape[0] == 3


def test_decode_recompile_bound_olog(moe_serving_setup):
    """Serving sweep across B in {1..8} x naturally varying U: the grouped
    decode FFN's distinct jit compilations stay within the enumerated
    (B, U_pad, C) key set and the O(log B)*O(log U) bound — the recompile
    discipline repro.analysis audits statically, asserted here against the
    LIVE jit cache-miss counter."""
    from repro.analysis.jaxpr_audit import (compile_key_bound,
                                            enumerate_grouped_keys)
    from repro.serving.batching import BatchedServingEngine
    cfg, params = moe_serving_setup
    MB = 8
    eng = BatchedServingEngine(cfg, params, policy="duo", max_batch=MB,
                               max_seq=48, temperature=0.0,
                               fused_prefill=False)
    orig = eng._grouped_raw
    sigs = []

    def spy(xn, jrows, *pools_and_slots):
        sigs.append((tuple(xn.shape), tuple(jrows.shape)))
        return orig(xn, jrows, *pools_and_slots)

    eng._grouped_raw = spy
    rng = np.random.default_rng(3)
    for b in range(MB):
        prompt = rng.integers(0, cfg.vocab, size=4 + b).astype(np.int32)
        eng.submit(prompt, max_new=b + 1)   # distinct lifetimes: B walks
    eng.run_until_drained()                 # 8 -> 1 as requests retire
    eng._grouped_raw = orig

    assert sigs, "grouped decode path never ran"
    keys = set()
    for (B, one, _d), (u_pad, c) in sigs:
        assert one == 1, "non-decode launch leaked through _grouped_raw"
        keys.add((B, u_pad, c))
    seen_B = {key[0] for key in keys}
    assert seen_B == set(range(1, MB + 1)), f"sweep missed batch sizes: {seen_B}"
    # every observed key is one the static auditor enumerates, and the
    # distinct-count respects the paper-claim bound
    legal = enumerate_grouped_keys(MB, eng.E, eng.k)
    assert keys <= legal, f"stray compile keys: {sorted(keys - legal)}"
    bound = compile_key_bound(MB, eng.E, eng.k)
    assert len(keys) <= bound
    # pow2-or-clamp discipline on the padded dims
    for B, u_pad, c in keys:
        ucap = min(eng.E, B * eng.k)
        assert u_pad == ucap or (u_pad & (u_pad - 1)) == 0
        assert c == B or (c & (c - 1)) == 0
    # the LIVE cache-miss counter: one compilation per distinct signature
    if hasattr(orig, "_cache_size"):
        assert orig._cache_size() == len(set(sigs)) <= bound
