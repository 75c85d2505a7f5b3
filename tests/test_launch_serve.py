"""The serving launcher's path (repro.launch.serve, which chip_smoke.py
drives at published widths), its host-side expert store, and the entry
points' compile-cache placement — at reduced widths on the CPU."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core.cache import HostExpertStore
from repro.launch import serve as S
from repro.launch.compile_cache import REPO_CACHE_DIR, cache_dir_to_set


def test_store_from_seed_is_seeded_and_scaled():
    """Expert (l, e) depends on (seed, l, e) only — not on the number of
    worker threads — and has the fan-in-scaled std of moe_params."""
    a = HostExpertStore.from_seed(2, 3, 64, 96, seed=5, workers=1)
    b = HostExpertStore.from_seed(2, 3, 64, 96, seed=5, workers=4)
    c = HostExpertStore.from_seed(2, 3, 64, 96, seed=6)
    assert set(a.weights) == {(l, e) for l in range(2) for e in range(3)}
    for key in a.weights:
        for x, y, z in zip(a.get(key), b.get(key), c.get(key)):
            assert x.dtype == jnp.bfloat16
            np.testing.assert_array_equal(x, y)
            assert not np.array_equal(x, z)
    w1, w3, w2 = a.get((1, 2))
    assert w1.shape == w3.shape == (64, 96) and w2.shape == (96, 64)
    assert a.bytes_per_expert == 3 * 64 * 96 * 2
    for w, fan_in in ((w1, 64), (w2, 96)):
        std = float(np.asarray(w, np.float32).std())
        assert abs(std * fan_in ** 0.5 - 1.0) < 0.05


def test_engine_shares_a_caller_store():
    """Engines built on launcher weights share the one host store (params
    carry no expert stacks); a store of the wrong shape is refused."""
    from repro.serving.batching import BatchedServingEngine
    cfg = reduced(get_config("mixtral-8x7b"))
    params, store = S.serving_weights(cfg, seed=0)
    assert set(params["layers"]["moe"]) == {"router"}
    eng = BatchedServingEngine(cfg, params, store=store, max_batch=2,
                               max_seq=32, temperature=0.0)
    assert eng.store is store
    small = HostExpertStore.from_seed(1, cfg.n_experts, cfg.d_model,
                                      cfg.d_expert)
    with pytest.raises(ValueError, match="expert store"):
        BatchedServingEngine(cfg, params, store=small, max_batch=2,
                             max_seq=32)


@pytest.mark.parametrize("mem_gib,cap,want", [
    (40, None, 12),   # (40 - 8) GiB of 2.63 GiB expert layers
    (40, 4, 4),       # capped for run time
    (200, None, 32),  # never past the published depth
])
def test_choose_depth(mem_gib, cap, want):
    cfg = get_config("mixtral-8x7b")
    assert S.choose_depth(cfg, mem_gib << 30, max_layers=cap) == want


def test_choose_depth_needs_two_layers():
    with pytest.raises(RuntimeError, match="at least 2"):
        S.choose_depth(get_config("mixtral-8x7b"), 12 << 30)


def test_serving_config_cuts_only_depth():
    cfg, cuts = S.serving_config("mixtral-8x7b", reduced_widths=False,
                                 layers=3)
    pub = get_config("mixtral-8x7b")
    assert cfg == dataclasses.replace(pub, n_layers=3)
    assert cuts == ["n_layers: 32 -> 3"]


def test_compile_cache_dir_placement():
    """An environment directory is left to JAX; otherwise the fixed
    repository directory, never one derived from time or process."""
    assert cache_dir_to_set({"JAX_COMPILATION_CACHE_DIR": "/x"}) is None
    assert cache_dir_to_set({}) == REPO_CACHE_DIR
    assert REPO_CACHE_DIR.endswith("/.jax_cache")


def test_launch_serves_through_frontend():
    """The launcher's whole path at reduced widths: traced predictor,
    warm-up, then every request through ServingFrontend with chunked
    prefill and batched decode under the expert-HBM bound."""
    args = S.parser().parse_args([
        "--reduced", "--prompt-len", "8,16,24", "--prefill-budget", "8",
        "--max-new", "3", "--max-batch", "3", "--trace-prompts", "2"])
    run = S.launch(args)
    eng = run.frontend.engine
    assert run.cfg.d_model == reduced(get_config("mixtral-8x7b")).d_model
    assert set(run.phase_s) == {"init", "trace", "warmup", "serve"}
    assert [len(p) for p in run.prompts] == [8, 16, 24]
    for h in run.handles:
        assert h.finish_reason == "length" and len(h.tokens) == 4
        assert all(0 <= t < run.cfg.vocab for t in h.tokens)
    assert eng.sched.predictor is not None
    assert eng.cache.hbm_bound_ok and eng.cache.transfer_log
    assert max(eng.decode_batch_hist) > 1
    assert eng.cache.capacity == 2 * run.cfg.top_k * 3
    assert run.hbm["expert_pools"] == eng.cache.device_bytes
    again = S.serve(run.frontend, run.prompts[:1], 3)[0]
    assert again.tokens == run.handles[0].tokens
    assert jax.default_backend() == "cpu"
