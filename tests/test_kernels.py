"""Per-kernel shape/dtype sweeps asserting allclose vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.expert_ffn import expert_ffn, expert_ffn_from_pool
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("E,C,d,f,bf", [
    (2, 16, 64, 128, 64),
    (4, 32, 128, 256, 128),
    (3, 8, 96, 192, 192),     # f == block (single tile)
    (1, 64, 256, 512, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_ffn(E, C, d, f, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (E, C, d), dtype)
    w1 = jax.random.normal(ks[1], (E, d, f), dtype) * 0.05
    w3 = jax.random.normal(ks[2], (E, d, f), dtype) * 0.05
    w2 = jax.random.normal(ks[3], (E, f, d), dtype) * 0.05
    got = expert_ffn(x, w1, w3, w2, block_f=bf, interpret=True)
    want = ref.expert_ffn_ref(x, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_expert_ffn_from_pool_matches_direct():
    """Slot-pool weight access convention: gathering the active experts'
    slabs out of oversized [pool_capacity, ...] residency buffers is
    bit-identical to running the kernel on directly stacked weights."""
    E, C, d, f, cap = 3, 8, 64, 128, 6
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (E, C, d), jnp.bfloat16)
    w1p = jax.random.normal(ks[1], (cap, d, f), jnp.bfloat16) * 0.05
    w3p = jax.random.normal(ks[2], (cap, d, f), jnp.bfloat16) * 0.05
    w2p = jax.random.normal(ks[3], (cap, f, d), jnp.bfloat16) * 0.05
    slots = [5, 0, 2]
    got = expert_ffn_from_pool(x, w1p, w3p, w2p, slots, block_f=64,
                               interpret=True)
    want = expert_ffn(x, w1p[jnp.asarray(slots)], w3p[jnp.asarray(slots)],
                      w2p[jnp.asarray(slots)], block_f=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_expert_ffn_from_pool_fused_prefill_parity():
    """Fused-prefill shape regime: segment-gathered [U, Cmax, d] rows (rows
    repeated across groups, zero-padded tails) through the pool kernel vs
    the grouped-einsum oracle the engine's default backend uses."""
    U, C, d, f, cap, T = 3, 8, 64, 128, 7, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    xt = jax.random.normal(ks[0], (T, d), jnp.bfloat16)
    w1p = jax.random.normal(ks[1], (cap, d, f), jnp.bfloat16) * 0.05
    w3p = jax.random.normal(ks[2], (cap, d, f), jnp.bfloat16) * 0.05
    w2p = jax.random.normal(ks[3], (cap, f, d), jnp.bfloat16) * 0.05
    row_idx = jax.random.randint(ks[4], (U, C), 0, T)   # dup + padded rows
    slots = [4, 0, 6]
    xg = xt[row_idx]                                    # [U, C, d]
    got = expert_ffn_from_pool(xg, w1p, w3p, w2p, slots, block_f=64,
                               interpret=True)
    sl = jnp.asarray(slots)
    w1, w3, w2 = w1p[sl], w3p[sl], w2p[sl]
    want = jnp.einsum(
        "ucf,ufd->ucd",
        jax.nn.silu(jnp.einsum("ucd,udf->ucf", xg, w1))
        * jnp.einsum("ucd,udf->ucf", xg, w3), w2).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **_tol(jnp.bfloat16))


def test_expert_ffn_block_f_fallback():
    """A d_expert that fits one 128-lane block runs as a single full-dim
    tile whatever block_f asks (96 with block_f=64 -> one 96 tile)."""
    E, C, d, f = 2, 8, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    x = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    w1 = jax.random.normal(ks[1], (E, d, f), jnp.float32) * 0.05
    w3 = jax.random.normal(ks[2], (E, d, f), jnp.float32) * 0.05
    w2 = jax.random.normal(ks[3], (E, f, d), jnp.float32) * 0.05
    got = expert_ffn(x, w1, w3, w2, block_f=64, interpret=True)
    want = ref.expert_ffn_ref(x, w1, w3, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("f,block_f,want", [
    (14336, 256, 256),    # mixtral: the VMEM-safe default tile
    (14336, 512, 512),
    (1408, 512, 128),     # only 128 of the lane multiples <= 512 divides
    (96, 64, 96),         # fits one block
    (384, 300, 128),      # a requested tile rounds down to lanes
])
def test_f_tile_picks_lane_multiples(f, block_f, want):
    from repro.kernels.expert_ffn import f_tile
    assert f_tile(f, block_f) == want


def test_f_tile_refuses_unlayoutable_tile():
    from repro.kernels.expert_ffn import f_tile
    with pytest.raises(ValueError, match="multiple of 128"):
        f_tile(1000, 512)


def test_interpret_mode_follows_backend():
    """Pallas kernels are interpreted on the CPU backend only; nothing in
    the environment turns the interpreter on for an accelerator."""
    from repro.kernels.ops import default_interpret
    assert default_interpret() == (jax.default_backend() == "cpu")


@pytest.mark.parametrize("B,H,Hkv,S,D,bq,bk", [
    (1, 2, 2, 64, 32, 32, 32),
    (2, 4, 2, 128, 64, 64, 32),    # GQA
    (1, 4, 1, 96, 32, 64, 64),     # MQA, ragged seq vs block
])
@pytest.mark.parametrize("window", [-1, 48])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, Hkv, S, D, bq, bk, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("BH,S,P,N,cl", [
    (2, 32, 16, 8, 8),
    (4, 64, 32, 16, 16),
    (1, 48, 16, 8, 32),    # ragged: S not multiple of chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(BH, S, P, N, cl, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (BH, S, P), dtype)
    b = jax.random.normal(ks[1], (BH, S, N), dtype) * 0.5
    c = jax.random.normal(ks[2], (BH, S, N), dtype) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (BH, S))) * 0.5
    da = -dt * jnp.exp(jax.random.normal(ks[4], (BH, S)) * 0.2)
    got = ssd_scan(x, b, c, da, dt, chunk=cl, interpret=True)
    want, _ = ref.ssd_scan_ref(x, b, c, da, dt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-3,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-3)


def test_expert_ffn_matches_model_moe():
    """The kernel computes the same grouped GEMMs the model's capacity path
    feeds — wire-level agreement with the dispatch buffers."""
    from repro.models import moe_layer as M
    from repro.configs.base import get_config, reduced
    cfg = reduced(get_config("mixtral_8x7b"))
    p = M.moe_params(jax.random.PRNGKey(0), cfg, n_model=1)
    T, d = 32, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (T, d), jnp.bfloat16)
    w, ids, _ = M.route(x, p["router"], cfg.n_experts, cfg.top_k)
    # build the capacity buffer exactly like the dispatch path, then compare
    # kernel vs ref on it
    got = expert_ffn(
        jnp.broadcast_to(x, (cfg.n_experts, T, d)), p["w1"], p["w3"], p["w2"],
        block_f=cfg.d_expert, interpret=True)
    want = ref.expert_ffn_ref(
        jnp.broadcast_to(x, (cfg.n_experts, T, d)), p["w1"], p["w3"], p["w2"])
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=3e-2,
                               atol=3e-2)


@pytest.mark.parametrize("B,H,Hkv,S,D,bk", [
    (2, 4, 2, 64, 32, 32),
    (1, 8, 1, 96, 64, 64),    # MQA + ragged
])
@pytest.mark.parametrize("window", [-1, 24])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(B, H, Hkv, S, D, bk, window, dtype):
    from repro.kernels.flash_decode import flash_decode
    from repro.models.layers import attention
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    pos = S - 10
    # ring semantics: some slots empty (-1), some beyond pos
    slot_pos = jnp.where(jnp.arange(S) < S - 4, jnp.arange(S), -1)
    got = flash_decode(q, k, v, slot_pos, jnp.int32(pos), window=window,
                       block_k=bk, interpret=True)
    want = attention(q[:, None].transpose(0, 1, 2, 3),
                     k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                     q_pos=jnp.full((B, 1), pos), k_pos=slot_pos[None],
                     window=window, causal=True)[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
