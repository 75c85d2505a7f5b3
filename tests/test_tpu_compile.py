"""Compile the chip path for a described TPU v5e, at mixtral-8x7b widths.

No chip is attached: ``jax.experimental.topologies`` describes one, and the
TPU compiler refuses here what the chip would refuse — a kernel tile that
overflows VMEM or breaks the lane layout, a program that does not fit HBM.
Interpret mode, which every other kernel test uses, catches neither. Each
program's ``memory_analysis()`` must stay under the chip's 16 GB.

The topology is described inside a module-scoped fixture, never at import,
so every test worker collects the same tests (only the worker given this
file loads the TPU library). The persistent compilation cache is off around
these compiles: an entry compiled for a described chip cannot be read back
without one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9   # TPU v5e
POOL_SLOTS = 16          # the smoke's duo capacity: 2 * top_k * max_batch
B, W, C = 4, 640, 128    # decode batch, KV slots per request, prefill chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


@pytest.fixture(scope="module")
def mixtral(one_chip):
    """(cfg at two layers, layer-0 param shapes, moe-router shapes, shape
    helper), all placed on the described chip."""
    from repro.configs.base import get_config
    from repro.models.model import init_without_experts

    cfg = dataclasses.replace(get_config("mixtral-8x7b"), n_layers=2)

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(init_without_experts(cfg), jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: S(a.shape[1:], a.dtype),
                         {k: v for k, v in params["layers"].items()
                          if k != "moe"})
    moe = jax.tree.map(lambda a: S(a.shape[1:], a.dtype),
                       params["layers"]["moe"])
    top = {k: S(params[k].shape, params[k].dtype) for k in ("embed", "ln_f")}
    return cfg, layer, moe, top, S


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit HBM"
    return total


@pytest.mark.parametrize("rows", [8, C])
def test_expert_ffn_default_tile_compiles(mixtral, rows):
    """The default hidden tile fits VMEM at d=4096, f=14336 (512 does
    not), decode-sized and prefill-chunk-sized row groups alike."""
    from repro.kernels.expert_ffn import expert_ffn
    cfg, *_, S = mixtral
    d, f, E = cfg.d_model, cfg.d_expert, 4
    c = expert_ffn.lower(S((E, rows, d)), S((E, d, f)), S((E, d, f)),
                         S((E, f, d))).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_flash_attention_gqa_compiles(mixtral):
    from repro.kernels.flash_attention import flash_attention
    cfg, *_, S = mixtral
    q = S((1, cfg.n_heads, 512, cfg.hd))
    kv = S((1, cfg.n_kv_heads, 512, cfg.hd))
    c = flash_attention.lower(q, kv, kv, block_q=128, block_k=128).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def test_non_expert_init_builds_no_expert_stack(mixtral, one_chip):
    """The device init drops the expert generation: its whole footprint
    stays far below one layer's experts (8 x 352 MB)."""
    from repro.models.model import init_without_experts
    cfg = mixtral[0]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    c = init_without_experts(cfg).lower(key).compile()
    assert _fits(c) < 2 * 10**9


def _engine_calls(cfg, layer, moe, top, S):
    """(kernel attribute, argument shapes) for each per-layer program the
    batched engine runs on the chip path."""
    d, de, hkv, hd = cfg.d_model, cfg.d_expert, cfg.n_kv_heads, cfg.hd
    i32 = jnp.int32
    pools = (S((POOL_SLOTS, d, de)), S((POOL_SLOTS, d, de)),
             S((POOL_SLOTS, de, d)))
    kv1, kvB = S((1, W, hkv, hd)), S((B, W, hkv, hd))
    U = min(cfg.n_experts, B * cfg.top_k)
    return {
        "attn_prefill_chunk": ("_attn_prefill_chunk", (
            layer, S((1, C, d)), kv1, kv1, S((1, W), i32), S((), i32))),
        "attn_decode_batched": ("_attn_decode_batched", (
            layer, S((B, 1, d)), kvB, kvB, S((B, W), i32), S((B,), i32),
            S((B,), i32))),
        "gate_chunk": ("_gate", (moe, layer, S((1, C, d)))),
        "gate_decode": ("_gate", (moe, layer, S((B, 1, d)))),
        "expert_apply_chunk": ("_expert", (
            S((1, C, d)), *pools, S((), i32), S((C,), jnp.float32))),
        "grouped_ffn_decode": ("_grouped_raw", (
            S((B, 1, d)), S((U, B), i32), *pools, S((U,), i32))),
        "head": ("_head", (top["ln_f"], top["embed"], S((B, d)))),
    }


@pytest.mark.parametrize("name", [
    "attn_prefill_chunk", "attn_decode_batched", "gate_chunk", "gate_decode",
    "expert_apply_chunk", "grouped_ffn_decode", "head"])
def test_engine_program_compiles(mixtral, name):
    from repro.serving.engine import EngineCore
    cfg, layer, moe, top, S = mixtral
    attr, args = _engine_calls(cfg, layer, moe, top, S)[name]
    fn = EngineCore._jit_fns(cfg)[attr]
    _fits(fn.lower(*args).compile())


def test_grouped_ffn_pallas_pool_kernel_compiles(mixtral):
    """The REPRO_OPT_GROUPED_FFN backend: the Pallas kernel straight off
    the residency pools, as the engine calls it."""
    from repro.kernels.expert_ffn import expert_ffn_from_pool
    cfg, *_, S = mixtral
    d, de = cfg.d_model, cfg.d_expert
    U = min(cfg.n_experts, B * cfg.top_k)
    fn = jax.jit(expert_ffn_from_pool)
    c = fn.lower(S((U, B, d)), S((POOL_SLOTS, d, de)), S((POOL_SLOTS, d, de)),
                 S((POOL_SLOTS, de, d)), S((U,), jnp.int32)).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)
