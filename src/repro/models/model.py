"""Unified model builder for all assigned architecture families.

Every family exposes the same four entry points through ``build(cfg)``:

  * ``init(key)``            -> params pytree (bf16; call under eval_shape for
                                 abstract dry-run params)
  * ``forward(params, batch)``-> (logits [B,S,Vp], aux) — teacher-forced, used
                                 by train_step
  * ``prefill(params, batch)``-> (last_logits [B,Vp], cache)
  * ``decode_step(params, step, cache)`` -> (logits [B,Vp], cache)

Layers are stacked and driven by ``lax.scan`` so compile time is O(1) in
depth (88–100-layer configs lower in seconds). Heterogeneous stacks use
pattern-block nesting (VLM: 20×[4 self + 1 cross]; Zamba2: 13×[6 mamba +
shared-attn] + 3 tail) instead of per-layer branching.

Decode uses a ring-buffer KV cache with absolute slot positions (exact for
sliding-window and bounded long-context decode). ``step`` = {'token': [B,1]}.
``batch`` = {'tokens': [B,S]} (+ 'patch_embeds' for vlm, 'frames' for
audio enc-dec).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe_layer as M
from repro.models import ssm as S
from repro.models.layers import PDT

CHUNKED_MIN_SEQ = 2048  # use flash-style chunked attention above this length


def attn_dims(cfg: ArchConfig) -> L.AttnDims:
    return L.AttnDims(cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                      cfg.qk_norm, cfg.qkv_bias, cfg.rope_theta, cfg.rms_eps)


def _stack_init(fn: Callable, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _logits(x, embed):
    return (x @ embed.T.astype(x.dtype))


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig
    init: Callable
    forward: Callable      # (params, batch) -> (logits, aux)
    prefill: Callable      # (params, batch) -> (last_logits, cache)
    decode_step: Callable  # (params, step, cache) -> (logits, cache)
    init_cache: Callable   # (batch_size, capacity, batch_extras) -> cache
    ring_axes: Dict[str, int] = dataclasses.field(default_factory=dict)


def pad_cache(cache, new_capacity: int, ring_axes: Dict[str, int]):
    """Grow ring-buffer KV caches to `new_capacity` slots.

    Valid immediately after prefill (entries at slot == pos, or a full rolled
    ring): appended empty slots keep the invariant slot == pos % capacity as
    long as the prefill length <= old capacity <= new capacity.
    """
    new = dict(cache)
    for k, ax in ring_axes.items():
        if k not in cache:
            continue
        arr = cache[k]
        extra = new_capacity - arr.shape[ax]
        if extra <= 0:
            continue
        pads = [(0, 0)] * arr.ndim
        pads[ax] = (0, extra)
        new[k] = jnp.pad(arr, pads)
    sp = cache.get("slot_pos")
    if sp is not None and sp.shape[0] < new_capacity:
        new["slot_pos"] = jnp.pad(sp, (0, new_capacity - sp.shape[0]),
                                  constant_values=-1)
    return new


# ---------------------------------------------------------------------------
# dense / moe decoder family (qwen3, granite, qwen1.5, gemma3, qwen2-moe,
# kimi-k2, mixtral)
# ---------------------------------------------------------------------------


def _dense_block_params(key, cfg: ArchConfig, d_ff: int):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.norm_params(cfg.d_model),
        "attn": L.attn_params(k1, attn_dims(cfg)),
        "ln2": L.norm_params(cfg.d_model),
        "mlp": L.mlp_params(k2, cfg.d_model, d_ff),
    }


def _moe_block_params(key, cfg: ArchConfig, n_model: int):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.norm_params(cfg.d_model),
        "attn": L.attn_params(k1, attn_dims(cfg)),
        "ln2": L.norm_params(cfg.d_model),
        "moe": M.moe_params(k2, cfg, n_model),
    }


def _ffn_apply(xn, lp, cfg: ArchConfig, mesh_info, dropless: bool):
    if "moe" in lp:
        cf = None
        if dropless:
            cf = float(cfg.top_k * M.n_experts_padded(cfg))  # => C = T*k
        return M.moe_ffn(xn, lp["moe"], cfg, mesh_info=mesh_info,
                         capacity_factor=cf)
    return L.swiglu(xn, lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"]), 0.0


def _seq_parallel_pin(x, mesh_info):
    """REPRO_OPT_SEQ_PARALLEL (§Perf): residual stream seq-sharded over the
    tensor axis between blocks -> GSPMD lowers the block-output all-reduces
    to reduce-scatter + all-gather (Megatron sequence parallelism)."""
    from repro.models import opt_flags
    if mesh_info is None or not opt_flags.seq_parallel() or x.ndim != 3:
        return x
    mesh, dp, tp = mesh_info["mesh"], mesh_info["dp"], mesh_info["tp"]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if x.shape[0] % n_dp or x.shape[1] % mesh.shape[tp]:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, tp, None)))


def _block_full(x, lp, win, cfg, mesh_info, chunked, emit_kv):
    h, kv = L.self_attn_full(L.rms_norm(x, lp["ln1"], cfg.rms_eps), lp["attn"],
                             attn_dims(cfg), window=win, chunked=chunked)
    x = _seq_parallel_pin(x + h, mesh_info)
    y, aux = _ffn_apply(L.rms_norm(x, lp["ln2"], cfg.rms_eps), lp, cfg,
                        mesh_info, dropless=False)
    return _seq_parallel_pin(x + y, mesh_info), (kv if emit_kv else None), aux


def _block_decode(x, lp, win, cfg, mesh_info, ck, cv, sp, slot, pos):
    h, ck, cv = L.self_attn_decode(
        L.rms_norm(x, lp["ln1"], cfg.rms_eps), lp["attn"], attn_dims(cfg),
        ck, cv, sp, slot, pos, window=win)
    x = x + h
    y, aux = _ffn_apply(L.rms_norm(x, lp["ln2"], cfg.rms_eps), lp, cfg,
                        mesh_info, dropless=True)
    return x + y, ck, cv, aux


def build_dense(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    vp = L.vocab_pad_of(cfg.vocab)
    n_model = mesh_info["mesh"].shape[mesh_info["tp"]] if mesh_info else 16
    n_scan = cfg.n_layers - cfg.n_dense_layers
    windows = jnp.array(
        [cfg.window_for_layer(l) for l in range(cfg.n_dense_layers, cfg.n_layers)],
        jnp.int32)

    def init(key):
        ks = jax.random.split(key, 4)
        block = ((lambda k: _moe_block_params(k, cfg, n_model)) if cfg.is_moe
                 else (lambda k: _dense_block_params(k, cfg, cfg.d_ff)))
        p = {
            "embed": L.embed_params(ks[0], vp, cfg.d_model),
            "ln_f": L.norm_params(cfg.d_model),
            "layers": _stack_init(block, ks[1], n_scan),
        }
        if cfg.n_dense_layers:
            p["dense0"] = _stack_init(
                lambda k: _dense_block_params(k, cfg, cfg.dense_d_ff),
                ks[2], cfg.n_dense_layers)
        return p

    def forward(params, batch):
        tokens = batch["tokens"]
        Bsz, Ssz = tokens.shape
        x = params["embed"].at[tokens].get(mode="clip")
        chunked = Ssz >= CHUNKED_MIN_SEQ
        aux0 = 0.0
        if cfg.n_dense_layers:
            @jax.checkpoint
            def body0(carry, lp):
                xx, aux = carry
                xx, _, a = _block_full(xx, lp, jnp.int32(-1), cfg, mesh_info,
                                       chunked, False)
                return (xx, aux + a), None
            (x, aux0), _ = lax.scan(body0, (x, 0.0), params["dense0"])

        @jax.checkpoint
        def body(carry, xs):
            xx, aux = carry
            lp, win = xs
            xx, _, a = _block_full(xx, lp, win, cfg, mesh_info, chunked, False)
            return (xx, aux + a), None

        (x, aux), _ = lax.scan(body, (x, aux0), (params["layers"], windows))
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x, params["embed"]), aux / cfg.n_layers

    def prefill(params, batch):
        tokens = batch["tokens"]
        Bsz, Ssz = tokens.shape
        x = params["embed"].at[tokens].get(mode="clip")
        chunked = Ssz >= CHUNKED_MIN_SEQ
        caches = {}
        if cfg.n_dense_layers:
            def body0(carry, lp):
                xx, kv, a = _block_full(carry, lp, jnp.int32(-1), cfg,
                                        mesh_info, chunked, True)
                return xx, kv
            x, kv0 = lax.scan(body0, x, params["dense0"])
            caches["k0"], caches["v0"] = kv0

        def body(carry, xs):
            lp, win = xs
            xx, kv, a = _block_full(carry, lp, win, cfg, mesh_info, chunked, True)
            return xx, kv

        x, (ks_, vs_) = lax.scan(body, x, (params["layers"], windows))
        caches["k"], caches["v"] = ks_, vs_
        caches["slot_pos"] = jnp.arange(Ssz, dtype=jnp.int32)
        caches["pos"] = jnp.int32(Ssz)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), caches

    def decode_step(params, step, cache):
        token = step["token"]
        x = params["embed"].at[token].get(mode="clip")
        pos = cache["pos"]
        W = cache["k"].shape[2]
        slot = pos % W
        sp = lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
        new = dict(cache)
        if cfg.n_dense_layers:
            def body0(carry, xs):
                lp, ck, cv = xs
                xx, ck, cv, _ = _block_decode(carry, lp, jnp.int32(-1), cfg,
                                              mesh_info, ck, cv, sp, slot, pos)
                return xx, (ck, cv)
            x, (k0, v0) = lax.scan(body0, x,
                                   (params["dense0"], cache["k0"], cache["v0"]))
            new["k0"], new["v0"] = k0, v0

        def body(carry, xs):
            lp, win, ck, cv = xs
            xx, ck, cv, _ = _block_decode(carry, lp, win, cfg, mesh_info,
                                          ck, cv, sp, slot, pos)
            return xx, (ck, cv)

        x, (ks_, vs_) = lax.scan(
            body, x, (params["layers"], windows, cache["k"], cache["v"]))
        new.update(k=ks_, v=vs_, slot_pos=sp, pos=pos + 1)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), new

    def init_cache(batch_size, capacity, extras=None):
        hkv, hd = cfg.n_kv_heads, cfg.hd
        c = {
            "k": jnp.zeros((n_scan, batch_size, capacity, hkv, hd), PDT),
            "v": jnp.zeros((n_scan, batch_size, capacity, hkv, hd), PDT),
            "slot_pos": jnp.full((capacity,), -1, jnp.int32),
            "pos": jnp.int32(0),
        }
        if cfg.n_dense_layers:
            c["k0"] = jnp.zeros((cfg.n_dense_layers, batch_size, capacity, hkv, hd), PDT)
            c["v0"] = jnp.zeros_like(c["k0"])
        return c

    return ModelBundle(cfg, init, forward, prefill, decode_step, init_cache,
                       ring_axes={"k": 2, "v": 2, "k0": 2, "v0": 2})


# ---------------------------------------------------------------------------
# pattern-block dense variant (REPRO_OPT_STATIC_WINDOW, §Perf):
# local:global stacks (gemma3) scan over pattern blocks with STATIC windows
# per inner position, enabling the band-restricted attention path.
# ---------------------------------------------------------------------------


def build_dense_pattern(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    assert cfg.local_global_pattern and not cfg.is_moe \
        and not cfg.n_dense_layers
    vp = L.vocab_pad_of(cfg.vocab)
    per = cfg.local_global_pattern + 1
    n_pat = cfg.n_layers // per
    n_tail = cfg.n_layers - n_pat * per
    win_in = [cfg.window_for_layer(i) for i in range(per)]          # static
    win_tail = [cfg.window_for_layer(n_pat * per + i) for i in range(n_tail)]

    def init(key):
        ks = jax.random.split(key, 3)
        blk = lambda k: _dense_block_params(k, cfg, cfg.d_ff)
        p = {
            "embed": L.embed_params(ks[0], vp, cfg.d_model),
            "ln_f": L.norm_params(cfg.d_model),
            "blocks": jax.vmap(lambda k: _stack_init(blk, k, per))(
                jax.random.split(ks[1], n_pat)),
        }
        if n_tail:
            p["tail"] = _stack_init(blk, ks[2], n_tail)
        return p

    def _run_full(params, tokens, emit_kv):
        Bsz, Ssz = tokens.shape
        chunked = Ssz >= CHUNKED_MIN_SEQ
        x = params["embed"].at[tokens].get(mode="clip")

        def outer(x, blk):
            kvs = []
            for i in range(per):
                lp = jax.tree.map(lambda a: a[i], blk)
                x, kv, _ = _block_full(x, lp, win_in[i], cfg, mesh_info,
                                       chunked, emit_kv)
                if emit_kv:
                    kvs.append(kv)
            ys = (jnp.stack([k for k, _ in kvs]),
                  jnp.stack([v for _, v in kvs])) if emit_kv else None
            return x, ys

        body = outer if emit_kv else jax.checkpoint(
            lambda c, b: outer(c, b))
        x, kvs = lax.scan(body, x, params["blocks"])
        tail_kv = None
        if n_tail:
            tk, tv = [], []
            for i in range(n_tail):
                lp = jax.tree.map(lambda a: a[i], params["tail"])
                x, kv, _ = _block_full(x, lp, win_tail[i], cfg, mesh_info,
                                       chunked, emit_kv)
                if emit_kv:
                    tk.append(kv[0])
                    tv.append(kv[1])
            if emit_kv:
                tail_kv = (jnp.stack(tk), jnp.stack(tv))
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return x, kvs, tail_kv

    def forward(params, batch):
        x, _, _ = _run_full(params, batch["tokens"], emit_kv=False)
        return _logits(x, params["embed"]), 0.0

    def prefill(params, batch):
        Ssz = batch["tokens"].shape[1]
        x, (ks_, vs_), tail_kv = _run_full(params, batch["tokens"], True)
        cache = {"k": ks_, "v": vs_,
                 "slot_pos": jnp.arange(Ssz, dtype=jnp.int32),
                 "pos": jnp.int32(Ssz)}
        if n_tail:
            cache["kt"], cache["vt"] = tail_kv
        return _logits(x[:, -1], params["embed"]), cache

    def decode_step(params, step, cache):
        token = step["token"]
        x = params["embed"].at[token].get(mode="clip")
        pos = cache["pos"]
        W = cache["k"].shape[3]
        slot = pos % W
        sp = lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))

        def outer(x, xs):
            blk, ck, cv = xs
            nk, nv = [], []
            for i in range(per):
                lp = jax.tree.map(lambda a: a[i], blk)
                x, cki, cvi, _ = _block_decode(
                    x, lp, jnp.int32(win_in[i]), cfg, mesh_info,
                    ck[i], cv[i], sp, slot, pos)
                nk.append(cki)
                nv.append(cvi)
            return x, (jnp.stack(nk), jnp.stack(nv))

        x, (ks_, vs_) = lax.scan(outer, x,
                                 (params["blocks"], cache["k"], cache["v"]))
        new = dict(cache)
        if n_tail:
            tk, tv = [], []
            for i in range(n_tail):
                lp = jax.tree.map(lambda a: a[i], params["tail"])
                x, cki, cvi, _ = _block_decode(
                    x, lp, jnp.int32(win_tail[i]), cfg, mesh_info,
                    cache["kt"][i], cache["vt"][i], sp, slot, pos)
                tk.append(cki)
                tv.append(cvi)
            new["kt"], new["vt"] = jnp.stack(tk), jnp.stack(tv)
        new.update(k=ks_, v=vs_, slot_pos=sp, pos=pos + 1)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), new

    def init_cache(batch_size, capacity, extras=None):
        hkv, hd = cfg.n_kv_heads, cfg.hd
        c = {
            "k": jnp.zeros((n_pat, per, batch_size, capacity, hkv, hd), PDT),
            "v": jnp.zeros((n_pat, per, batch_size, capacity, hkv, hd), PDT),
            "slot_pos": jnp.full((capacity,), -1, jnp.int32),
            "pos": jnp.int32(0),
        }
        if n_tail:
            c["kt"] = jnp.zeros((n_tail, batch_size, capacity, hkv, hd), PDT)
            c["vt"] = jnp.zeros_like(c["kt"])
        return c

    return ModelBundle(cfg, init, forward, prefill, decode_step, init_cache,
                       ring_axes={"k": 3, "v": 3, "kt": 2, "vt": 2})


# ---------------------------------------------------------------------------
# vlm family (llama-3.2-vision): blocks of [n_self self-attn + 1 cross-attn]
# ---------------------------------------------------------------------------


def build_vlm(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    vp = L.vocab_pad_of(cfg.vocab)
    n_self = cfg.cross_attn_every - 1  # 4 self per cross
    n_blocks = cfg.n_layers // cfg.cross_attn_every
    assert n_blocks * cfg.cross_attn_every == cfg.n_layers

    def cross_block_params(key):
        p = _dense_block_params(key, cfg, cfg.d_ff)
        p["gate_attn"] = jnp.zeros((), PDT)
        p["gate_mlp"] = jnp.zeros((), PDT)
        return p

    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "embed": L.embed_params(ks[0], vp, cfg.d_model),
            "ln_f": L.norm_params(cfg.d_model),
            "proj": (jax.random.normal(ks[1], (cfg.frontend_dim, cfg.d_model))
                     * cfg.frontend_dim ** -0.5).astype(PDT),
            "blocks": {
                "self": jax.vmap(lambda k: _stack_init(
                    lambda kk: _dense_block_params(kk, cfg, cfg.d_ff), k, n_self)
                )(jax.random.split(ks[2], n_blocks)),
                "cross": _stack_init(cross_block_params, ks[3], n_blocks),
            },
        }

    def _cross_apply_full(x, cp, mem_k, mem_v):
        h = L.cross_attn_full(L.rms_norm(x, cp["ln1"], cfg.rms_eps), cp["attn"],
                              attn_dims(cfg), mem_k, mem_v)
        x = x + jnp.tanh(cp["gate_attn"].astype(jnp.float32)).astype(x.dtype) * h
        y = L.swiglu(L.rms_norm(x, cp["ln2"], cfg.rms_eps),
                     cp["mlp"]["w1"], cp["mlp"]["w3"], cp["mlp"]["w2"])
        return x + jnp.tanh(cp["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * y

    def _run_full(params, batch, emit_kv):
        tokens = batch["tokens"]
        Bsz, Ssz = tokens.shape
        chunked = Ssz >= CHUNKED_MIN_SEQ
        mem = (batch["patch_embeds"].astype(PDT) @ params["proj"])
        x = params["embed"].at[tokens].get(mode="clip")

        def outer(x, blk):
            def inner(carry, lp):
                xx, kv, _ = _block_full(carry, lp, jnp.int32(-1), cfg,
                                        mesh_info, chunked, emit_kv)
                return xx, kv
            if not emit_kv:
                inner = jax.checkpoint(inner)
            x, kvs = lax.scan(inner, x, blk["self"])
            mk, mv = L.cross_kv(mem, blk["cross"]["attn"], attn_dims(cfg))
            x = _cross_apply_full(x, blk["cross"], mk, mv)
            return x, (kvs, (mk, mv))

        outer_body = (lambda c, blk: outer(c, blk)) if emit_kv else \
            jax.checkpoint(lambda c, blk: outer(c, blk))
        x, (self_kv, cross_kv) = lax.scan(outer_body, x, params["blocks"])
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return x, self_kv, cross_kv

    def forward(params, batch):
        x, _, _ = _run_full(params, batch, emit_kv=False)
        return _logits(x, params["embed"]), 0.0

    def prefill(params, batch):
        Ssz = batch["tokens"].shape[1]
        x, (ks_, vs_), (mk, mv) = _run_full(params, batch, emit_kv=True)
        cache = {
            "k": ks_, "v": vs_, "mk": mk, "mv": mv,
            "slot_pos": jnp.arange(Ssz, dtype=jnp.int32),
            "pos": jnp.int32(Ssz),
        }
        return _logits(x[:, -1], params["embed"]), cache

    def decode_step(params, step, cache):
        token = step["token"]
        x = params["embed"].at[token].get(mode="clip")
        pos = cache["pos"]
        W = cache["k"].shape[3]
        slot = pos % W
        sp = lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))

        def outer(x, xs):
            blk, ck, cv, mk, mv = xs

            def inner(carry, ys):
                lp, ckl, cvl = ys
                xx, ckl, cvl, _ = _block_decode(carry, lp, jnp.int32(-1), cfg,
                                                mesh_info, ckl, cvl, sp, slot, pos)
                return xx, (ckl, cvl)

            x, kv = lax.scan(inner, x, (blk["self"], ck, cv))
            cp = blk["cross"]
            h = L.cross_attn_decode(L.rms_norm(x, cp["ln1"], cfg.rms_eps),
                                    cp["attn"], attn_dims(cfg), mk, mv)
            x = x + jnp.tanh(cp["gate_attn"].astype(jnp.float32)).astype(x.dtype) * h
            y = L.swiglu(L.rms_norm(x, cp["ln2"], cfg.rms_eps),
                         cp["mlp"]["w1"], cp["mlp"]["w3"], cp["mlp"]["w2"])
            x = x + jnp.tanh(cp["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * y
            return x, kv

        x, (ks_, vs_) = lax.scan(
            outer, x, (params["blocks"], cache["k"], cache["v"],
                       cache["mk"], cache["mv"]))
        new = dict(cache)
        new.update(k=ks_, v=vs_, slot_pos=sp, pos=pos + 1)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), new

    def init_cache(batch_size, capacity, extras=None):
        hkv, hd = cfg.n_kv_heads, cfg.hd
        p_tok = cfg.n_frontend_tokens
        return {
            "k": jnp.zeros((n_blocks, n_self, batch_size, capacity, hkv, hd), PDT),
            "v": jnp.zeros((n_blocks, n_self, batch_size, capacity, hkv, hd), PDT),
            "mk": jnp.zeros((n_blocks, batch_size, p_tok, hkv, hd), PDT),
            "mv": jnp.zeros((n_blocks, batch_size, p_tok, hkv, hd), PDT),
            "slot_pos": jnp.full((capacity,), -1, jnp.int32),
            "pos": jnp.int32(0),
        }

    return ModelBundle(cfg, init, forward, prefill, decode_step, init_cache,
                       ring_axes={"k": 3, "v": 3})


# ---------------------------------------------------------------------------
# encoder-decoder family (seamless-m4t): audio frames -> encoder; text decoder
# ---------------------------------------------------------------------------


def build_encdec(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    vp = L.vocab_pad_of(cfg.vocab)

    def dec_block_params(key):
        k1, k2 = jax.random.split(key)
        p = _dense_block_params(k1, cfg, cfg.d_ff)
        p["ln_x"] = L.norm_params(cfg.d_model)
        p["xattn"] = L.attn_params(k2, attn_dims(cfg))
        return p

    def init(key):
        ks = jax.random.split(key, 5)
        return {
            "embed": L.embed_params(ks[0], vp, cfg.d_model),
            "proj": (jax.random.normal(ks[1], (cfg.frontend_dim, cfg.d_model))
                     * cfg.frontend_dim ** -0.5).astype(PDT),
            "enc": _stack_init(lambda k: _dense_block_params(k, cfg, cfg.d_ff),
                               ks[2], cfg.enc_layers),
            "ln_enc": L.norm_params(cfg.d_model),
            "dec": _stack_init(dec_block_params, ks[3], cfg.n_layers),
            "ln_f": L.norm_params(cfg.d_model),
        }

    def encode(params, frames):
        x = frames.astype(PDT) @ params["proj"]
        chunked = x.shape[1] >= CHUNKED_MIN_SEQ

        @jax.checkpoint
        def body(carry, lp):
            h, _ = L.self_attn_full(L.rms_norm(carry, lp["ln1"], cfg.rms_eps),
                                    lp["attn"], attn_dims(cfg), causal=False,
                                    chunked=chunked)
            xx = carry + h
            y = L.swiglu(L.rms_norm(xx, lp["ln2"], cfg.rms_eps),
                         lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])
            return xx + y, None

        x, _ = lax.scan(body, x, params["enc"])
        return L.rms_norm(x, params["ln_enc"], cfg.rms_eps)

    def _dec_full(params, tokens, enc_out, emit_kv):
        chunked = tokens.shape[1] >= CHUNKED_MIN_SEQ
        x = params["embed"].at[tokens].get(mode="clip")

        def body(carry, lp):
            h, kv = L.self_attn_full(L.rms_norm(carry, lp["ln1"], cfg.rms_eps),
                                     lp["attn"], attn_dims(cfg), chunked=chunked)
            xx = carry + h
            mk, mv = L.cross_kv(enc_out, lp["xattn"], attn_dims(cfg))
            xx = xx + L.cross_attn_full(L.rms_norm(xx, lp["ln_x"], cfg.rms_eps),
                                        lp["xattn"], attn_dims(cfg), mk, mv)
            y = L.swiglu(L.rms_norm(xx, lp["ln2"], cfg.rms_eps),
                         lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])
            return xx + y, ((kv, (mk, mv)) if emit_kv else None)

        if not emit_kv:
            body = jax.checkpoint(body)
        x, kvs = lax.scan(body, x, params["dec"])
        return L.rms_norm(x, params["ln_f"], cfg.rms_eps), kvs

    def forward(params, batch):
        enc_out = encode(params, batch["frames"])
        x, _ = _dec_full(params, batch["tokens"], enc_out, emit_kv=False)
        return _logits(x, params["embed"]), 0.0

    def prefill(params, batch):
        Ssz = batch["tokens"].shape[1]
        enc_out = encode(params, batch["frames"])
        x, ((ks_, vs_), (mk, mv)) = _dec_full(params, batch["tokens"], enc_out,
                                              emit_kv=True)
        cache = {"k": ks_, "v": vs_, "mk": mk, "mv": mv,
                 "slot_pos": jnp.arange(Ssz, dtype=jnp.int32),
                 "pos": jnp.int32(Ssz)}
        return _logits(x[:, -1], params["embed"]), cache

    def decode_step(params, step, cache):
        token = step["token"]
        x = params["embed"].at[token].get(mode="clip")
        pos = cache["pos"]
        W = cache["k"].shape[2]
        slot = pos % W
        sp = lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))

        def body(carry, xs):
            lp, ck, cv, mk, mv = xs
            h, ck, cv = L.self_attn_decode(
                L.rms_norm(carry, lp["ln1"], cfg.rms_eps), lp["attn"],
                attn_dims(cfg), ck, cv, sp, slot, pos)
            xx = carry + h
            xx = xx + L.cross_attn_decode(
                L.rms_norm(xx, lp["ln_x"], cfg.rms_eps), lp["xattn"],
                attn_dims(cfg), mk, mv)
            y = L.swiglu(L.rms_norm(xx, lp["ln2"], cfg.rms_eps),
                         lp["mlp"]["w1"], lp["mlp"]["w3"], lp["mlp"]["w2"])
            return xx + y, (ck, cv)

        x, (ks_, vs_) = lax.scan(
            body, x, (params["dec"], cache["k"], cache["v"],
                      cache["mk"], cache["mv"]))
        new = dict(cache)
        new.update(k=ks_, v=vs_, slot_pos=sp, pos=pos + 1)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), new

    def init_cache(batch_size, capacity, extras=None):
        hkv, hd = cfg.n_kv_heads, cfg.hd
        mem_len = extras["mem_len"] if extras else capacity
        return {
            "k": jnp.zeros((cfg.n_layers, batch_size, capacity, hkv, hd), PDT),
            "v": jnp.zeros((cfg.n_layers, batch_size, capacity, hkv, hd), PDT),
            "mk": jnp.zeros((cfg.n_layers, batch_size, mem_len, hkv, hd), PDT),
            "mv": jnp.zeros((cfg.n_layers, batch_size, mem_len, hkv, hd), PDT),
            "slot_pos": jnp.full((capacity,), -1, jnp.int32),
            "pos": jnp.int32(0),
        }

    return ModelBundle(cfg, init, forward, prefill, decode_step, init_cache,
                       ring_axes={"k": 2, "v": 2})


# ---------------------------------------------------------------------------
# ssm family (mamba2)
# ---------------------------------------------------------------------------


def build_ssm(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    vp = L.vocab_pad_of(cfg.vocab)

    def layer_params(key):
        return {"ln": L.norm_params(cfg.d_model), "ssm": S.ssm_params(key, cfg)}

    def init(key):
        k0, k1 = jax.random.split(key)
        return {
            "embed": L.embed_params(k0, vp, cfg.d_model),
            "ln_f": L.norm_params(cfg.d_model),
            "layers": _stack_init(layer_params, k1, cfg.n_layers),
        }

    def _run_full(params, tokens, emit_state):
        x = params["embed"].at[tokens].get(mode="clip")

        def body(carry, lp):
            y, hfin, tails = S.ssd_forward(
                L.rms_norm(carry, lp["ln"], cfg.rms_eps), lp["ssm"], cfg)
            st = ((hfin, tails) if emit_state else None)
            return carry + y, st

        if not emit_state:
            body = jax.checkpoint(body)
        x, states = lax.scan(body, x, params["layers"])
        return L.rms_norm(x, params["ln_f"], cfg.rms_eps), states

    def forward(params, batch):
        x, _ = _run_full(params, batch["tokens"], emit_state=False)
        return _logits(x, params["embed"]), 0.0

    def prefill(params, batch):
        x, (hfin, tails) = _run_full(params, batch["tokens"], emit_state=True)
        cache = {"ssm": hfin, "conv_x": tails["x"], "conv_B": tails["B"],
                 "conv_C": tails["C"], "pos": jnp.int32(batch["tokens"].shape[1])}
        return _logits(x[:, -1], params["embed"]), cache

    def decode_step(params, step, cache):
        token = step["token"]
        x = params["embed"].at[token].get(mode="clip")

        def body(carry, xs):
            lp, st, cx, cb, cc = xs
            y, st, conv = S.ssd_decode_step(
                L.rms_norm(carry, lp["ln"], cfg.rms_eps), lp["ssm"], cfg, st,
                {"x": cx, "B": cb, "C": cc})
            return carry + y, (st, conv["x"], conv["B"], conv["C"])

        x, (st, cx, cb, cc) = lax.scan(
            body, x, (params["layers"], cache["ssm"], cache["conv_x"],
                      cache["conv_B"], cache["conv_C"]))
        new = {"ssm": st, "conv_x": cx, "conv_B": cb, "conv_C": cc,
               "pos": cache["pos"] + 1}
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), new

    def init_cache(batch_size, capacity, extras=None):
        one = S.init_ssm_cache(cfg, batch_size)
        LN = cfg.n_layers
        return {
            "ssm": jnp.zeros((LN,) + one["ssm"].shape, one["ssm"].dtype),
            "conv_x": jnp.zeros((LN,) + one["conv_x"].shape, PDT),
            "conv_B": jnp.zeros((LN,) + one["conv_B"].shape, PDT),
            "conv_C": jnp.zeros((LN,) + one["conv_C"].shape, PDT),
            "pos": jnp.int32(0),
        }

    return ModelBundle(cfg, init, forward, prefill, decode_step, init_cache)


# ---------------------------------------------------------------------------
# hybrid family (zamba2): mamba2 backbone + weight-shared attn block
# ---------------------------------------------------------------------------


def build_hybrid(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    vp = L.vocab_pad_of(cfg.vocab)
    per = cfg.hybrid_attn_every
    n_blocks = cfg.n_layers // per
    n_tail = cfg.n_layers - n_blocks * per

    def m_layer(key):
        return {"ln": L.norm_params(cfg.d_model), "ssm": S.ssm_params(key, cfg)}

    def init(key):
        ks = jax.random.split(key, 5)
        p = {
            "embed": L.embed_params(ks[0], vp, cfg.d_model),
            "ln_f": L.norm_params(cfg.d_model),
            "mamba": jax.vmap(lambda k: _stack_init(m_layer, k, per))(
                jax.random.split(ks[1], n_blocks)),
            "shared": _dense_block_params(ks[2], cfg, cfg.d_ff),
        }
        if n_tail:
            p["tail"] = _stack_init(m_layer, ks[3], n_tail)
        return p

    def _mamba_scan(x, stack, emit_state):
        def body(carry, lp):
            y, hfin, tails = S.ssd_forward(
                L.rms_norm(carry, lp["ln"], cfg.rms_eps), lp["ssm"], cfg)
            return carry + y, ((hfin, tails) if emit_state else None)
        if not emit_state:
            body = jax.checkpoint(body)
        return lax.scan(body, x, stack)

    def _run_full(params, tokens, emit):
        Ssz = tokens.shape[1]
        chunked = Ssz >= CHUNKED_MIN_SEQ
        x = params["embed"].at[tokens].get(mode="clip")
        sh = params["shared"]
        win = jnp.int32(cfg.sliding_window or -1)

        def outer(x, blk):
            x, st = _mamba_scan(x, blk, emit)
            xx, kv, _ = _block_full(x, sh, win, cfg, None, chunked, emit)
            return xx, (st, kv)

        outer_body = outer if emit else jax.checkpoint(outer)
        x, (m_states, attn_kv) = lax.scan(outer_body, x, params["mamba"])
        tail_states = None
        if n_tail:
            x, tail_states = _mamba_scan(x, params["tail"], emit)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return x, m_states, attn_kv, tail_states

    def forward(params, batch):
        x, _, _, _ = _run_full(params, batch["tokens"], emit=False)
        return _logits(x, params["embed"]), 0.0

    def prefill(params, batch):
        Ssz = batch["tokens"].shape[1]
        x, (h_m, tails_m), (ks_, vs_), tail_st = _run_full(
            params, batch["tokens"], emit=True)
        W = min(Ssz, cfg.sliding_window or Ssz)
        # Keep only the last W entries of the attn kv (window cache), rolled
        # so the ring invariant slot == pos % W holds for decode continuation.
        shift = (Ssz - W) % W
        ak = jnp.roll(ks_[:, :, -W:], shift, axis=2)
        av = jnp.roll(vs_[:, :, -W:], shift, axis=2)
        sp = jnp.roll(jnp.arange(Ssz - W, Ssz, dtype=jnp.int32), shift)
        cache = {
            "ssm": h_m, "conv_x": tails_m["x"], "conv_B": tails_m["B"],
            "conv_C": tails_m["C"], "ak": ak, "av": av,
            "slot_pos": sp, "pos": jnp.int32(Ssz),
        }
        if n_tail:
            h_t, tails_t = tail_st
            cache.update(ssm_t=h_t, conv_xt=tails_t["x"], conv_Bt=tails_t["B"],
                         conv_Ct=tails_t["C"])
        return _logits(x[:, -1], params["embed"]), cache

    def _mamba_decode_scan(x, stack, st, cx, cb, cc):
        def body(carry, xs):
            lp, s, a, b, c = xs
            y, s, conv = S.ssd_decode_step(
                L.rms_norm(carry, lp["ln"], cfg.rms_eps), lp["ssm"], cfg, s,
                {"x": a, "B": b, "C": c})
            return carry + y, (s, conv["x"], conv["B"], conv["C"])
        return lax.scan(body, x, (stack, st, cx, cb, cc))

    def decode_step(params, step, cache):
        token = step["token"]
        x = params["embed"].at[token].get(mode="clip")
        pos = cache["pos"]
        W = cache["ak"].shape[2]
        slot = pos % W
        sp = lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
        sh = params["shared"]
        win = jnp.int32(cfg.sliding_window or -1)

        def outer(x, xs):
            blk, st, cx, cb, cc, ak, av = xs
            x, (st, cx, cb, cc) = _mamba_decode_scan(x, blk, st, cx, cb, cc)
            x, ak, av, _ = _block_decode(x, sh, win, cfg, None, ak, av, sp,
                                         slot, pos)
            return x, (st, cx, cb, cc, ak, av)

        x, (st, cx, cb, cc, ak, av) = lax.scan(
            outer, x, (params["mamba"], cache["ssm"], cache["conv_x"],
                       cache["conv_B"], cache["conv_C"], cache["ak"],
                       cache["av"]))
        new = dict(cache)
        new.update(ssm=st, conv_x=cx, conv_B=cb, conv_C=cc, ak=ak, av=av,
                   slot_pos=sp, pos=pos + 1)
        if n_tail:
            x, (st_t, cxt, cbt, cct) = _mamba_decode_scan(
                x, params["tail"], cache["ssm_t"], cache["conv_xt"],
                cache["conv_Bt"], cache["conv_Ct"])
            new.update(ssm_t=st_t, conv_xt=cxt, conv_Bt=cbt, conv_Ct=cct)
        x = L.rms_norm(x, params["ln_f"], cfg.rms_eps)
        return _logits(x[:, -1], params["embed"]), new

    def init_cache(batch_size, capacity, extras=None):
        one = S.init_ssm_cache(cfg, batch_size)
        W = min(capacity, cfg.sliding_window or capacity)
        hkv, hd = cfg.n_kv_heads, cfg.hd
        c = {
            "ssm": jnp.zeros((n_blocks, per) + one["ssm"].shape, one["ssm"].dtype),
            "conv_x": jnp.zeros((n_blocks, per) + one["conv_x"].shape, PDT),
            "conv_B": jnp.zeros((n_blocks, per) + one["conv_B"].shape, PDT),
            "conv_C": jnp.zeros((n_blocks, per) + one["conv_C"].shape, PDT),
            "ak": jnp.zeros((n_blocks, batch_size, W, hkv, hd), PDT),
            "av": jnp.zeros((n_blocks, batch_size, W, hkv, hd), PDT),
            "slot_pos": jnp.full((W,), -1, jnp.int32),
            "pos": jnp.int32(0),
        }
        if n_tail:
            c.update(
                ssm_t=jnp.zeros((n_tail,) + one["ssm"].shape, one["ssm"].dtype),
                conv_xt=jnp.zeros((n_tail,) + one["conv_x"].shape, PDT),
                conv_Bt=jnp.zeros((n_tail,) + one["conv_B"].shape, PDT),
                conv_Ct=jnp.zeros((n_tail,) + one["conv_C"].shape, PDT))
        return c

    return ModelBundle(cfg, init, forward, prefill, decode_step, init_cache,
                       ring_axes={"ak": 2, "av": 2})


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_BUILDERS = {
    "dense": build_dense,
    "moe": build_dense,   # dense decoder with MoE FFN blocks
    "vlm": build_vlm,
    "encdec": build_encdec,
    "ssm": build_ssm,
    "hybrid": build_hybrid,
}


def build(cfg: ArchConfig, mesh_info=None) -> ModelBundle:
    from repro.models import opt_flags
    if (cfg.family == "dense" and cfg.local_global_pattern
            and opt_flags.static_window()):
        return build_dense_pattern(cfg, mesh_info=mesh_info)
    return _BUILDERS[cfg.family](cfg, mesh_info=mesh_info)


EXPERT_KEYS = ("w1", "w3", "w2")


def init_without_experts(cfg: ArchConfig) -> Callable:
    """A jitted ``key -> params``: ``build(cfg).init`` without the
    routed-expert stacks, for the serving engines, which take those weights
    from a host store (``core.cache.HostExpertStore``). XLA drops the dead
    expert generation, so no expert-sized array is built on the device."""
    bundle = build(cfg)

    def init(k):
        p = bundle.init(k)
        p["layers"]["moe"] = {n: a for n, a in p["layers"]["moe"].items()
                              if n not in EXPERT_KEYS}
        return p

    return jax.jit(init)
