"""Random weights of a configuration, made on the device from its weight
seed, and laid out for the program and for the reference.

``dense(dims)`` makes every weight but the routed experts in one jitted
call, in the type it is served in (bfloat16; the router in float32, as the
program holds it). ``expert(dims, l, e)`` makes one routed expert. The
program receives the experts in host RAM (``host_experts``), one expert at
a time off the device, so no expert stack is ever built there; the
reference makes the same experts again, layer by layer, from the same seed.

RMSNorm gains are ``1 + u`` with ``u`` uniform in (-0.1, 0.1), rounded to
bfloat16; ``u`` is exact in bfloat16, so the program's offset form
(``x_norm * (1 + w)``, ``w = u``) and the reference's plain gain agree to
the bit.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16


def _key(dims, *path: int) -> jax.Array:
    k = jax.random.PRNGKey(dims.weight_seed)
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


@functools.lru_cache(maxsize=None)
def _dense_fn(dims):
    d, L, V = dims.d_model, dims.n_layers, dims.vocab
    H, Hkv, hd, E = dims.n_heads, dims.n_kv_heads, dims.head_dim, \
        dims.n_experts

    def normal(k, shape, fan_in, dtype=BF16):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def gain(k, shape):
        return jax.random.uniform(k, shape, jnp.float32, -0.1,
                                  0.1).astype(BF16)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 11)
        w = {
            "embed": normal(ks[0], (V, d), d),
            "ln_f": gain(ks[1], (d,)),
            "ln1": gain(ks[2], (L, d)),
            "ln2": gain(ks[3], (L, d)),
            "wq": normal(ks[4], (L, d, H * hd), d),
            "wk": normal(ks[5], (L, d, Hkv * hd), d),
            "wv": normal(ks[6], (L, d, Hkv * hd), d),
            "wo": normal(ks[7], (L, H * hd, d), H * hd),
            "router": normal(ks[8], (L, d, E), d, jnp.float32),
        }
        if dims.qk_norm:
            w["q_norm"] = gain(ks[9], (L, hd))
            w["k_norm"] = gain(ks[10], (L, hd))
        return w

    return make


def dense(dims) -> Dict[str, jax.Array]:
    """Every weight but the routed experts, on the device. Norm entries
    hold the offset ``u`` of the gain ``1 + u``."""
    return _dense_fn(dims)(_key(dims, 0))


@functools.lru_cache(maxsize=None)
def _expert_fn(dims):
    d, de = dims.d_model, dims.d_expert

    def uniform(k, shape, fan_in):
        b = float(np.sqrt(3.0 / fan_in))
        return jax.random.uniform(k, shape, jnp.float32, -b, b).astype(BF16)

    @jax.jit
    def make(key):
        k1, k3, k2 = jax.random.split(key, 3)
        return (uniform(k1, (d, de), d), uniform(k3, (d, de), d),
                uniform(k2, (de, d), de))

    return make


def expert(dims, l: int, e: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Routed expert ``(l, e)``: (w1 [d, de], w3 [d, de], w2 [de, d]) in
    bfloat16 on the device (gate, up and down projections)."""
    return _expert_fn(dims)(_key(dims, 1, l, e))


@functools.lru_cache(maxsize=None)
def _experts_fn(dims):
    return jax.jit(jax.vmap(_expert_fn(dims)))


def experts(dims, l: int, first: int, count: int):
    """Routed experts ``first .. first + count - 1`` of layer ``l`` in one
    call, stacked on a leading axis: the same values as ``expert``."""
    keys = jnp.stack([_key(dims, 1, l, e) for e in range(first, first + count)])
    return _experts_fn(dims)(keys)


def host_experts(dims) -> Dict[Tuple[int, int], Tuple[np.ndarray, ...]]:
    """Every routed expert in host RAM, made on the device a layer's
    experts at a time and copied off before the next layer's are made."""
    out = {}
    for l in range(dims.n_layers):
        made = [expert(dims, l, e) for e in range(dims.n_experts)]
        for e, ws in enumerate(jax.device_get(made)):
            out[(l, e)] = tuple(np.asarray(w) for w in ws)
        del made
    return out


def program_params(dims, w: Dict[str, jax.Array], expected) -> dict:
    """The program's parameter tree (``repro.models.model
    .init_without_experts`` layout) holding the weights ``w``.
    ``expected`` is that tree's shapes (``jax.eval_shape`` of it); a
    layout that differs from it raises instead of guessing."""
    attn = {n: w[n] for n in ("wq", "wk", "wv", "wo")}
    if dims.qk_norm:
        attn["q_norm"], attn["k_norm"] = w["q_norm"], w["k_norm"]
    vpad = expected["embed"].shape[0] - dims.vocab
    embed = w["embed"] if vpad == 0 else jnp.pad(w["embed"],
                                                 ((0, vpad), (0, 0)))
    params = {"embed": embed, "ln_f": w["ln_f"],
              "layers": {"ln1": w["ln1"], "ln2": w["ln2"], "attn": attn,
                         "moe": {"router": w["router"]}}}
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), expected)
    if got != want:
        raise ValueError(f"the program's parameter layout changed:\n"
                         f"expected {want}\nmade {got}")
    return params
