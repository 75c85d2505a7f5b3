"""Mixture-of-Experts FFN with sort+capacity dispatch, expert-parallel ready.

Design (see DESIGN.md §4):
  * Tokens arrive replicated over the tensor axis (post-attention all-reduce,
    Megatron pattern). Each model-rank owns a contiguous slice of experts
    ('expert' sharding) or a slice of every expert's hidden dim ('hidden'
    sharding, used when E < mesh model size, e.g. Mixtral's 8 experts).
  * Dispatch is fully local: sort token-expert assignments, scatter into a
    per-rank capacity buffer [E_local, C, d], run the batched expert FFN,
    gather back, weight by router probs. The only collective is one psum of
    the combined output over the model axis per MoE layer — the same cost as
    a Megatron FFN all-reduce. No all-to-all is needed because activations
    are replicated over the tensor axis.
  * Experts whose count doesn't divide the axis are padded with dummy experts
    that the router can never select (qwen2-moe: 60 -> 64).
  * Shared experts are fused into one wide SwiGLU whose hidden dim is sharded
    over the model axis; their partial output folds into the same psum.

The capacity path (tokens above capacity dropped) is used for sharded
training and dry-run lowering; single-device calls default to an exact
capacity of T (top-k ids are distinct per token, so no expert can receive
more than T assignments), so prefill/decode/teacher-forced eval never drop
and agree
bit-for-tolerance. The *serving engine* uses the exact sequential per-expert
path (`expert_ffn_exact`) — that is the paper's own execution model (experts
run one at a time from a small cache).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models.layers import PDT


def n_experts_padded(cfg: ArchConfig, n_model: int = 16) -> int:
    e = cfg.n_experts
    if e >= n_model and e % n_model:
        return -(-e // n_model) * n_model
    return e


def expert_shard_mode(cfg: ArchConfig, n_model: int = 16) -> str:
    """'expert' = experts over model axis; 'hidden' = d_expert over model."""
    return "expert" if cfg.n_experts >= n_model else "hidden"


def moe_params(key, cfg: ArchConfig, n_model: int = 16, dtype=PDT):
    d, de = cfg.d_model, cfg.d_expert
    ep = n_experts_padded(cfg, n_model)
    ks = jax.random.split(key, 7)
    p = {
        "router": (jax.random.normal(ks[0], (d, ep)) * d ** -0.5).astype(jnp.float32),
        "w1": (jax.random.normal(ks[1], (ep, d, de)) * d ** -0.5).astype(dtype),
        "w3": (jax.random.normal(ks[2], (ep, d, de)) * d ** -0.5).astype(dtype),
        "w2": (jax.random.normal(ks[3], (ep, de, d)) * de ** -0.5).astype(dtype),
    }
    if cfg.n_shared_experts:
        sh = cfg.n_shared_experts * de
        p["sw1"] = (jax.random.normal(ks[4], (d, sh)) * d ** -0.5).astype(dtype)
        p["sw3"] = (jax.random.normal(ks[5], (d, sh)) * d ** -0.5).astype(dtype)
        p["sw2"] = (jax.random.normal(ks[6], (sh, d)) * sh ** -0.5).astype(dtype)
    return p


def route(x2d: jax.Array, router: jax.Array, n_real: int, top_k: int):
    """Router: returns (weights [T,k] f32, ids [T,k] i32, probs [T,E] f32)."""
    logits = x2d.astype(jnp.float32) @ router  # [T, E_pad]
    e_pad = router.shape[1]
    if e_pad > n_real:
        pad_mask = jnp.arange(e_pad) >= n_real
        logits = jnp.where(pad_mask[None], -1e9, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, ids, probs


def _dispatch_compute_combine(x2d, w, ids, w1, w3, w2, *, capacity: int,
                              e_start, active_max: Optional[int] = None,
                              use_pallas: bool = False) -> jax.Array:
    """Capacity dispatch against a local expert slice [E_loc, d, de].

    e_start: first global expert id owned locally (0 in 'hidden' mode).
    active_max (REPRO_OPT_ACTIVE_GATHER, §Perf): for tiny token counts
    (decode) gather only the `active_max` most-loaded local experts' weights
    instead of running the dense [E_loc, C, d] einsum over every local
    expert — HBM weight traffic drops E_loc/active_max x. Assignments beyond
    the A busiest local experts drop (capacity-style bound; the serving
    engine's exact path is unaffected).
    Returns the (partial) combined output [T, d].
    """
    T, d = x2d.shape
    k = ids.shape[1]
    e_loc = w1.shape[0]
    flat = ids.reshape(-1)  # [T*k]
    order = jnp.argsort(flat)
    sid = flat[order]
    stok = order // k
    # position of each assignment within its expert's run (sorted => runs)
    pos = jnp.arange(T * k) - jnp.searchsorted(sid, sid, side="left")
    lid = sid - e_start  # local expert id; OOB rows dropped by scatter/gather
    oob = (lid < 0) | (lid >= e_loc) | (pos >= capacity)

    if active_max is not None and active_max < e_loc:
        # loads per local expert -> top-A busiest; remap lid into [0, A)
        loads = jnp.zeros((e_loc,), jnp.int32).at[lid].add(
            (~oob).astype(jnp.int32), mode="drop")
        _, sel = lax.top_k(loads, active_max)          # [A] local ids
        inv_sel = jnp.full((e_loc,), -1, jnp.int32).at[sel].set(
            jnp.arange(active_max, dtype=jnp.int32))
        lid = inv_sel.at[jnp.clip(lid, 0, e_loc - 1)].get(mode="clip")
        oob = oob | (lid < 0)
        w1 = jnp.take(w1, sel, axis=0)                 # [A, d, de] gather
        w3 = jnp.take(w3, sel, axis=0)
        w2 = jnp.take(w2, sel, axis=0)
        e_loc = active_max

    buf = jnp.zeros((e_loc, capacity, d), x2d.dtype)
    buf = buf.at[lid, pos].set(
        jnp.where(oob[:, None], 0, x2d[stok]), mode="drop")
    if use_pallas:
        # grouped GEMMs via the double-buffered expert-streaming kernel
        # (the paper's prefill pipeline, TPU-native; interpreted on CPU)
        from repro.kernels.ops import expert_ffn_op
        y = expert_ffn_op(buf, w1, w3, w2)
    else:
        h = jnp.einsum("ecd,edf->ecf", buf, w1)
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, w3)
        y = jnp.einsum("ecf,efd->ecd", h, w2)  # [E_loc, C, d]
    g = y.at[lid, pos].get(mode="fill", fill_value=0)  # [T*k, d]
    g = jnp.where(oob[:, None], 0, g)
    inv = jnp.argsort(order)
    g = g[inv].reshape(T, k, d)
    return (g.astype(jnp.float32) * w[..., None]).sum(1).astype(x2d.dtype)


def active_gather_max(t_loc: int, top_k: int, e_loc: int, e_pad: int
                      ) -> Optional[int]:
    """A = 2x the expected active local experts, floor top_k — None if the
    dense path is already as cheap (large-T training/prefill)."""
    from repro.models import opt_flags
    if not opt_flags.active_gather() or t_loc * top_k > 512:
        return None
    expected = t_loc * top_k * e_loc / max(e_pad, 1)
    a = int(max(top_k, -(-2 * expected // 1)))
    a = min(a, e_loc)
    return a if a < e_loc else None


def load_balance_loss(probs: jax.Array, ids: jax.Array, n_real: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e (over real experts)."""
    T, k = ids.shape
    sel = jax.nn.one_hot(ids, probs.shape[1], dtype=jnp.float32).sum(1)  # [T,E]
    f = sel.mean(0)                       # fraction routed (counts/T, sums to k)
    p = probs.mean(0)
    return n_real * jnp.sum(f[:n_real] * p[:n_real]) / k


def moe_ffn_local(x2d, p, cfg: ArchConfig, *, capacity: int, e_start=0,
                  axis: Optional[str] = None):
    """Local (per-shard) MoE FFN body. If `axis` is set, runs under shard_map
    and psums the combined output over that axis."""
    w, ids, probs = route(x2d, p["router"], cfg.n_experts, cfg.top_k)
    e_loc = p["w1"].shape[0]
    amax = active_gather_max(x2d.shape[0], cfg.top_k, e_loc,
                             n_experts_padded(cfg))
    import os
    y = _dispatch_compute_combine(
        x2d, w, ids, p["w1"], p["w3"], p["w2"], capacity=capacity,
        e_start=e_start, active_max=amax,
        use_pallas=os.environ.get("REPRO_MOE_PALLAS", "0") == "1")
    if "sw1" in p:
        h = jax.nn.silu(x2d @ p["sw1"]) * (x2d @ p["sw3"])
        y = y + h @ p["sw2"]
    if axis is not None:
        y = lax.psum(y, axis)
    aux = load_balance_loss(probs, ids, cfg.n_experts)
    return y, aux


def capacity_for(t_local: int, cfg: ArchConfig, e_pad: int,
                 factor: Optional[float] = None) -> int:
    f = cfg.capacity_factor if factor is None else factor
    c = int(t_local * cfg.top_k * f / max(e_pad, 1)) + 1
    c = min(-(-c // 8) * 8, t_local * cfg.top_k)
    return max(c, 8) if t_local >= 8 else max(c, cfg.top_k)


def moe_ffn(x, p, cfg: ArchConfig, *, mesh_info=None, capacity_factor=None):
    """MoE FFN on [B, S, d] (or [T, d]). Handles optional shard_map wrapping.

    mesh_info: None for single-device, else dict(mesh=Mesh, dp=(axes,),
    tp='model'). Expert weights must already be passed with global shapes;
    shard_map slices them via in_specs.
    """
    shp = x.shape
    x2d = x.reshape(-1, shp[-1]) if x.ndim == 3 else x
    e_pad = p["w1"].shape[0]

    if mesh_info is None:
        # Single-device dispatch is exact by default: top-k ids are distinct
        # per token, so no expert can receive more than T assignments and
        # capacity = T guarantees zero drops. Prefill, decode and teacher-
        # forced eval therefore agree, and the KV-cache exactness tests hold
        # for MoE families too. The capacity-bounded path stays available via
        # an explicit capacity_factor (and is always used under shard_map,
        # where the buffer bounds per-rank work).
        t_loc = x2d.shape[0]
        if capacity_factor is None:
            cap = t_loc
        else:
            cap = capacity_for(t_loc, cfg, e_pad, capacity_factor)
        y, aux = moe_ffn_local(x2d, p, cfg, capacity=cap)
        return y.reshape(shp), aux

    mesh, dp, tp = mesh_info["mesh"], mesh_info["dp"], mesh_info["tp"]
    n_model = mesh.shape[tp]
    mode = expert_shard_mode(cfg, n_model)
    P = jax.sharding.PartitionSpec
    if mode == "expert":
        wspec = {"router": P(), "w1": P(tp), "w3": P(tp), "w2": P(tp)}
        e_loc = e_pad // n_model
    else:
        wspec = {"router": P(), "w1": P(None, None, tp), "w3": P(None, None, tp),
                 "w2": P(None, tp, None)}
        e_loc = e_pad
    if "sw1" in p:
        wspec.update({"sw1": P(None, tp), "sw3": P(None, tp), "sw2": P(tp, None)})

    B = shp[0]
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    if B % n_dp:  # tiny/odd batches: replicate tokens over the data axes
        dp = ()
        n_dp = 1
    t_loc = (B // n_dp) * (shp[1] if x.ndim == 3 else 1)
    cap = capacity_for(t_loc, cfg, e_pad, capacity_factor)

    def body(xl, pl):
        xl2 = xl.reshape(-1, xl.shape[-1])
        if mode == "expert":
            e0 = lax.axis_index(tp) * e_loc
        else:
            e0 = 0
        y, aux = moe_ffn_local(xl2, pl, cfg, capacity=cap, e_start=e0, axis=tp)
        aux = lax.pmean(aux, dp + (tp,))
        return y.reshape(xl.shape), aux

    xspec = P(dp, *([None] * (x.ndim - 1)))
    out = jax.shard_map(
        body, mesh=mesh, in_specs=(xspec, wspec),
        out_specs=(xspec, P()), check_vma=False)(x, {k: p[k] for k in wspec})
    return out


# ---------------------------------------------------------------------------
# exact per-expert path (serving engine; paper's execution model) + oracle
# ---------------------------------------------------------------------------


def expert_ffn_exact(x2d, w, ids, expert_weights):
    """Sequential exact MoE: loop experts, mask-select tokens (no drops).

    expert_weights: list of (w1, w3, w2) per real expert — in the engine these
    come from the *device expert cache*, not a monolithic array.
    """
    T, d = x2d.shape
    y = jnp.zeros((T, d), jnp.float32)
    for e, (w1, w3, w2) in enumerate(expert_weights):
        m = (ids == e)                       # [T, k]
        gate = (w * m).sum(-1)               # [T]
        h = jax.nn.silu(x2d @ w1) * (x2d @ w3)
        y = y + (h @ w2).astype(jnp.float32) * gate[:, None]
    return y.astype(x2d.dtype)


def moe_ffn_ref(x2d, p, cfg: ArchConfig):
    """Dense-loop oracle (no capacity drops) for tests."""
    w, ids, probs = route(x2d, p["router"], cfg.n_experts, cfg.top_k)
    ew = [(p["w1"][e], p["w3"][e], p["w2"][e]) for e in range(cfg.n_experts)]
    y = expert_ffn_exact(x2d, w, ids, ew)
    if "sw1" in p:
        h = jax.nn.silu(x2d @ p["sw1"]) * (x2d @ p["sw3"])
        y = y + h @ p["sw2"]
    return y, load_balance_loss(probs, ids, cfg.n_experts)
