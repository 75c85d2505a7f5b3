"""Plain float32 reference of a decoder-only mixture-of-experts model
(Mixtral-8x7B, arXiv:2401.04088; Qwen3-MoE, hf:Qwen/Qwen3-30B-A3B), as the
published equations state, with no cache, no batching and no kernels:

  h_0 = E[x]
  per layer l:
    a   = RMSNorm(h; g1)                                 (eps from config)
    q, k, v = a Wq, a Wk, a Wv;  Qwen3: q, k = RMSNorm per head (qk_norm)
    q, k = RoPE(q, k)  (rotate-half, inv_freq = theta^(-2i/head_dim))
    h  += softmax(q k^T / sqrt(head_dim) + causal) v Wo  (GQA: query head
                                                          h uses kv head
                                                          h // (H / Hkv))
    m   = RMSNorm(h; g2)
    p   = softmax(m Wr);  top-k of p, renormalised to sum 1
    h  += sum_j p_j * (silu(m W1_j) * (m W3_j)) W2_j
  logits = RMSNorm(h; gf) Wlm^T         (Wlm = E: the served head is tied)

Everything runs in float32 under ``highest`` matmul precision, computed
layer by layer over all sequences at once and in row blocks, so a layer's
experts are made (``weights.experts``) and dropped a group at a time.
``quant="fp8"`` is the control: every matmul operand rounded to
float8_e4m3 with a per-row (or per-column) absmax scale, the precision step
below the bfloat16 the configurations state.

It imports nothing of the program and takes nothing the program made: it
makes its weights from the configuration's weight seed.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
ROW_BLOCK = 2048        # rows of a block of the expert and head passes
SEQ_MIN = 256           # sequences are padded to a power of two at least this


def _q8(x, axis):
    """Round ``x`` to float8_e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, b, quant):
    """a [..., K] @ b [K, N] in float32 (or the fp8 control)."""
    a, b = a.astype(F32), b.astype(F32)
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, gain_offset, eps):
    g = 1.0 + gain_offset.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half form."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _attn(h, lw, dims, quant):
    """One sequence's attention sub-layer, residual included: h [S, d]."""
    S = h.shape[0]
    H, Hkv, D = dims.n_heads, dims.n_kv_heads, dims.head_dim
    a = _rms(h, lw["ln1"], dims.rms_eps)
    q = _mm(a, lw["wq"], quant).reshape(S, H, D)
    k = _mm(a, lw["wk"], quant).reshape(S, Hkv, D)
    v = _mm(a, lw["wv"], quant).reshape(S, Hkv, D)
    if dims.qk_norm:
        q = _rms(q, lw["q_norm"], dims.rms_eps)
        k = _rms(k, lw["k_norm"], dims.rms_eps)
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=1)          # head h reads kv head h // rep
    v = jnp.repeat(v, rep, axis=1)
    if quant == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * D ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant == "fp8":
        p = _q8(p, -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(S, H * D)
    return h + _mm(o, lw["wo"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _route(h, ln2, router, dims, quant):
    """(normed input m [N, d], gate weight of each expert [N, E], router
    margin [N]: the k-th largest router logit less the (k+1)-th, how near
    the token was to taking another expert)."""
    m = _rms(h, ln2, dims.rms_eps)
    logits = _mm(m, router, quant)
    p = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(p, dims.top_k)
    w = w / jnp.sum(w, -1, keepdims=True)
    gate = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], ids].set(w)
    top = jax.lax.top_k(logits, dims.top_k + 1)[0]
    return m, gate, top[:, -2] - top[:, -1]


def _mmg(a, b, quant):
    """A group of experts: a [..., N, K] @ b [G, K, F] -> [G, N, F]."""
    a, b = a.astype(F32), b.astype(F32)
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 1)
    if a.ndim == 2:
        return jnp.einsum("nk,gkf->gnf", a, b, precision=HIGHEST)
    return jnp.einsum("gnk,gkf->gnf", a, b, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("quant",))
def _experts_out(m, g, w1, w3, w2, quant):
    """sum_e g_e * (silu(m W1_e) * (m W3_e)) W2_e over a group of experts:
    rows m [N, d], gates g [N, G], weights stacked on G."""
    y = jax.nn.silu(_mmg(m, w1, quant)) * _mmg(m, w3, quant)
    return jnp.einsum("gnd,ng->nd", _mmg(y, w2, quant), g, precision=HIGHEST)


def _group(dims) -> int:
    """Experts made and applied per call: about 256 MiB of f32 hidden
    activations per row block, a power of two dividing the expert count."""
    bound = (1 << 28) // (ROW_BLOCK * dims.d_expert * 4)
    g = 1
    while g * 2 <= bound and dims.n_experts % (g * 2) == 0:
        g *= 2
    return g


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _head(h, ln_f, embed, dims, quant):
    """Logits [N, V] of final hidden rows h [N, d]."""
    return _mm(_rms(h, ln_f, dims.rms_eps), embed.T, quant)


def _blocks(n: int):
    for i in range(0, n, ROW_BLOCK):
        yield slice(i, min(i + ROW_BLOCK, n))


def _padded_len(n: int) -> int:
    """A power of two, at least SEQ_MIN: few shapes to compile."""
    return max(SEQ_MIN, 1 << (n - 1).bit_length())


def final_hidden(dims, seqs: Sequence[np.ndarray],
                 quant: Optional[str] = None):
    """(hidden states [len(seq), d] after the last layer, smallest router
    margin over the layers [len(seq)]), one pair per token sequence,
    computed layer by layer over all sequences at once."""
    with jax.default_matmul_precision("highest"):
        dw = W.dense(dims)
        embed = dw["embed"].astype(F32)
        lens = [len(s) for s in seqs]
        padded = [_padded_len(n) for n in lens]
        hs = [embed[jnp.asarray(np.pad(np.asarray(s, np.int32), (0, p - n)))]
              for s, n, p in zip(seqs, lens, padded)]
        n_rows = sum(padded)
        fill = -n_rows % ROW_BLOCK       # whole row blocks: one shape each
        margin = jnp.full((n_rows + fill,), jnp.inf, F32)
        for l in range(dims.n_layers):
            lw = {k: v[l] for k, v in dw.items()
                  if k not in ("embed", "ln_f")}
            hs = [_attn(h, lw, dims, quant) for h in hs]
            x = jnp.pad(jnp.concatenate(hs), ((0, fill), (0, 0)))
            parts = [_route(x[b], lw["ln2"], lw["router"], dims, quant)
                     for b in _blocks(x.shape[0])]
            margin = jnp.minimum(margin, jnp.concatenate(
                [mg for _, _, mg in parts]))
            out = [jnp.zeros_like(x[b]) for b in _blocks(x.shape[0])]
            G = _group(dims)
            for e in range(0, dims.n_experts, G):
                w1, w3, w2 = W.experts(dims, l, e, G)
                out = [o + _experts_out(m, g[:, e:e + G], w1, w3, w2, quant)
                       for o, (m, g, _) in zip(out, parts)]
            x = (x + jnp.concatenate(out))[:n_rows]
            hs = list(jnp.split(x, np.cumsum(padded)[:-1]))
        margins = jnp.split(margin[:n_rows], np.cumsum(padded)[:-1])
        return [(h[:n], mg[:n]) for h, mg, n in zip(hs, margins, lens)]


def served_gaps(dims, rows: jax.Array, tokens: np.ndarray,
                ctrl_rows: Optional[jax.Array] = None):
    """Gaps of chosen tokens below the reference's best logit.

    ``rows`` [N, d]: the reference's final hidden state at each position
    that chose a token; ``tokens`` [N]: the token chosen there. Returns
    ``max(logits) - logits[token]`` per row (0 where the token is the
    reference's own first choice), and, with ``ctrl_rows`` (the control's
    hidden states at the same positions), the same gap of the token the
    control puts first."""
    with jax.default_matmul_precision("highest"):
        dw = W.dense(dims)
        n = rows.shape[0]
        pad = -n % ROW_BLOCK                    # one block shape to compile
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        tokens = np.pad(np.asarray(tokens, np.int32), (0, pad))
        if ctrl_rows is not None:
            ctrl_rows = jnp.pad(ctrl_rows, ((0, pad), (0, 0)))
        gaps, ctrl = [], []
        for b in _blocks(rows.shape[0]):
            lg = _head(rows[b], dw["ln_f"], dw["embed"], dims, None)
            t = jnp.asarray(tokens[b], jnp.int32)[:, None]
            best = jnp.max(lg, -1)
            gaps.append(np.asarray(best - jnp.take_along_axis(lg, t, 1)[:, 0]))
            if ctrl_rows is not None:
                lq = _head(ctrl_rows[b], dw["ln_f"], dw["embed"], dims,
                           "fp8")
                tq = jnp.argmax(lq, -1)[:, None]
                ctrl.append(np.asarray(
                    best - jnp.take_along_axis(lg, tq, 1)[:, 0]))
        gaps = np.concatenate(gaps)[:n] if n else np.zeros(0, np.float32)
        if ctrl_rows is None:
            return gaps, None
        return gaps, np.concatenate(ctrl)[:n]
