"""Double-buffered streaming expert FFN — the DuoServe prefill pipeline as a
TPU Pallas kernel.

The paper overlaps expert-weight fetches with expert computation using two
CUDA streams and a k-slot GPU cache. On TPU the same structure is expressed
with Pallas grid pipelining: the grid walks (expert e, hidden tile j); while
the MXU computes tile (e, j), Pallas's automatic double buffering DMAs tile
(e, j+1) — and across experts, expert e+1's first tiles stream from HBM while
expert e finishes. HBM here plays the role of the paper's host-side expert
cache; VMEM is the k=2-deep device-side cache (one tile computing, one
arriving).

Operands:
  x   [E, C, d]   capacity-grouped tokens (dispatch done upstream)
  w1  [E, d, f]   gate proj     w3 [E, d, f] up proj     w2 [E, f, d] down
  out [E, C, d]   f32 accumulated across hidden tiles

The stacked [E, ...] weight layout is exactly the ExpertResidency slot-pool
layout (core/cache.py: [pool_capacity, d, de] buffers) — `expert_ffn_from_pool`
runs the kernel straight off the serving engine's resident pools by slot
index, so the prefill pipeline and the Pallas kernel share one weight-access
convention.

Grid: (E, f // block_f); the hidden dim is tiled so each expert's working set
fits VMEM regardless of d_expert (SwiGLU is computed per f-tile and
down-projected immediately: out += (silu(x@w1_j) * (x@w3_j)) @ w2_j).

The default tile, ``DEFAULT_BLOCK_F = 256``, is the largest that fits a TPU
v5e's default 16 MiB of scoped VMEM at mixtral-8x7b widths (d=4096,
f=14336): at 512 the three double-buffered 4 MiB weight tiles alone come to
24 MiB and the chip's compiler refuses the kernel
(tests/test_tpu_compile.py compiles it for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]                      # [C, d] bf16
    h1 = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
    h3 = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
    h = jax.nn.silu(h1) * h3          # [C, bf] f32
    o_ref[0] += jnp.dot(h.astype(x.dtype), w2_ref[0],
                        preferred_element_type=jnp.float32)


DEFAULT_BLOCK_F = 256
LANES = 128


def f_tile(f: int, block_f: int = DEFAULT_BLOCK_F) -> int:
    """Hidden-dim tile for d_expert ``f``: the full dim when it fits one
    block (``f <= max(block_f, 128)``), else the largest multiple of 128
    lanes that is at most ``block_f`` and divides ``f``. A tile the TPU
    cannot lay out is an error, not a silent fallback."""
    if f <= max(block_f, LANES):
        return f
    bf = max(LANES, block_f - block_f % LANES)
    while f % bf:
        bf -= LANES
        if bf < LANES:
            raise ValueError(f"d_expert={f} has no tile of a multiple of "
                             f"{LANES} lanes <= block_f={block_f}")
    return bf


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def expert_ffn(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array,
               *, block_f: int = DEFAULT_BLOCK_F,
               interpret: bool = False) -> jax.Array:
    """x: [E, C, d]; w1/w3: [E, d, f]; w2: [E, f, d] -> [E, C, d] (x.dtype)."""
    E, C, d = x.shape
    f = w1.shape[2]
    bf = f_tile(f, block_f)
    grid = (E, f // bf)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, d), lambda e, j: (e, 0, 0)),
            pl.BlockSpec((1, d, bf), lambda e, j: (e, 0, j)),
            pl.BlockSpec((1, d, bf), lambda e, j: (e, 0, j)),
            pl.BlockSpec((1, bf, d), lambda e, j: (e, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, d), lambda e, j: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, d), jnp.float32),
        interpret=interpret,
    )(x, w1, w3, w2)
    return out.astype(x.dtype)


def expert_ffn_from_pool(x: jax.Array, w1_pool: jax.Array,
                         w3_pool: jax.Array, w2_pool: jax.Array,
                         slots, **kw) -> jax.Array:
    """Run the streaming expert FFN straight off ExpertResidency slot pools.

    x: [E', C, d] capacity-grouped tokens for E' active experts; slots: [E']
    pool slot of each active expert (``residency.slot(key)``); w*_pool: the
    residency's fixed [pool_capacity, ...] buffers. The gather selects only
    the active experts' slabs, so the kernel's HBM reads stay bounded by the
    residency capacity — the device-side counterpart of the paper's k-slot
    cache feeding the two-stream prefill pipeline.
    """
    idx = jnp.asarray(slots, jnp.int32)
    return expert_ffn(x, w1_pool[idx], w3_pool[idx], w2_pool[idx], **kw)
