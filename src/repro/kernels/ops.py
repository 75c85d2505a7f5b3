"""Jit'd public wrappers over the Pallas kernels.

Interpret mode is decided by the backend (``default_interpret``): the
kernels run in the Pallas interpreter on the CPU backend only, and lower
natively everywhere else — never interpreted on a TPU. ``expert_ffn_op`` is
the drop-in hot path for the capacity-dispatched MoE block
(dispatch/combine stay in XLA; the grouped GEMMs run in the double-buffered
kernel).
"""
from __future__ import annotations

import jax

from repro.kernels.expert_ffn import DEFAULT_BLOCK_F, expert_ffn
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.ssd_scan import ssd_scan


def default_interpret() -> bool:
    """Interpret Pallas kernels on the CPU backend only."""
    return jax.default_backend() == "cpu"


def expert_ffn_op(x, w1, w3, w2, *, block_f: int = DEFAULT_BLOCK_F,
                  interpret: bool | None = None):
    return expert_ffn(x, w1, w3, w2, block_f=block_f,
                      interpret=default_interpret() if interpret is None
                      else interpret)


def flash_attention_op(q, k, v, *, causal=True, window=-1, block_q=512,
                       block_k=512, interpret: bool | None = None):
    return flash_attention(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k,
        interpret=default_interpret() if interpret is None else interpret)


def ssd_scan_op(x, b, c, da, dt, *, chunk=256, interpret: bool | None = None):
    return ssd_scan(x, b, c, da, dt, chunk=chunk,
                    interpret=default_interpret() if interpret is None
                    else interpret)


def flash_decode_op(q, k, v, slot_pos, pos, *, window=-1, block_k=512,
                    interpret: bool | None = None):
    return flash_decode(
        q, k, v, slot_pos, pos, window=window, block_k=block_k,
        interpret=default_interpret() if interpret is None else interpret)
