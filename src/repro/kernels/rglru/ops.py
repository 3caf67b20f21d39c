"""Public RG-LRU scan op: padding + interpret fallback."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.rglru.kernel import rglru_pallas
from repro.kernels.rglru.ref import rglru_scan_ref


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_d", "force_ref")
)
def rglru_scan(log_a, b, h0, *, block_s: int = 256, block_d: int = 512,
               force_ref: bool = False):
    """h_t = exp(log_a_t) h_{t-1} + b_t over axis 1. Returns (B, S, D) fp32.

    Pads S with log_a=0, b=0 steps (identity updates) and D with dead
    channels; both are exact."""
    if force_ref:
        return rglru_scan_ref(log_a, b, h0)
    B, S, D = log_a.shape
    block_s = min(block_s, S)
    block_d = min(block_d, D)
    pad_s = (-S) % block_s
    pad_d = (-D) % block_d
    la = jnp.pad(log_a, ((0, 0), (0, pad_s), (0, pad_d)))
    bb = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_d)))
    h = jnp.pad(h0, ((0, 0), (0, pad_d)))
    out = rglru_pallas(
        la, bb, h, block_s=block_s, block_d=block_d,
        interpret=backend.interpret(),
    )
    return out[:, :S, :D]
