"""Public sliding-window attention op with padding + interpret fallback."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.swa.kernel import swa_pallas
from repro.kernels.swa.ref import swa_ref


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "force_ref")
)
def swa_attention(q, k, v, *, window: int, block_q: int = 256,
                  block_k: int = 256, force_ref: bool = False):
    """q: (B, H, S, D); k, v: (B, KV, S, D). Causal sliding-window attention.

    Pads S up to a block multiple; padded queries are garbage but sliced off,
    padded keys are masked by ``k_pos < seq_len`` inside the kernel.
    """
    if force_ref:
        return swa_ref(q, k, v, window)
    B, H, S, D = q.shape
    block_q = min(block_q, S)
    pad = (-S) % block_q
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    out = swa_pallas(
        qp, kp, vp, window=window, block_q=block_q,
        block_k=min(block_k, S + pad), interpret=backend.interpret(),
    )
    return out[:, :, :S]
