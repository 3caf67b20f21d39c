"""Public chunkwise-mLSTM op: padding + interpret fallback.

Sequence padding uses identity steps: log_f = 0 (forget nothing) and
i_gate = -inf (admit nothing), so padded positions leave the state
untouched and their outputs are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.mlstm.kernel import mlstm_pallas
from repro.kernels.mlstm.ref import mlstm_sequential_ref


@functools.partial(jax.jit, static_argnames=("chunk", "force_ref"))
def mlstm_chunkwise(q, k, v, log_f, i_gate, *, chunk: int = 64,
                    force_ref: bool = False):
    if force_ref:
        return mlstm_sequential_ref(q, k, v, log_f, i_gate)
    B, H, S, D = q.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        zpad4 = ((0, 0), (0, 0), (0, pad), (0, 0))
        zpad3 = ((0, 0), (0, 0), (0, pad))
        q = jnp.pad(q, zpad4)
        k = jnp.pad(k, zpad4)
        v = jnp.pad(v, zpad4)
        log_f = jnp.pad(log_f, zpad3)
        i_gate = jnp.pad(i_gate, zpad3, constant_values=-1e30)
    out = mlstm_pallas(q, k, v, log_f, i_gate, chunk=chunk,
                       interpret=backend.interpret())
    return out[:, :, :S]
