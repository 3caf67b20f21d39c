"""Activation-sharding helpers.

Models annotate activations with *physical* mesh axes via ``maybe_shard``;
when no mesh is active (unit tests, single-CPU runs) the call is a no-op, so
the same model code runs everywhere. Weight shardings are assigned by
path-pattern rules in ``repro.launch.shardings`` at jit boundaries.

Conventions (DESIGN.md §8):
  batch    -> ("pod", "data")  (both data-parallel axes)
  heads/ff/experts/vocab -> "model" (tensor/expert parallel)
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def _active_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def _filter_spec(spec: P, axis_names) -> P:
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axis_names)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in axis_names else None)
    return P(*out)


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint if a mesh is active; no-op otherwise.

    Axes named in ``spec`` but absent from the active mesh are dropped, so
    the same annotations work for (data, model) and (pod, data, model).
    """
    mesh = _active_mesh()
    if mesh is None:
        return x
    spec = _filter_spec(spec, mesh.axis_names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


import os


def _seq_shard_enabled() -> bool:
    return os.environ.get("REPRO_NO_SEQ_SHARD", "0") != "1"


def shard_batch_seq(x: jax.Array) -> jax.Array:
    """(B, S, ...) activations: batch over the data axes; for long sequences
    additionally shard S over "model" (Megatron-style sequence parallelism).

    The block boundary is where scan carries / remat residuals live, so
    seq-sharding here divides the dominant activation-memory term by the
    model-axis size; GSPMD inserts the all-gather on entry to attention.
    REPRO_NO_SEQ_SHARD=1 disables it (perf-hillclimb knob)."""
    rest = (None,) * (x.ndim - 2)
    if x.ndim >= 2 and x.shape[1] >= 1024 and _seq_shard_enabled():
        return maybe_shard(x, P(BATCH_AXES, MODEL_AXIS, *rest))
    return maybe_shard(x, P(BATCH_AXES, None, *rest))


def shard_heads(x: jax.Array) -> jax.Array:
    """(B, S, H, D) attention tensors: batch over data, heads over model."""
    return maybe_shard(x, P(BATCH_AXES, None, MODEL_AXIS, None))


def shard_ff(x: jax.Array) -> jax.Array:
    """(B, S, F) mlp hidden: batch over data, features over model."""
    return maybe_shard(x, P(BATCH_AXES, None, MODEL_AXIS))
