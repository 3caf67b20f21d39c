"""The benchmark's inputs, made from ``--seed``: raw features, targets, the
frozen hidden layer's weights and every fit's start state.

The feature generator is a copy of ``data.synthetic.multitask_classification``
(tasks share a latent class subspace; each task classifies ``D`` of the
``n_global_classes`` classes), split in two so that a stream can draw its
chunks from one task structure: ``tasks`` fixes the prototypes and each
task's classes, ``samples`` draws rows of it.  Features come out in
bfloat16, as a bf16 backbone emits them.  A resident cell widens them to
float32 in a program of its own (``widen``): inside one jitted program XLA
may drop a round trip through bf16 (excess precision), and the values
would not be bf16 values.  The frozen hidden layer's weights are drawn in
float32 as ``core.elm.make_feature_map(dist="normal")`` draws them.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Keys(NamedTuple):
    tasks: jax.Array     # prototypes and per-task classes
    samples: jax.Array   # feature rows (folded with a chunk index)
    weights: jax.Array   # the frozen hidden layer
    start: jax.Array     # each fit's start state (folded with the fit index)
    sample: jax.Array    # which fit the check compares


def keys(seed: int) -> Keys:
    return Keys(*jax.random.split(seed_key(seed), 5))


class Tasks(NamedTuple):
    protos: jax.Array        # (n_global_classes, d_in)
    task_classes: jax.Array  # (m, D) global class ids per task


def tasks(key, *, m: int, d_in: int, n_global_classes: int, D: int,
          latent_r: int = 8, class_sep: float = 2.0) -> Tasks:
    kp, kb, kt = jax.random.split(key, 3)
    basis = jax.random.normal(kb, (latent_r, d_in)) / jnp.sqrt(latent_r)
    protos = class_sep * jax.random.normal(kp, (n_global_classes, latent_r))
    task_classes = jax.vmap(
        lambda k: jax.random.choice(k, n_global_classes, shape=(D,),
                                    replace=False)
    )(jax.random.split(kt, m))
    return Tasks(protos @ basis, task_classes)


def samples(key, t: Tasks, n: int, noise: float = 1.0):
    """(X (m, n, d_in) bfloat16; T (m, n, D) one-hot float32)."""
    m, D = t.task_classes.shape
    d_in = t.protos.shape[-1]

    def one(k, classes):
        ky, kx = jax.random.split(k)
        y = jax.random.randint(ky, (n,), 0, D)
        x = t.protos[classes[y]] + noise * jax.random.normal(kx, (n, d_in))
        return x, jax.nn.one_hot(y, D, dtype=jnp.float32)

    X, T = jax.vmap(one)(jax.random.split(key, m), t.task_classes)
    return X.astype(jnp.bfloat16), T


def hidden_weights(key, d_in: int, L: int):
    """(W (d_in, L), b (L,)) float32: W ~ N(0, 1/d_in), b ~ N(0, 1)."""
    kw, kb = jax.random.split(key)
    W = jax.random.normal(kw, (d_in, L), jnp.float32) / jnp.sqrt(d_in)
    return W, jax.random.normal(kb, (L,), jnp.float32)


def widen(x: jax.Array) -> jax.Array:
    """A bf16 array as float32, in a program of its own (exact)."""
    return x.astype(jnp.float32)


def start_state(key, fit_index: int, m: int, L: int, r: int, D: int):
    """(U0 (m, L, r), A0 (m, r, D)) float32 for fit ``fit_index``."""
    kU, kA = jax.random.split(jax.random.fold_in(key, fit_index))
    return (jax.random.normal(kU, (m, L, r), jnp.float32),
            jax.random.normal(kA, (m, r, D), jnp.float32))


class Reservoir:
    """Keeps one fit, drawn uniformly from all fits offered, from a seed:
    fit ``j`` replaces the kept one with probability 1 / (j + 1)."""

    def __init__(self, key):
        self._rng = np.random.default_rng(
            np.asarray(key, np.uint32).tolist())
        self.index = None
        self.item = None

    def offer(self, index: int, item) -> None:
        if self._rng.random() * (index + 1) < 1.0:
            self.index, self.item = index, item
