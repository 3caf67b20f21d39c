"""Deterministic synthetic LM data pipeline + streaming stats accumulation.

Generates structured (learnable) token streams on-device: a mixture of
order-2 Markov chains whose transition tables are fixed by seed. Losses on
this data genuinely decrease during the end-to-end training examples, unlike
uniform-random tokens. Batches are generated per (step, shard) from the PRNG
key alone, so any data-parallel worker can materialize exactly its shard —
the standard deterministic-pipeline contract.

``stream_sufficient_stats`` is the pipeline-side bridge into the stats-first
consensus engine: it folds an iterator of per-agent feature batches into the
engine's :class:`~repro.core.engine.SufficientStats` (chunked, bounded peak
memory), so multi-task ELM heads can be fitted over data that never fully
materializes.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_modes: int = 8        # Markov mixture components


def _transition_logits(cfg: DataConfig):
    key = jax.random.PRNGKey(cfg.seed)
    # (modes, vocab_bucket, vocab) low-rank transition structure
    vb = min(cfg.vocab_size, 256)
    return jax.random.gumbel(key, (cfg.n_modes, vb, vb)) * 2.0


def make_batch(cfg: DataConfig, step: int):
    """Returns {tokens, labels} of shape (global_batch, seq_len)."""
    vb = min(cfg.vocab_size, 256)
    trans = _transition_logits(cfg)
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 1), step)
    kmode, kinit, kscan = jax.random.split(key, 3)
    modes = jax.random.randint(kmode, (cfg.global_batch,), 0, cfg.n_modes)
    tok0 = jax.random.randint(kinit, (cfg.global_batch,), 0, vb)

    def step_fn(carry, k):
        tok = carry
        logits = trans[modes, tok]                  # (B, vb)
        nxt = jax.random.categorical(k, logits)
        return nxt, nxt

    keys = jax.random.split(kscan, cfg.seq_len)
    _, toks = jax.lax.scan(step_fn, tok0, keys)
    tokens = jnp.concatenate([tok0[:, None], toks.T], axis=1)[:, : cfg.seq_len]
    labels = jnp.concatenate(
        [tokens[:, 1:], -jnp.ones((cfg.global_batch, 1), jnp.int32)], axis=1
    )
    return {"tokens": tokens.astype(jnp.int32), "labels": labels.astype(jnp.int32)}


def batches(cfg: DataConfig, start_step: int = 0) -> Iterator[dict]:
    gen = jax.jit(lambda s: make_batch(cfg, s))
    step = start_step
    while True:
        yield gen(step)
        step += 1


def stream_sufficient_stats(
    feature_batches: Iterable[Tuple[jax.Array, jax.Array]],
    stats=None,
    *,
    chunk: Optional[int] = None,
    use_pallas: bool | None = None,
    precision: str = "fp32",
    compensated: bool = False,
    producer: str = "materialized",
    feature_map=None,
):
    """Fold a stream of per-agent feature batches into SufficientStats.

    feature_batches yields (H, T) with H: (m, B, L), T: (m, B, d) — e.g.
    frozen-backbone pooled features and task targets.  Each batch goes
    through the engine's Gram producer (on TPU: the agent-batched
    triangular Pallas kernel, ONE launch per batch for all m agents);
    ``chunk`` caps the rows folded per inner step so arbitrarily large
    stream batches accumulate at bounded peak memory.  Chunked accumulation
    equals one-shot accumulation exactly (zero-row padding is a no-op).

    ``producer="fused"`` (with ``feature_map=``, the frozen ELM hidden
    layer) switches the stream to RAW inputs: batches yield (X, T) with
    X: (m, B, d_in), and ``H = act(X W + b)`` is computed inside the Gram
    kernel — the hidden features never materialize in HBM, at any point of
    the stream (``engine.produce_stats``).

    ``precision="bf16"`` streams the Gram pass in bf16 with fp32
    accumulators ("int8" streams per-tile-quantized tiles, unfused only);
    ``compensated=True`` switches the running G/R/t2 totals to Kahan
    summation carried across the WHOLE stream — every batch's contribution
    (itself reduced from zero, chunked if requested) is folded through one
    compensated add, so long streams of small batches don't lose low bits
    against the running totals (recommended together with bf16).
    """
    from repro.core.engine import (
        SufficientStats, _kahan_add, accumulate_stats,
        accumulate_stats_chunked, init_stats,
    )

    def empty_stats(H, T):
        L = feature_map.L if producer == "fused" else H.shape[-1]
        return init_stats(H.shape[0], L, T.shape[-1], jnp.float32)

    # one span over the stream; inside it, the time each pull of the next
    # batch waited for its producer
    with obs_trace.span("stats", producer=producer,
                        precision=precision) as span_args:
        comp = None
        batches = 0
        for H, T in _pulls(feature_batches):
            batches += 1
            if stats is None:
                stats = empty_stats(H, T)
            if not compensated:
                if chunk is not None and H.shape[1] > chunk:
                    stats = accumulate_stats_chunked(stats, H, T, chunk,
                                                     use_pallas=use_pallas,
                                                     precision=precision,
                                                     producer=producer,
                                                     feature_map=feature_map)
                else:
                    stats = accumulate_stats(stats, H, T,
                                             use_pallas=use_pallas,
                                             precision=precision,
                                             producer=producer,
                                             feature_map=feature_map)
                continue
            # Compensated: reduce THIS batch alone from zero (its internal
            # sums are same-magnitude, so the plain/chunked fold is fine),
            # then fold it into the running totals through Kahan adds whose
            # compensation persists across batches.
            zero = empty_stats(H, T)
            if chunk is not None and H.shape[1] > chunk:
                b = accumulate_stats_chunked(zero, H, T, chunk,
                                             use_pallas=use_pallas,
                                             precision=precision,
                                             compensated=True,
                                             producer=producer,
                                             feature_map=feature_map)
            else:
                b = accumulate_stats(zero, H, T, use_pallas=use_pallas,
                                     precision=precision, producer=producer,
                                     feature_map=feature_map)
            t2_run = jnp.broadcast_to(
                jnp.asarray(stats.t2, jnp.float32), b.t2.shape)
            if comp is None:
                comp = (jnp.zeros_like(stats.G), jnp.zeros_like(stats.R),
                        jnp.zeros_like(t2_run))
            G, cG = _kahan_add(stats.G, comp[0], b.G)
            R, cR = _kahan_add(stats.R, comp[1], b.R)
            t2, ct2 = _kahan_add(t2_run, comp[2], b.t2)
            comp = (cG, cR, ct2)
            stats = SufficientStats(G=G, R=R, n=stats.n + b.n, t2=t2)
        if span_args is not None:
            span_args["batches"] = batches
    if stats is None:
        raise ValueError(
            "stream_sufficient_stats: empty feature stream and no initial "
            "stats — pass `stats=init_stats(...)` or a non-empty iterator"
        )
    return stats


def _pulls(batches):
    """The items of ``batches``, each pull inside a ``feed_wait`` span."""
    it = iter(batches)
    while True:
        with obs_trace.span("feed_wait"):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item
