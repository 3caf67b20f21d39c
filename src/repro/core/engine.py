"""Stats-first consensus engine: ONE ADMM agent-update body, many executors.

All three of the paper's algorithms (MTL-ELM, DMTL-ELM, FO-DMTL-ELM) reduce
to per-agent updates over the sufficient statistics

    G_t = H_t^T H_t     (L, L)   feature Gram
    R_t = H_t^T T_t     (L, d)   feature-target cross terms

so the engine is organized around a shared :class:`SufficientStats` type and
a single pure per-agent round, instead of one implementation per execution
backend:

  ``sufficient_stats`` / ``sufficient_stats_fused`` / ``accumulate_stats``
      The stats producers — the Pallas ``gram`` kernels, or their jnp
      oracles.  ``use_pallas=None`` (the default) means the compiled kernel
      on a TPU and the jnp oracle on the CPU; ``use_pallas=True`` on the
      CPU runs the kernel in interpret mode (``repro.kernels.backend``
      decides, in one place).  On the Pallas path a stacked
      (m, N, L) input is ONE agent-batched triangular-grid kernel launch
      (``gram_batched``: grid (m, tri, n + 1), the trailing step mirroring
      G's symmetric tiles in-kernel) rather than m vmapped launches.
      Streaming accumulation is chunked addition of producer outputs, so
      chunked == one-shot exactly; ``compensated=True`` upgrades the
      chunked fold to Kahan summation for long low-magnitude streams.
      ``produce_stats`` dispatches on the producer/precision matrix
      (``cfg.stats_producer`` x ``cfg.stats_precision`` — oracle relations
      asserted in tests):

        materialized fp32   H computed in XLA, then reduced by the
                            triangular kernel (on a TPU) or by
                            ``gram_ref`` (the CPU default).  The parity
                            oracle for every row below.
        materialized bf16   bf16 tiles, fp32 accumulators: half the H read
                            traffic, ~4e-3 relative error on G/R — see
                            ``benchmarks/convergence.run_precision`` for
                            the ADMM impact.
        materialized int8   per-(BN, BL)-tile maxabs/127 scales +
                            stochastic rounding, int8 MXU tiles with exact
                            int32 tile sums (half of bf16 again).
                            ``quant_seed`` selects the rounding stream;
                            the mean over seeds converges to the fp32
                            truth (unbiased).
        fused fp32          H = act(X W + b) computed INSIDE the kernel
                            from raw features; H never hits HBM.
                            Bitwise-identical to materialized fp32
                            on H built from the kernel's tiles.
        fused bf16          in-kernel hidden tiles rounded to bf16 before
                            the MXU — matches the materialized bf16
                            stream bit for bit.
  ``agent_update``
      The one ADMM round body for ONE agent (paper eqs. 19/23 + 21): U-solve
      through the solver registry (``kron`` | ``sylvester`` | ``cg`` |
      ``pcg`` — Gram-diagonal-preconditioned CG for backbone-scale L), the
      first-order branch, and the local A-solve.  Pure function of
      ``(stats, state, neighbor_msgs, cfg)`` — no communication inside.
  ``dual_step``
      The shared adaptive-gamma dual ascent (eq. 16 + Lemma 2), per edge.
  ``fit_dense``
      Executor 1: all agents on one device; neighbor messages are dense
      incidence/adjacency einsums, the body is ``jax.vmap``-ed over agents.
      Sweep order: synchronous Jacobian (every agent reads its neighbors'
      previous iterate), the paper's scheme.
  ``fit_sharded``
      Executor 2: one agent per mesh shard on a ring/torus; neighbor
      messages travel over ``jax.lax.ppermute``, the *same* body runs
      per shard inside ``shard_map``.  Jacobian sweep order (all shards
      update simultaneously each round).  The fast path when the graph IS
      the mesh torus (up to edge orientation — ``graph_matches_torus``).
  ``fit_colored``
      Executor 3: Gauss-Seidel colored sweeps — agents update one color
      class of ``Graph.chromatic_schedule()`` at a time, re-gathering
      neighbor messages between phases so later classes see the current
      iterate of earlier classes.  A ``staleness`` knob delays neighbor
      messages by k rounds to model asynchronous execution.
  ``fit_sharded_graph``
      Executor 4: ANY connected ``Graph`` on the mesh — the edge-schedule
      compiler (``graph.compile_edge_schedule``) decomposes the edge list
      into ≤ Δ+1 matchings (Misra-Gries proper edge coloring), each
      matching ONE partial ``ppermute`` round on the flattened agent axes;
      per-edge duals live on the edge's source shard (slot table).  An
      optional vertex ``schedule`` runs ``fit_colored``-style phase-masked
      Gauss-Seidel sweeps inside shard_map.
  ``fit_async``
      Executor 5: event-driven asynchrony (``repro.netsim``) — a
      ``ChannelModel`` (per-edge delay distribution, message drops,
      compute stragglers) is sampled up front into a fixed-shape
      ``EventTape`` and the whole simulated run is one ``jax.lax.scan``
      around the same body, with stale neighbor views served from a ring
      buffer of published subspaces.

Executor matrix — one ``agent_update`` body, five message schedules, all
drawing their neighbor views from the ONE exchange layer
(``repro.core.exchange``), each pinned to the reference by a parity oracle
(all asserted in tests):

  1. ``fit_dense``          vmap + ``exchange.DenseExchange`` edge-list
                            segment sums; the reference.
  2. ``fit_sharded``        ring/torus ppermute; ≡ ``fit_dense`` on the
                            mesh torus (up to edge orientation).  Robust
                            reduce via ``exchange.stack_ring_candidates``.
  3. ``fit_colored``        sequential color phases over the same
                            ``DenseExchange``; ``staleness=1`` or the
                            single-class ``jacobian_schedule`` ≡
                            ``fit_dense`` (bitwise).
  4. ``fit_sharded_graph``  ``exchange.ShardedGraphExchange``: compiled
                            ≤ Δ+1 ppermute rounds on any graph;
                            ``schedule=None`` ≡ ``fit_dense``, a chromatic
                            ``schedule`` ≡ ``fit_colored(staleness=0)``.
  5. ``fit_async``          event-tape scan; views gathered by
                            ``exchange.DenseTapeGather``;
                            ``zero_delay_tape`` ≡ ``fit_dense`` (bitwise),
                            ``constant_tape(k)`` ≡
                            ``fit_colored(staleness=k)``, an all-dropped
                            channel ≡ ``fit_colored(staleness>=iters)``
                            (every view pinned at U^0), and a zero-attack
                            full-membership ``AdversaryTape`` ≡ its base
                            ``EventTape`` (bitwise).

The exchange-layer contract (``repro.core.exchange``): a backend turns
(published iterates, duals, an optional per-tick round context) into an
``ExchangeViews`` bundle — the aggregated neighbor sum ``neigh``, the
shipped-dual transpose term ``ct_lam``, the effective (live) degree and
proximal weight, the aggregation ``center``, and, for robust aggregators,
the padded candidate ``table`` + validity ``mask`` that feed
``cfg.aggregator``.  Two backends realize it:

* ``DenseExchange``       — edge-list gather/segment-sum over all agents
                            on one device (vmap executors 1 and 3); its
                            tape-driving wrapper ``DenseTapeGather``
                            age-selects views from the published-U ring
                            buffer and applies ``exchange.apply_attack``
                            corruption per tick (executor 5).
* ``ShardedGraphExchange`` — masked-ppermute rounds over the compiled
                            edge schedule inside ``shard_map`` (executor
                            4); its tape driver (``tape_exchange`` /
                            ``tape_ct_lam`` + host-side ``tape_tables``)
                            replays the SAME EventTape/AdversaryTape
                            in-mesh: each shard keeps a depth-D ring
                            buffer of its OWN published U (RunState
                            ``hist``), the sender age-selects and
                            corrupts what each ppermute ships, and the
                            receiver masks arrivals by the tape's
                            membership/round liveness.  Executor
                            ``"sharded"``/``"sharded_graph"`` therefore
                            accepts ``tape=`` and replays asynchrony +
                            Byzantine behavior + churn with multi-device
                            parallelism, agreeing with ``fit_async`` on
                            the same tape (bitwise for zero-delay /
                            zero-adversary tapes, psum-reduction-order
                            tolerance otherwise — measured and pinned in
                            tests).

Robust aggregation (``cfg.aggregator``) threads through ALL FIVE rows:
``"mean"`` keeps every executor's pre-existing plain-sum gather verbatim
(segment sums, ppermute adds — the bitwise parity oracle for the knob),
while ``"trimmed_mean"`` / ``"coordinate_median"`` / ``"krum_like"``
replace ``neigh_sum`` with ``deg * robust_center(received views + own U)``
— dense/colored/GS gather a padded (m, K) neighbor table, the sharded
executors stack their per-round/per-axis ppermute deliveries (round-mask
aware on ``fit_sharded_graph``: idle-round zeros are EXCLUDED, never
treated as candidates), and the tape drivers feed the per-tick delivered
(possibly adversary-corrupted) views.  Membership events ride the two
tape-replaying paths (``fit_async`` and the in-mesh tape driver): an
``AdversaryTape``'s per-tick ``member`` row masks a departed agent's
edges out of every reduction (its duals freeze via the masked residuals),
re-resolves the scalar-tau proximal weight against the LIVE degree,
freezes the agent itself like a straggler tick, and warm-starts a
(re)joining agent from the aggregate of its live neighbors; the other
executor paths treat membership as out of scope (static graphs).

The executor contract: all five return per-iteration diagnostics with the
SAME keys — ``objective`` (primal, eq. 12), ``lagrangian`` (eq. 13),
``consensus`` (RMS edge disagreement), ``gamma``/``gamma_min`` (mean/min
adaptive dual step over edges — the ``cfg.gamma_floor`` observable) and
``primal_sq`` — all computable from stats alone because every stats leaf
(G, R, n, t2) is threaded through each executor, including the shard_map
paths.  (``fit_async`` additionally reports ``tape_cursor``, the absolute
tape tick each row was computed at, so a resumed run can be audited
against its tape position.)

Telemetry extension (``cfg.telemetry=True``; the observability layer,
``repro.obs``): every executor additionally reports, per iteration,

  resid_max       max |C U| over live edges (worst-agent consensus)
  msgs_delivered  fresh deliveries this tick (age == 1, live edges)
  msgs_stale      stale-served deliveries (age > 1, live edges)
  msgs_dropped    deliveries masked out (dead membership / idle rounds)
  agg_rejected    robust-aggregation rejection count — candidates flagged
                  as distance outliers by ``exchange.aggregator_audit``
                  (identically 0.0 for the mean aggregator and on clean
                  federations; the Byzantine-detection signal, verifiable
                  against ``AdversaryTape.attack`` ground truth)
  comm_floats     the analytic floats-per-iteration model of the
                  executor's message schedule
                  (``repro.obs.counters.modeled_floats_per_iter``)

The fresh-view executors (dense / colored / sharded / sharded_graph
without a tape) report the static schedule (all deliveries fresh); the
tape paths count from the replayed ``age``/``member`` rows, and the two
tape drivers agree on the same tape.  Zero-overhead guarantee: the gate
is a Python-level ``if cfg.telemetry`` at trace time, so with telemetry
OFF the diag key set, every value, and the sha256 golden-path hashes are
byte-identical to the pre-telemetry engine; host-side span tracing
(``obs.Tracer``) is likewise a no-op unless a tracer is installed.

Checkpointable runtime — the segmented step core under every executor:

Each ``fit_*`` is a thin wrapper over ONE shared, explicitly serializable
:class:`RunState` pytree (``U``, ``A``, per-edge duals ``lam``, the
iteration counter ``k``, and — where the executor needs them — the
published-subspace ring buffer ``hist`` and the aged-dual ring buffer
``lam_hist``) advanced by a :class:`Runner`:

    runner = make_runner(stats, g, cfg, executor=...)
    state  = runner.init_state()                     # RunState at k = 0
    state, diags = runner.run_segment(state, n)      # n more iterations
    state, diags = runner.run()                      # drive to cfg.iters

The segment core is constructed so that a segment boundary CANNOT perturb
the numerics: every scan carry is structurally identical to the monolithic
executor's carry (the counter ``k`` advances outside the scan; the async
executor threads the absolute tick through the scan inputs), so splitting
``cfg.iters`` into any sequence of ``run_segment`` calls — including a
save/restore through ``repro.checkpoint`` between segments — is bitwise
identical to the uninterrupted run, in final state AND in every
diagnostics trajectory, for all five executors and both dual modes.  The
shard_map executors feed ``RunState`` leaves in as sharded operands
(``Runner.state_shardings()`` gives the matching NamedSharding tree for
restore-onto-mesh).

Checkpoint layout (``repro.checkpoint.runstate`` drives it through
``fit(..., checkpoint_dir=, checkpoint_every=, resume=)``):

    <dir>/step_<k>/arrays.npz   flat ``state/*`` + ``diags/*`` leaves
                                (non-native dtypes stored as byte views)
    <dir>/step_<k>/meta.json    step, key order, per-leaf dtype strings,
                                executor name + cfg.iters for resume audit

Sweep-order / staleness trade-off: Gauss-Seidel (``fit_colored``,
``staleness=0``) propagates information within an iteration and typically
reaches a given objective in fewer iterations than Jacobian, but its color
phases are sequential — per-iteration parallel width drops from ``m`` to
``max_class_size``, so it suits few-device / iteration-bound deployments,
while the Jacobian executors (``fit_dense`` / ``fit_sharded``) keep all
agents in flight and suit wide meshes.  ``staleness=k`` interpolates toward
asynchrony tolerance: ``staleness=1`` is exactly the Jacobian schedule (the
parity oracle — so is the single-class ``jacobian_schedule(m)``), larger k
emulates k-round-late messages and degrades convergence gracefully instead
of blocking on stragglers.

Because all executors call the identical ``agent_update``, cross-executor
parity is true by construction; new topologies or async sweeps only need a
new executor, never a new update body.  Iteration-invariant work (the
eigendecomposition of G_t used by the ``sylvester`` solver) is hoisted out
of the ADMM scan by ``hoist_precomp`` in every executor.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import exchange
from repro.core.graph import Graph
from repro.kernels import backend as kernel_backend
from repro.obs import trace as obs_trace
from repro.obs.counters import modeled_floats_per_iter
from repro.core.solvers import (
    HIGHEST,
    kron_ridge_solve,
    mm,
    sum_sylvester_cg,
    sylvester_ridge_solve,
)


# --------------------------------------------------------------------------
# Sufficient statistics: the one producer
# --------------------------------------------------------------------------


class SufficientStats(NamedTuple):
    """Per-agent Gram statistics; leading axes (if any) index agents.

    ``n`` (samples folded in) and ``t2`` (sum of squared targets) make the
    primal objective computable from stats alone — the raw data never needs
    to be revisited (or moved between agents) after accumulation.
    """

    G: jax.Array            # (..., L, L)  H^T H
    R: jax.Array            # (..., L, d)  H^T T
    n: jax.Array | float = 0.0   # (...,) samples seen
    t2: jax.Array | float = 0.0  # (...,) sum T**2


def _gram_one(H: jax.Array, T: jax.Array, use_pallas: bool,
              precision: str = "fp32", quant_seed=0):
    if precision == "int8":
        # int8 always routes through the kernels package — the quantization
        # (per-tile scales + stochastic rounding) is part of the op; with
        # use_pallas=False the op's own jnp emulation runs instead of the
        # int8-streaming kernel.
        from repro.kernels.gram.ops import gram as gram_op

        return gram_op(H, T, precision="int8", force_ref=not use_pallas,
                       quant_seed=quant_seed)
    if use_pallas:
        from repro.kernels.gram.ops import gram as gram_op

        return gram_op(H, T, precision=precision)
    from repro.kernels.gram.ref import gram_ref

    if precision == "bf16":
        # jnp oracle path: emulate the bf16 tile stream by rounding the
        # operands to bf16 storage before the fp32 contraction (the kernel's
        # fp32 accumulator contributes nothing beyond this rounding).
        H = H.astype(jnp.bfloat16)
        T = T.astype(jnp.bfloat16)
    return gram_ref(H, T)


def sufficient_stats(
    H: jax.Array, T: jax.Array, use_pallas: bool | None = None,
    precision: str = "fp32", quant_seed=0,
) -> SufficientStats:
    """The MATERIALIZED stats producer. H: (N, L) or (m, N, L); T matches.

    Routes through the fused Pallas ``gram`` kernel when requested (one HBM
    pass for both products on TPU) and its jnp oracle otherwise.  A stacked
    (m, N, L) input on the Pallas path is ONE agent-batched triangular
    kernel launch (``gram_batched``) covering all m agents, not m vmapped
    launches.  ``precision="bf16"`` streams the feature/target tiles in
    bf16 with fp32 accumulation; ``precision="int8"`` streams per-tile-
    quantized 1-byte tiles (stochastic rounding over ``quant_seed``; see
    ``repro.kernels.gram.ops``); ``t2`` (a scalar diagnostics reduction)
    always stays fp32.  See :func:`sufficient_stats_fused` for the producer
    that never materializes H at all.  ``use_pallas=None`` resolves by
    backend (:func:`repro.kernels.backend.stats_use_pallas`).
    """
    use_pallas = kernel_backend.stats_use_pallas(use_pallas)
    if H.ndim == 2:
        G, R = _gram_one(H, T, use_pallas, precision, quant_seed)
        n = jnp.asarray(H.shape[0], jnp.float32)
    elif use_pallas or precision == "int8":
        from repro.kernels.gram.ops import gram_batched

        G, R = gram_batched(H, T, precision=precision,
                            force_ref=not use_pallas, quant_seed=quant_seed)
        n = jnp.full(H.shape[:-2], H.shape[-2], jnp.float32)
    else:
        G, R = jax.vmap(lambda h, t: _gram_one(h, t, False, precision))(H, T)
        n = jnp.full(H.shape[:-2], H.shape[-2], jnp.float32)
    t2 = jnp.sum(jnp.square(T.astype(jnp.float32)), axis=(-2, -1))
    return SufficientStats(G=G, R=R, n=n, t2=t2)


def sufficient_stats_fused(
    X: jax.Array, feature_map, T: jax.Array, use_pallas: bool | None = None,
    precision: str = "fp32",
) -> SufficientStats:
    """The FUSED stats producer: statistics straight from raw features.

    X: (N, d_in) or (m, N, d_in) raw (backbone) inputs; ``feature_map`` a
    frozen :class:`repro.core.elm.ELMFeatureMap` shared across agents; T
    matches X's leading shape.  The hidden layer ``H = act(X W + b)`` is
    computed INSIDE the Gram kernel (``gram_fused``) and never written to
    HBM at full precision — the O(N L) materialize write + re-read of the
    unfused pipeline disappears.  Equal to
    ``sufficient_stats(feature_map(X), T)`` in fp32 up to how XLA blocks
    the hidden-layer matmul (bitwise on H built from the kernel's tiles);
    ``precision="bf16"`` rounds the in-kernel hidden tiles like the
    materialized bf16 stream.  int8 is not offered fused (its maxabs
    scale pass needs a materialized H — use the unfused int8 stream).
    """
    from repro.kernels.gram.ops import gram_fused

    use_pallas = kernel_backend.stats_use_pallas(use_pallas)
    G, R = gram_fused(
        X, feature_map.W, feature_map.b, T,
        activation=feature_map.activation, precision=precision,
        force_ref=not use_pallas,
    )
    if X.ndim == 2:
        n = jnp.asarray(X.shape[0], jnp.float32)
    else:
        n = jnp.full(X.shape[:-2], X.shape[-2], jnp.float32)
    t2 = jnp.sum(jnp.square(T.astype(jnp.float32)), axis=(-2, -1))
    return SufficientStats(G=G, R=R, n=n, t2=t2)


STATS_PRODUCERS = ("materialized", "fused")


def produce_stats(
    batch: jax.Array, T: jax.Array, *, producer: str = "materialized",
    feature_map=None, use_pallas: bool | None = None,
    precision: str = "fp32", quant_seed=0, mesh=None, agent_axes=None,
) -> SufficientStats:
    """Dispatch ONE batch through the configured stats producer.

    ``producer="materialized"`` treats ``batch`` as the hidden features H;
    ``producer="fused"`` treats it as raw inputs X and needs
    ``feature_map=`` (the frozen ELM hidden layer, applied in-kernel).
    This is the single validation point for the
    ``cfg.stats_producer`` plumbing (``dmtl_elm.fit``,
    ``data.pipeline.stream_sufficient_stats``).

    ``mesh=`` / ``agent_axes=`` (the sharded executors' layout) run the
    producer per shard under ``shard_map``: each device reduces its own
    agents' rows and the stats come out placed by agent, so no device
    holds the whole (m, L, L) stack.
    """
    if producer not in STATS_PRODUCERS:
        raise ValueError(
            f"unknown stats producer {producer!r}; expected one of "
            f"{STATS_PRODUCERS}"
        )
    if producer == "fused":
        if feature_map is None:
            raise ValueError(
                "producer='fused' needs feature_map= (the frozen "
                "ELMFeatureMap whose hidden layer runs in-kernel)"
            )
        if precision == "int8":
            raise ValueError(
                "precision='int8' is the unfused (materialized) stream; "
                "the fused producer supports fp32/bf16"
            )
    elif feature_map is not None:
        raise ValueError(
            "feature_map= only applies to producer='fused', got "
            f"producer={producer!r}"
        )

    if (mesh is None) != (agent_axes is None):
        raise ValueError("mesh= and agent_axes= go together")

    def _produce(batch, T):
        if producer == "fused":
            return sufficient_stats_fused(batch, feature_map, T,
                                          use_pallas=use_pallas,
                                          precision=precision)
        return sufficient_stats(batch, T, use_pallas=use_pallas,
                                precision=precision, quant_seed=quant_seed)

    def _dispatch():
        if mesh is None:
            return _produce(batch, T)
        from jax.sharding import PartitionSpec as P

        spec = P(tuple(agent_axes))
        return jax.jit(jax.shard_map(
            _produce, mesh=mesh, check_vma=False, in_specs=(spec, spec),
            out_specs=SufficientStats(spec, spec, spec, spec),
        ))(batch, T)

    tr = obs_trace.current()
    if tr is None:
        return _dispatch()
    # span durations should reflect the stats pass itself, not dispatch
    # latency — block inside the span (tracing-on only)
    with tr.span("stats", producer=producer, precision=precision):
        out = _dispatch()
        jax.block_until_ready(out)
    return out


def init_stats(m: int, L: int, d: int, dtype=jnp.float32) -> SufficientStats:
    return SufficientStats(
        G=jnp.zeros((m, L, L), dtype),
        R=jnp.zeros((m, L, d), dtype),
        n=jnp.zeros((m,), dtype),
        t2=jnp.zeros((m,), dtype),
    )


def accumulate_stats(
    stats: SufficientStats, H: jax.Array, T: jax.Array,
    use_pallas: bool | None = None, precision: str = "fp32",
    producer: str = "materialized", feature_map=None, quant_seed=0,
) -> SufficientStats:
    """Fold one feature batch into running stats (streaming accumulation).

    ``producer="fused"`` (with ``feature_map=``) accepts raw-input batches
    and runs the hidden layer in-kernel — see :func:`produce_stats`."""
    b = produce_stats(H, T, producer=producer, feature_map=feature_map,
                      use_pallas=use_pallas, precision=precision,
                      quant_seed=quant_seed)
    return SufficientStats(
        G=stats.G + b.G, R=stats.R + b.R, n=stats.n + b.n, t2=stats.t2 + b.t2
    )


def _kahan_add(total: jax.Array, comp: jax.Array, delta: jax.Array):
    """One compensated-summation step: returns (new_total, new_comp) with
    the fp32 rounding error of ``total + delta`` carried in ``comp``."""
    y = delta - comp
    t = total + y
    return t, (t - total) - y


def accumulate_stats_chunked(
    stats: SufficientStats, H: jax.Array, T: jax.Array,
    chunk: int, use_pallas: bool | None = None, precision: str = "fp32",
    compensated: bool = False, producer: str = "materialized",
    feature_map=None, quant_seed=0,
) -> SufficientStats:
    """Fold a long batch in ``chunk``-row pieces (bounded peak memory).

    The scan walks the full chunks; a ragged tail is folded by one extra
    producer call on the true tail rows.  (Zero-padding the tail would be
    wrong for the fused producer: its hidden layer maps zero input rows to
    ``act(b) != 0``, which would pollute G.)  The sample count ``n`` uses
    the true batch size and — like every other leaf — comes out per-agent
    ``(m,)``, identical in shape and value to the one-shot
    :func:`accumulate_stats` path.

    ``compensated=True`` switches the chunk fold to Kahan summation: the
    fp32 accumulators carry a running compensation term, so the per-chunk
    rounding error stays O(eps) instead of growing O(k eps) with the chunk
    count — the natural companion of ``precision="bf16"`` streams, whose
    per-chunk contributions are already rounded and would otherwise lose
    their low bits against a large running total.

    ``producer="fused"`` (with ``feature_map=``) chunks raw-input rows the
    same way — the hidden layer runs in-kernel per chunk.  int8 chunks
    fold with per-chunk rounding seeds (``quant_seed + chunk index``) so
    chunk errors stay independent.
    """
    m, B = H.shape[0], H.shape[1]
    k = B // chunk
    tail = B - k * chunk
    # (k, m, chunk, ...) so the scan walks the full chunks
    Hc = H[:, :k * chunk].reshape(m, k, chunk, H.shape[-1]).swapaxes(0, 1)
    Tc = T[:, :k * chunk].reshape(m, k, chunk, T.shape[-1]).swapaxes(0, 1)
    seeds = jnp.asarray(quant_seed, jnp.int32) + jnp.arange(k, dtype=jnp.int32)
    tail_seed = jnp.asarray(quant_seed, jnp.int32) + k

    def chunk_stats(h, t, seed):
        return produce_stats(h, t, producer=producer,
                             feature_map=feature_map,
                             use_pallas=use_pallas, precision=precision,
                             quant_seed=seed)
    # scalar n/t2 (the (G, R)-only construction) must be broadcast to the
    # per-agent shape the fold produces, or the scan carry types mismatch
    # (and downstream consumers would see a scalar n from the chunked path
    # but an (m,) n from the one-shot path)
    n_0 = jnp.broadcast_to(jnp.asarray(stats.n, jnp.float32), (m,))
    t2_0 = jnp.broadcast_to(jnp.asarray(stats.t2, jnp.float32), (m,))

    if compensated:
        zeros = (jnp.zeros_like(stats.G), jnp.zeros_like(stats.R),
                 jnp.zeros_like(t2_0))

        def fold_kahan(carry, hts):
            (G, cG), (R, cR), (t2, ct2) = carry
            h, t, seed = hts
            b = chunk_stats(h, t, seed)
            return (_kahan_add(G, cG, b.G), _kahan_add(R, cR, b.R),
                    _kahan_add(t2, ct2, b.t2)), None

        ((G, cG), (R, cR), (t2, ct2)), _ = jax.lax.scan(
            fold_kahan,
            ((stats.G, zeros[0]), (stats.R, zeros[1]), (t2_0, zeros[2])),
            (Hc, Tc, seeds),
        )
        if tail:
            b = chunk_stats(H[:, k * chunk:], T[:, k * chunk:], tail_seed)
            (G, _), (R, _), (t2, _) = (
                _kahan_add(G, cG, b.G), _kahan_add(R, cR, b.R),
                _kahan_add(t2, ct2, b.t2))
        return SufficientStats(G=G, R=R, n=n_0 + B, t2=t2)

    def fold(carry, hts):
        h, t, seed = hts
        b = chunk_stats(h, t, seed)
        return (carry[0] + b.G, carry[1] + b.R, carry[2] + b.t2), None

    (G, R, t2), _ = jax.lax.scan(fold, (stats.G, stats.R, t2_0),
                                 (Hc, Tc, seeds))
    if tail:
        b = chunk_stats(H[:, k * chunk:], T[:, k * chunk:], tail_seed)
        G, R, t2 = G + b.G, R + b.R, t2 + b.t2
    return SufficientStats(G=G, R=R, n=n_0 + B, t2=t2)


# --------------------------------------------------------------------------
# Objectives from stats alone
# --------------------------------------------------------------------------


def fit_error_from_stats(
    stats: SufficientStats, U: jax.Array, A: jax.Array
) -> jax.Array:
    """sum_t 0.5 ||H_t U_t A_t - T_t||^2 computed from (G, R, t2) only:

        ||H U A - T||^2 = tr(A^T U^T G U A) - 2 tr(A^T U^T R) + ||T||^2.

    U: (m, L, r) per-agent or (L, r) shared (broadcast against agents).
    """
    if U.ndim == 2:
        U = jnp.broadcast_to(U, (A.shape[0],) + U.shape)
    UtGU = jnp.einsum("mlr,mlk,mks->mrs", U, stats.G, U, precision=HIGHEST)
    quad = jnp.einsum("mrs,msd,mrd->", UtGU, A, A, precision=HIGHEST)
    cross = jnp.einsum("mlr,mld,mrd->", U, stats.R, A, precision=HIGHEST)
    t2 = jnp.sum(jnp.asarray(stats.t2, jnp.float32))
    return 0.5 * (quad - 2.0 * cross + t2)


def objective_from_stats(
    stats: SufficientStats, U: jax.Array, A: jax.Array,
    mu1: float, mu2: float, shared_u: bool = False,
) -> jax.Array:
    """Primal objective: eq. (12) for per-agent U (mu1/(2m) ||U||^2), or
    eq. (6) for a shared U (mu1/2 ||U||^2) with ``shared_u=True``."""
    m = A.shape[0]
    u_reg = mu1 if shared_u else mu1 / m
    return (
        fit_error_from_stats(stats, U, A)
        + 0.5 * u_reg * jnp.sum(U**2)
        + 0.5 * mu2 * jnp.sum(A**2)
    )


# --------------------------------------------------------------------------
# Config + solver registry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    """Shared configuration of the DMTL-ELM / FO-DMTL-ELM family."""

    r: int
    mu1: float = 2.0
    mu2: float = 2.0
    rho: float = 1.0
    delta: float = 10.0
    # tau_t / zeta_t: proximal weights; paper uses tau_t = const + d_t.
    tau: float = 2.0             # scalar -> tau_t = tau + d_t (or per-agent array)
    zeta: float = 1.0
    iters: int = 100
    prox: str = "prox_linear"    # P_t = tau_t I - rho C_t^T C_t | "standard": tau_t I
    u_solver: str = "sylvester"  # U_SOLVERS key: "kron" | "sylvester" | "cg" | "pcg"
    # Gram-pass precision for entry points that reduce raw (H, T) to stats:
    # "bf16" streams feature/target tiles in bf16 with fp32 accumulators
    # (half the stats HBM read traffic; see benchmarks/convergence.
    # run_precision for the measured ADMM convergence impact); "int8"
    # streams per-tile-quantized 1-byte tiles with stochastic rounding
    # (half of bf16 again; unfused path only).
    stats_precision: str = "fp32"
    # Stats producer for entry points that reduce raw data to stats:
    # "materialized" computes H = g(X W + b) in XLA and reduces it with the
    # triangular kernel on a TPU (the jnp oracle on the CPU — the parity
    # oracle, see ``repro.kernels.backend``); "fused" computes the
    # hidden layer INSIDE the Gram kernel from raw inputs (needs a
    # feature_map= at the call site), so H never hits HBM — see
    # ``produce_stats`` / ``sufficient_stats_fused``.
    stats_producer: str = "materialized"
    first_order: bool = False    # FO-DMTL-ELM (Algorithm 3)
    gamma_cap: float = 1.0       # gamma = min(cap, delta * dual/primal) as in §IV
    # Lower bound on the adaptive gamma (0 = the paper's rule untouched).
    # The §IV heuristic shrinks gamma with the ITERATE movement, which is
    # tuned to Jacobian dynamics: Gauss-Seidel sweeps (fit_colored) reach
    # the frozen-dual fixed point much faster, so gamma can collapse while
    # the consensus residual is still large, freezing the duals.  A small
    # floor (e.g. 0.05) keeps the dual ascent alive for those executors.
    gamma_floor: float = 0.0
    # Neighbor-aggregation rule for the consensus reduction (AGGREGATORS
    # key): "mean" is the paper's plain sum-of-neighbors (every executor's
    # pre-existing segment-sum / ppermute-sum path, bitwise untouched — the
    # parity oracle); the robust rules ("trimmed_mean",
    # "coordinate_median", "krum_like") replace the mean of received
    # subspaces with a Byzantine-resilient center over the received views
    # PLUS the receiver's own U (self-inclusion keeps degree-<=2 reductions
    # meaningful), scaled back by the live degree so ``agent_update`` is
    # untouched.  Mask-aware: departed/absent neighbors are excluded from
    # the candidate set rather than averaged in as zeros.
    aggregator: str = "mean"
    # Device-side telemetry counters (the observability layer, repro.obs):
    # False (default) keeps every executor's diag dict and traced
    # computation EXACTLY as before — the gate is a Python-level branch at
    # trace time, so the sha256 golden paths are byte-identical.  True
    # extends the per-iteration diagnostics with the comm/aggregator
    # counters documented in ``_iteration_diag``.
    telemetry: bool = False


def _u_solve_kron(G, M, rhs, c, precomp=None):
    return kron_ridge_solve(G, M, rhs, c)


def _u_solve_sylvester(G, M, rhs, c, precomp=None):
    """Solve G U M + c U = R by double eigendecomposition, O(L^3 + r^3).

    ``precomp`` is an optional hoisted eigh(G): since G is iteration-
    invariant, executors compute it once outside the ADMM scan and each
    iteration costs only O(L^2 r + r^3).
    """
    return sylvester_ridge_solve(G, M, rhs, c, eig_g=precomp)


def _u_solve_cg(G, M, rhs, c, precomp=None):
    return sum_sylvester_cg(G, M, rhs, c)


def _u_solve_pcg(G, M, rhs, c, precomp=None):
    """Gram-diagonal (Jacobi) preconditioned CG: divides the eigen-spread
    of diag(G) out of the operator, so iteration counts track the
    *off-diagonal* conditioning only — the backbone-scale (L = d_model)
    solve where even one O(L^3) eigh per agent is undesirable."""
    return sum_sylvester_cg(G, M, rhs, c, precond="jacobi")


U_SOLVERS: dict[str, Callable] = {
    "kron": _u_solve_kron,
    "sylvester": _u_solve_sylvester,
    "cg": _u_solve_cg,
    "pcg": _u_solve_pcg,
}


def register_u_solver(name: str, fn: Callable) -> None:
    """Extension point: fn(G, M, rhs, c, precomp) solving G U M + c U = rhs."""
    U_SOLVERS[name] = fn


def hoist_precomp(stats: SufficientStats, cfg: ConsensusConfig):
    """Iteration-invariant precomputation for the configured U-solver
    (eigh(G) for ``sylvester``; batched over any leading agent axes)."""
    if _hoists_eigh(cfg):
        return jnp.linalg.eigh(stats.G)
    return None


def _hoists_eigh(cfg: ConsensusConfig) -> bool:
    return cfg.u_solver == "sylvester" and not cfg.first_order


# --------------------------------------------------------------------------
# The one per-agent ADMM round
# --------------------------------------------------------------------------


class AgentState(NamedTuple):
    U: jax.Array    # (L, r) local subspace        [per agent: no leading axis]
    A: jax.Array    # (r, d) local head
    lam: jax.Array  # (E_own, L, r) duals of the edges this agent owns


class NeighborMsgs(NamedTuple):
    """Everything the topology delivered to one agent this round."""

    neigh_sum: jax.Array  # (L, r)  sum_{j in N(t)} U_j^k
    ct_lam: jax.Array     # (L, r)  C_t^T lambda^k
    deg: jax.Array        # ()      degree d_t
    tau: jax.Array        # ()      resolved proximal weight tau_t
    zeta: jax.Array       # ()      resolved proximal weight zeta_t


def agent_update(
    stats: SufficientStats,
    state: AgentState,
    msgs: NeighborMsgs,
    cfg: ConsensusConfig,
    *,
    m_total: int,
    precomp=None,
) -> tuple[jax.Array, jax.Array]:
    """ONE agent's ADMM round (Gauss-Seidel U then A; paper eqs. 19/23, 21).

    Pure: all cross-agent information arrives pre-gathered in ``msgs``; the
    executors decide whether that gathering is a dense incidence einsum
    (vmap) or a ring ppermute (shard_map).  Returns (U_new, A_new); the
    edge-dual update is :func:`dual_step`, applied by the executor once it
    has exchanged the fresh U.
    """
    U, A = state.U, state.A
    rho, mu1 = cfg.rho, cfg.mu1
    p_t = msgs.tau - rho * msgs.deg if cfg.prox == "prox_linear" else msgs.tau

    M = mm(A, A.T)                                         # (r, r)
    rhs = mm(stats.R, A.T) + rho * msgs.neigh_sum - msgs.ct_lam + p_t * U
    if cfg.first_order:
        # eq. (23): prox-linear collapses the solve to a scaled gradient step
        grad_f = mm(mm(stats.G, U), M)
        U_new = (rhs - grad_f - (mu1 / m_total) * U) / (rho * msgs.deg + p_t)
    else:
        if cfg.u_solver not in U_SOLVERS:
            raise ValueError(
                f"unknown u_solver {cfg.u_solver!r}; registered: "
                f"{sorted(U_SOLVERS)}"
            )
        c_t = mu1 / m_total + rho * msgs.deg + p_t
        U_new = U_SOLVERS[cfg.u_solver](stats.G, M, rhs, c_t, precomp)

    # A update (eq. 21), purely local, on the fresh U
    Ga = mm(mm(U_new.T, stats.G), U_new)
    Ga = Ga + (msgs.zeta + cfg.mu2) * jnp.eye(cfg.r, dtype=U.dtype)
    A_new = jnp.linalg.solve(Ga, mm(U_new.T, stats.R) + msgs.zeta * A)
    return U_new, A_new


def dual_step(
    lam: jax.Array, resid_old: jax.Array, resid_new: jax.Array,
    cfg: ConsensusConfig,
):
    """Adaptive dual ascent on edge residuals (eq. 16 + the Lemma 2 / §IV
    gamma choice).  Works for any leading edge layout — (E, L, r) dense or
    (L, r) per owned edge — summing over the trailing (L, r) axes.

    resid_old/new are C U^k and C U^{k+1} per edge.  Returns
    (lam_new, gamma, primal_sq).
    """
    dual = jnp.sum((resid_old - resid_new) ** 2, axis=(-2, -1))
    primal = jnp.sum(resid_new**2, axis=(-2, -1))
    gamma = jnp.minimum(
        cfg.gamma_cap, cfg.delta * dual / jnp.maximum(primal, 1e-12)
    )
    gamma = jnp.maximum(gamma, cfg.gamma_floor)   # 0.0 = paper rule as-is
    gamma = jnp.where(primal <= 1e-12, cfg.gamma_cap, gamma)
    return lam + cfg.rho * gamma[..., None, None] * resid_new, gamma, primal


def _resolve_tau_zeta(cfg: ConsensusConfig, deg: jax.Array, m: int, dtype):
    tau = jnp.asarray(cfg.tau, dtype=dtype)
    tau_t = tau + deg if tau.ndim == 0 else tau
    zeta_t = jnp.broadcast_to(jnp.asarray(cfg.zeta, dtype=dtype), (m,))
    return tau_t, zeta_t


# --------------------------------------------------------------------------
# Robust neighbor aggregation (Byzantine resilience, ROADMAP item 4a)
# --------------------------------------------------------------------------
#
# An aggregator replaces the plain mean of the views an agent received with
# a Byzantine-resilient center.  Signature: ``fn(V, M) -> center`` where
# ``V`` is ``(..., K, L, r)`` candidate views stacked on axis -3 and ``M``
# is a ``(..., K)`` {0, 1} validity mask (dropped / departed / padded
# candidates carry 0 and are EXCLUDED, never averaged in as zeros).  The
# executors always append the receiver's OWN current U as one candidate —
# on degree-2 rings a median over two foreign views alone is meaningless —
# and rescale the center by the live degree so ``agent_update``'s
# ``rho * neigh_sum`` term (and hence the solver body) is untouched:
# ``neigh_sum = deg_eff * center``.  ``"mean"`` deliberately maps to None:
# executors keep their pre-existing segment-sum / ppermute-sum code paths
# verbatim, which is the bitwise parity oracle for this knob.
#
# All three robust rules are candidate-ORDER-invariant (sorting per
# coordinate, or an order-free score), so executors that assemble the
# candidate axis in different orders (edge-list gather vs ppermute rounds)
# still agree to float tolerance.


def _sorted_candidates(V: jax.Array, M: jax.Array) -> jax.Array:
    """(..., K, L, r) + mask -> per-coordinate ascending sort (..., L, r, K)
    with invalid candidates pushed to the top via a +huge sentinel."""
    Vk = jnp.moveaxis(V, -3, -1)                       # (..., L, r, K)
    Mk = M[..., None, None, :]                         # (..., 1, 1, K)
    big = jnp.asarray(jnp.finfo(V.dtype).max, V.dtype)
    return jnp.sort(jnp.where(Mk > 0, Vk, big), axis=-1)


def _agg_trimmed_mean(V: jax.Array, M: jax.Array) -> jax.Array:
    """Coordinate-wise trimmed mean: drop the single smallest and largest
    VALID value per coordinate (only when >= 3 candidates are valid, else
    plain masked mean), average the rest."""
    Vs = _sorted_candidates(V, M)                      # (..., L, r, K)
    de = jnp.sum(M, axis=-1)[..., None, None, None]    # (..., 1, 1, 1)
    b = jnp.where(de >= 3.0, 1.0, 0.0)
    pos = jnp.arange(V.shape[-3], dtype=V.dtype)       # (K,)
    w = (pos >= b) & (pos < de - b)                    # (..., 1, 1, K)
    kept = jnp.where(w, Vs, 0.0)          # where (not *) — sentinel*0 = nan
    cnt = jnp.maximum(de - 2.0 * b, 1.0)
    return jnp.sum(kept, axis=-1) / cnt[..., 0]


def _agg_coordinate_median(V: jax.Array, M: jax.Array) -> jax.Array:
    """Coordinate-wise median over the valid candidates (midpoint of the
    two central order statistics when the valid count is even)."""
    Vs = _sorted_candidates(V, M)                      # (..., L, r, K)
    n = jnp.maximum(jnp.sum(M, axis=-1).astype(jnp.int32), 1)
    lo = jnp.broadcast_to(
        ((n - 1) // 2)[..., None, None, None], Vs.shape[:-1] + (1,)
    )
    hi = jnp.broadcast_to((n // 2)[..., None, None, None], lo.shape)
    vlo = jnp.take_along_axis(Vs, lo, axis=-1)[..., 0]
    vhi = jnp.take_along_axis(Vs, hi, axis=-1)[..., 0]
    return 0.5 * (vlo + vhi)


def _agg_krum_like(V: jax.Array, M: jax.Array) -> jax.Array:
    """Krum-flavored medoid: pick the single valid candidate minimizing the
    summed squared distance to all valid candidates.  Unlike the
    coordinate-wise rules the center is one agent's ACTUAL subspace, which
    matters when coordinate mixing would leave the consensus manifold."""
    Vf = V.reshape(V.shape[:-2] + (-1,))               # (..., K, L*r)
    D = jnp.sum((Vf[..., :, None, :] - Vf[..., None, :, :]) ** 2, axis=-1)
    score = jnp.sum(M[..., None, :] * D, axis=-1)      # (..., K)
    big = jnp.asarray(jnp.finfo(V.dtype).max, V.dtype)
    idx = jnp.argmin(jnp.where(M > 0, score, big), axis=-1)
    idx_b = jnp.broadcast_to(
        idx[..., None, None, None], V.shape[:-3] + (1,) + V.shape[-2:]
    )
    return jnp.take_along_axis(V, idx_b, axis=-3)[..., 0, :, :]


AGGREGATORS: dict[str, Callable | None] = {
    "mean": None,                # sentinel: executors keep their plain-sum path
    "trimmed_mean": _agg_trimmed_mean,
    "coordinate_median": _agg_coordinate_median,
    "krum_like": _agg_krum_like,
}


def register_aggregator(name: str, fn: Callable) -> None:
    """Extension point: fn(V, M) -> center over the (..., K, L, r) candidate
    axis with a (..., K) {0, 1} validity mask (see AGGREGATORS notes)."""
    AGGREGATORS[name] = fn


def resolve_aggregator(cfg: ConsensusConfig) -> Callable | None:
    """cfg.aggregator -> the aggregation fn, or None for the plain mean."""
    if cfg.aggregator not in AGGREGATORS:
        raise ValueError(
            f"unknown aggregator {cfg.aggregator!r}; registered: "
            f"{sorted(AGGREGATORS)}"
        )
    return AGGREGATORS[cfg.aggregator]


def neighbor_table(g: Graph):
    """Host-side padded adjacency table: (nbr_idx, nbr_mask) numpy arrays of
    shape (m, K_max) — the gather layout the robust aggregators consume.
    (Lives in ``repro.core.exchange``; re-exported here for compat.)"""
    return exchange.neighbor_table(g)


# --------------------------------------------------------------------------
# Shared edge-list machinery of the single-program executors (1 and 3)
# --------------------------------------------------------------------------


class _EdgeSetup(NamedTuple):
    """Everything fit_dense / fit_colored share: normalized stats, resolved
    proximal weights, the hoisted precomp, edge-list gather closures, the
    vmapped ``agent_update`` body, and the all-ones initial state.  One
    construction site keeps the executors' numerics identical by code, not
    by convention."""

    stats: SufficientStats
    deg: jax.Array
    tau_t: jax.Array
    zeta_t: jax.Array
    precomp: object
    edge_diff: Callable
    neighbor_sum: Callable
    ct_transpose: Callable
    body: Callable
    init: "DenseState"
    ex: "exchange.DenseExchange"


def _edge_setup(
    stats: SufficientStats, g: Graph, cfg: ConsensusConfig
) -> _EdgeSetup:
    m, L = stats.G.shape[0], stats.G.shape[-1]
    d = stats.R.shape[-1]
    dtype = stats.G.dtype
    # normalize scalar n/t2 (e.g. from the raw-Gram compatibility path) so
    # every stats leaf carries the agent axis the body is vmapped over
    stats = SufficientStats(
        G=stats.G,
        R=stats.R,
        n=jnp.broadcast_to(jnp.asarray(stats.n, jnp.float32), (m,)),
        t2=jnp.broadcast_to(jnp.asarray(stats.t2, jnp.float32), (m,)),
    )
    # The dense exchange backend owns the edge-list message gathering
    # (O(E L r) segment sums on the mean path, the padded candidate gather
    # + cfg.aggregator on the robust path) — see repro.core.exchange.
    ex = exchange.DenseExchange(g, dtype, resolve_aggregator(cfg))
    deg = ex.deg                                       # (m,)
    tau_t, zeta_t = _resolve_tau_zeta(cfg, deg, m, dtype)
    precomp = hoist_precomp(stats, cfg)                # batched eigh or None
    edge_diff = ex.edge_diff
    neighbor_sum = ex.neighbor_sum
    ct_transpose = ex.ct_transpose

    def one_agent(stats_t, state_t, msgs_t, precomp_t):
        return agent_update(
            stats_t, state_t, msgs_t, cfg, m_total=m, precomp=precomp_t
        )

    body = jax.vmap(
        one_agent,
        in_axes=(
            0,
            AgentState(0, 0, None),
            0,
            None if precomp is None else 0,
        ),
    )

    init = DenseState(
        U=jnp.ones((m, L, cfg.r), dtype=dtype),
        A=jnp.ones((m, cfg.r, d), dtype=dtype),
        lam=jnp.zeros((g.n_edges, L, cfg.r), dtype=dtype),
    )
    return _EdgeSetup(
        stats, deg, tau_t, zeta_t, precomp,
        edge_diff, neighbor_sum, ct_transpose, body, init, ex,
    )


def _iteration_diag(stats, cfg, U, A, lam_new, resid_new, gamma, primal) -> dict:
    """The per-iteration diagnostics EVERY executor reports (the shared
    contract, asserted by the cross-executor diagnostics-parity test):

      objective   primal objective (eq. 12), from stats alone
      lagrangian  augmented Lagrangian (eq. 13)
      consensus   RMS edge disagreement sqrt(mean (C U)^2)
      gamma       mean adaptive dual step size over edges (§IV rule) — the
                  observable for tuning ``cfg.gamma_floor``
      gamma_min   min over edges (the first gamma to collapse)
      primal_sq   sum of squared edge residuals (consensus, unnormalized)

    ``gamma``/``primal`` are the per-edge (E,) outputs of :func:`dual_step`.

    ``cfg.telemetry=True`` extends the dict with the observability keys
    (module docstring "Telemetry extension"): this helper contributes
    ``resid_max`` (the max-abs edge residual — the worst single consensus
    violation, vs ``consensus``'s RMS); the message counters
    (``msgs_delivered`` / ``msgs_stale`` / ``msgs_dropped``), the
    ``agg_rejected`` aggregator audit, and the analytic ``comm_floats``
    model are schedule-specific and added by each executor.  Gating is a
    Python-level branch at trace time: with telemetry off the returned
    dict is byte-identical to the pre-telemetry contract (the
    zero-overhead guarantee the golden sha256 battery pins).
    """
    obj = objective_from_stats(stats, U, A, cfg.mu1, cfg.mu2)
    diag = {
        "objective": obj,
        "lagrangian": obj
        + jnp.sum(lam_new * resid_new)
        + 0.5 * cfg.rho * jnp.sum(resid_new**2),
        "consensus": jnp.sqrt(jnp.mean(resid_new**2)),
        "gamma": jnp.mean(gamma),
        "gamma_min": jnp.min(gamma),
        "primal_sq": jnp.sum(primal),
    }
    if cfg.telemetry:
        diag["resid_max"] = jnp.max(jnp.abs(resid_new))
    return diag


# --------------------------------------------------------------------------
# The ONE serializable run state + the segmented step core
# --------------------------------------------------------------------------


class RunState(NamedTuple):
    """The ONE serializable mid-run state every executor advances.

    A plain pytree of arrays — everything a preempted consensus run needs
    to restart bitwise-identically mid-scan.  ``None`` leaves (ring buffers
    an executor does not use) drop out of the flattened tree, so a
    checkpoint written by one executor round-trips through
    ``repro.checkpoint`` against that executor's own template.

    Per-executor leaf layouts (m agents, E edges, depth = tape.depth):

      dense / southwell   lam (E, L, r); hist = lam_hist = None
      colored             hist (staleness, m, L, r) — the delayed-view
                          window (zero-depth when staleness=0)
      async               hist (depth, m, L, r) published-U ring buffer;
                          lam_hist (depth, E, L, r) iff aged_duals; ``k``
                          doubles as the tape cursor
      sharded (ring)      lam (m, n_axes, L, r), agent-sharded; the
                          per-shard block is ring_iteration's (n_axes,L,r)
      sharded_graph       lam (m, n_slots, L, r), agent-sharded slot table
      sharded_graph+tape  additionally hist (m, depth, L, r) — each
                          shard's ring buffer of its OWN published U,
                          agent-sharded on the LEADING axis (the mesh
                          axes), depth slots of (L, r); slot ``k % depth``
                          holds the U published at the end of tick ``k``,
                          pre-history slots hold U^0 (all-ones).  With
                          aged_duals also lam_hist (m, depth, n_slots, L,
                          r): the per-slot dual table as it stood AFTER
                          tick ``k``'s dual step, same slot rule.  Note
                          the axis order differs from the async layouts
                          above — agents lead (shard_map partitions axis
                          0), depth is second; both serialize through the
                          same generic npz round-trip.
    """

    U: jax.Array                  # (m, L, r) stacked subspaces
    A: jax.Array                  # (m, r, d) stacked heads
    lam: jax.Array                # per-edge duals, executor layout (above)
    k: jax.Array                  # ()  int32 iteration counter / tape cursor
    hist: jax.Array | None = None      # published-U / staleness ring buffer
    lam_hist: jax.Array | None = None  # aged-duals ring buffer (tape paths)


@dataclasses.dataclass(frozen=True)
class Runner:
    """A segmented executor: ``init_state()`` + ``run_segment(state, n)``.

    Every ``fit_*`` is one of these driven to completion.  The maker
    functions guarantee the segment property: the traced computation of
    ``run_segment(state, a); run_segment(·, b)`` is the SAME scan body as
    ``run_segment(state, a + b)`` with identical carries, so any segment
    split — including a serialize/deserialize through ``repro.checkpoint``
    at the boundary — reproduces the uninterrupted run bit for bit.
    """

    executor: str                 # "dense" | "colored" | "async" | ...
    cfg: ConsensusConfig
    init_fn: Callable[[], "RunState"]
    segment_fn: Callable[["RunState", int], tuple["RunState", dict]]
    shardings_fn: Callable[[], "RunState"] | None = None

    def init_state(self) -> "RunState":
        """The k=0 state (all-ones U/A, zero duals, pristine ring buffers)."""
        return self.init_fn()

    def state_shardings(self):
        """NamedSharding tree matching :class:`RunState` for the shard_map
        executors (checkpoint restore places leaves back onto the mesh);
        ``None`` for the single-device executors."""
        return None if self.shardings_fn is None else self.shardings_fn()

    def run_segment(self, state: "RunState", n_iters: int):
        """Advance ``n_iters`` iterations: ``(state, diags)`` with one
        diagnostics row per iteration of THIS segment."""
        n = int(n_iters)
        if n < 0:
            raise ValueError(f"n_iters must be >= 0, got {n_iters}")
        done = int(jax.device_get(state.k))
        if done + n > self.cfg.iters:
            raise ValueError(
                f"segment [{done}, {done + n}) runs past cfg.iters="
                f"{self.cfg.iters}"
            )
        tr = obs_trace.current()
        if tr is None:
            return self.segment_fn(state, n)
        # span durations should reflect device completion, not dispatch —
        # block inside the span (tracing-on only)
        with tr.span("segment", executor=self.executor, start=done, iters=n):
            out = self.segment_fn(state, n)
            jax.block_until_ready(out)
        return out

    def run(self, state: "RunState | None" = None):
        """Drive to ``cfg.iters`` from ``state`` (or a fresh init_state)."""
        if state is None:
            state = self.init_state()
        done = int(jax.device_get(state.k))
        if done > self.cfg.iters:
            raise ValueError(
                f"state is at iteration {done}, past cfg.iters="
                f"{self.cfg.iters}"
            )
        return self.run_segment(state, self.cfg.iters - done)


# --------------------------------------------------------------------------
# Executor 1: vmap + dense incidence (reference; all agents on one device)
# --------------------------------------------------------------------------


def _make_dense_runner(
    stats: SufficientStats, g: Graph, cfg: ConsensusConfig,
) -> Runner:
    es = _edge_setup(stats, g, cfg)
    stats = es.stats

    def step(state, _):
        U, A, lam = state
        neigh = es.neighbor_sum(U)                     # sum of neighbor U^k
        ct_lam = es.ct_transpose(lam)                  # C_t^T lambda^k
        msgs = NeighborMsgs(neigh, ct_lam, es.deg, es.tau_t, es.zeta_t)
        U_new, A_new = es.body(stats, AgentState(U, A, None), msgs, es.precomp)
        resid_old = es.edge_diff(U)
        resid_new = es.edge_diff(U_new)
        lam_new, gamma, primal = dual_step(lam, resid_old, resid_new, cfg)
        diag = _iteration_diag(
            stats, cfg, U_new, A_new, lam_new, resid_new, gamma, primal
        )
        if cfg.telemetry:
            dtype = U.dtype
            # synchronous Jacobian delivery: both endpoints of every edge
            # receive the fresh U each iteration; nothing is stale/dropped
            diag["msgs_delivered"] = jnp.asarray(2.0 * g.n_edges, dtype)
            diag["msgs_stale"] = jnp.zeros((), dtype)
            diag["msgs_dropped"] = jnp.zeros((), dtype)
            diag["agg_rejected"] = (
                es.ex.audit(U) if es.ex.agg is not None
                else jnp.zeros((), dtype)
            )
        return DenseState(U_new, A_new, lam_new), diag

    def init_fn():
        return RunState(
            U=es.init.U, A=es.init.A, lam=es.init.lam,
            k=jnp.zeros((), jnp.int32),
        )

    def segment_fn(state, n):
        # the scan carry is exactly the monolithic executor's DenseState —
        # the counter advances OUTSIDE the scan, so a segment boundary
        # cannot perturb the traced computation
        final, diags = jax.lax.scan(
            step, DenseState(state.U, state.A, state.lam), None, length=n
        )
        if cfg.telemetry:
            model = modeled_floats_per_iter(
                "dense", L=stats.G.shape[-1], r=cfg.r, n_edges=g.n_edges
            )
            diags["comm_floats"] = jnp.full((n,), float(model), stats.G.dtype)
        return state._replace(
            U=final.U, A=final.A, lam=final.lam, k=state.k + n
        ), diags

    return Runner("dense", cfg, init_fn, segment_fn)


def fit_dense(
    stats: SufficientStats, g: Graph, cfg: ConsensusConfig,
) -> tuple["DenseState", dict]:
    """Run Algorithm 2 (or 3 if cfg.first_order) over stats on graph ``g``.

    Neighbor messages are dense adjacency/incidence products; the shared
    :func:`agent_update` body is vmapped over the agent axis.  Returns the
    final stacked state and per-iteration diagnostics ('objective',
    'lagrangian', 'consensus') — all computed from stats alone.  One
    ``run_segment`` of :func:`make_runner`'s dense :class:`Runner`, driven
    to completion.
    """
    state, diags = _make_dense_runner(stats, g, cfg).run()
    return DenseState(state.U, state.A, state.lam), diags


class DenseState(NamedTuple):
    """Stacked executor state: all agents on the leading axis."""

    U: jax.Array    # (m, L, r)
    A: jax.Array    # (m, r, d)
    lam: jax.Array  # (E, L, r)


# --------------------------------------------------------------------------
# Executor 3: colored Gauss-Seidel sweeps (sequential color phases)
# --------------------------------------------------------------------------


def jacobian_schedule(m: int) -> tuple[tuple[int, ...], ...]:
    """The single-phase schedule: every agent in one class.  Running
    :func:`fit_colored` with it reproduces the Jacobian sweep of
    :func:`fit_dense` exactly — the executor-parity oracle."""
    return (tuple(range(m)),)


def _validate_schedule(schedule, m: int) -> None:
    seen: set[int] = set()
    for cls in schedule:
        for t in cls:
            if not 0 <= t < m:
                raise ValueError(f"schedule agent {t} out of range for m={m}")
            if t in seen:
                raise ValueError(f"agent {t} appears twice in schedule")
            seen.add(t)
    if len(seen) != m:
        raise ValueError(
            f"schedule covers {len(seen)} of {m} agents; classes must "
            f"partition the agent set"
        )


def fit_colored(
    stats: SufficientStats,
    g: Graph,
    cfg: ConsensusConfig,
    *,
    schedule: Sequence[Sequence[int]] | None = None,
    staleness: int = 0,
    order: str = "fixed",
) -> tuple[DenseState, dict]:
    """Gauss-Seidel / colored-sweep executor around the same ``agent_update``.

    The paper's scheme is Jacobian across agents: every agent updates from
    its neighbors' *previous*-iteration subspaces.  This executor instead
    sweeps the agents one color class at a time (``schedule`` defaults to
    :meth:`Graph.chromatic_schedule`, a greedy proper coloring), re-gathering
    ``neigh_sum`` / ``ct_lam`` from the live ``U`` between phases — so later
    classes see the *current*-iteration subspaces of earlier classes, the
    classic Gauss-Seidel acceleration.  The per-agent round body is the ONE
    shared :func:`agent_update`; only the message schedule differs.

    ``staleness`` models asynchronous execution by delaying neighbor
    messages:

      * ``staleness=0`` (default): pure Gauss-Seidel — each phase gathers
        from the live, freshest ``U``.
      * ``staleness=k >= 1``: every phase of iteration ``i`` gathers from
        the ``U`` snapshot published at the end of iteration ``i - k``
        (the initial ``U^0`` while ``i < k``).  In particular
        ``staleness=1`` delivers exactly the previous iterate to every
        phase, which reproduces the synchronous Jacobian sweep of
        :func:`fit_dense` for ANY schedule — the second parity oracle.
        Larger ``k`` emulates k-round-late messages on a slow interconnect.

    One ADMM iteration = all color phases + one shared :func:`dual_step` on
    the edge duals (duals are per-iteration, exactly as in ``fit_dense``, so
    the single-class schedule is bit-for-bit the Jacobian path).

    ``order`` picks the sweep order of the color classes:

      * ``order="fixed"`` (default): classes run in schedule order every
        iteration — bitwise the pre-existing behavior.
      * ``order="gauss_southwell"``: classes are reordered EVERY iteration
        by their primal residual (the summed squared consensus violation
        of each class's incident edges, largest first) — the classic
        Gauss-Southwell largest-violation-first sweep.  Requires
        ``staleness=0`` (with frozen views the phases are independent and
        order cannot matter).  The order is data-dependent, so this path
        pads classes to a common width and gathers with traced indices to
        stay inside one ``jax.lax.scan``; per-iteration gather work is
        O(c·E) instead of the fixed path's O(E).

    Because the sweep solves the frozen-dual subproblem faster than the
    Jacobian iteration, the paper's §IV adaptive gamma (which shrinks with
    iterate movement) can collapse before consensus is enforced; set
    ``cfg.gamma_floor`` (e.g. 0.05) to keep the dual ascent alive on
    long-horizon Gauss-Seidel runs.

    Returns the same ``(DenseState, diagnostics)`` contract as
    :func:`fit_dense` ('objective', 'lagrangian', 'consensus').
    """
    runner = _colored_runner(
        stats, g, cfg, schedule=schedule, staleness=staleness, order=order
    )
    state, diags = runner.run()
    return DenseState(state.U, state.A, state.lam), diags


def _colored_runner(
    stats: SufficientStats,
    g: Graph,
    cfg: ConsensusConfig,
    *,
    schedule: Sequence[Sequence[int]] | None = None,
    staleness: int = 0,
    order: str = "fixed",
) -> Runner:
    """Validate the colored-sweep arguments and build the matching Runner."""
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    if order not in ("fixed", "gauss_southwell"):
        raise ValueError(
            f"unknown order {order!r}; expected 'fixed' or 'gauss_southwell'"
        )
    m = stats.G.shape[0]
    if schedule is None:
        schedule = g.chromatic_schedule()
    schedule = tuple(tuple(int(t) for t in cls) for cls in schedule)
    _validate_schedule(schedule, m)
    if order == "gauss_southwell":
        if staleness != 0:
            raise ValueError(
                "order='gauss_southwell' requires staleness=0: with frozen "
                "k-round-old views every phase reads the same snapshot, so "
                "the class order cannot affect the sweep"
            )
        return _make_southwell_runner(stats, g, cfg, schedule)
    return _make_colored_runner(stats, g, cfg, schedule, staleness)


def _make_colored_runner(
    stats: SufficientStats,
    g: Graph,
    cfg: ConsensusConfig,
    schedule: tuple[tuple[int, ...], ...],
    staleness: int,
) -> Runner:
    es = _edge_setup(stats, g, cfg)
    stats = es.stats
    robust_agg = resolve_aggregator(cfg)

    # Class-constant slices (stats, precomp, degrees) and the per-class
    # incident-edge lists are gathered ONCE, outside the ADMM scan — only
    # U/A/lam move between phases.  Each phase sums only the edges touching
    # its class (two segment_sums added in the same order as the full
    # ``neighbor_sum``, so the single-class schedule stays bitwise-equal to
    # ``fit_dense``); total per-iteration gather work is O(E) across all
    # phases, not O(c * E).
    phases = []
    for cls in schedule:
        idx = jnp.asarray(cls, jnp.int32)
        stats_c = SufficientStats(
            G=stats.G[idx], R=stats.R[idx], n=stats.n[idx], t2=stats.t2[idx]
        )
        precomp_c = (
            None if es.precomp is None
            else jax.tree_util.tree_map(lambda x: x[idx], es.precomp)
        )
        msg_consts = (es.deg[idx], es.tau_t[idx], es.zeta_t[idx])
        pos = {t: i for i, t in enumerate(cls)}
        s_rows = jnp.asarray(
            [pos[s] for (s, e) in g.edges if s in pos], jnp.int32)
        s_others = jnp.asarray(
            [e for (s, e) in g.edges if s in pos], jnp.int32)
        e_rows = jnp.asarray(
            [pos[e] for (s, e) in g.edges if e in pos], jnp.int32)
        e_others = jnp.asarray(
            [s for (s, e) in g.edges if e in pos], jnp.int32)

        def gather_c(view, k=len(cls), sr=s_rows, so=s_others,
                     er=e_rows, eo=e_others):
            return jax.ops.segment_sum(view[so], sr, k) + jax.ops.segment_sum(
                view[eo], er, k
            )

        phases.append((idx, stats_c, precomp_c, msg_consts, gather_c))

    # hist[j] = U published at the end of iteration i - staleness + j;
    # pre-history is the initial subspace.
    hist0 = jnp.broadcast_to(es.init.U, (staleness,) + es.init.U.shape)

    def step(state, _):
        U, A, lam, hist = state
        U_start = U
        # lam only moves at iteration end, so C^T lam is gathered once; the
        # neighbor view is the live U (staleness=0, regathered per phase
        # over the class's incident edges only) or the frozen k-round-old
        # snapshot.
        ct_lam_full = es.ct_transpose(lam)
        for idx, stats_c, precomp_c, (deg_c, tau_c, zeta_c), gather_c in phases:
            view = U if staleness == 0 else hist[0]
            # robust aggregators need the full candidate set, so the robust
            # path reuses the full-graph ``es.neighbor_sum`` and slices the
            # class rows; the mean path keeps the O(E)-total per-class
            # gather (and its bitwise parity with fit_dense).
            gathered = (
                gather_c(view) if robust_agg is None
                else es.neighbor_sum(view)[idx]
            )
            msgs = NeighborMsgs(
                gathered, ct_lam_full[idx], deg_c, tau_c, zeta_c
            )
            U_c, A_c = es.body(
                stats_c, AgentState(U[idx], A[idx], None), msgs, precomp_c
            )
            U = U.at[idx].set(U_c)
            A = A.at[idx].set(A_c)
        resid_old = es.edge_diff(U_start)
        resid_new = es.edge_diff(U)
        lam_new, gamma, primal = dual_step(lam, resid_old, resid_new, cfg)
        diag = _iteration_diag(
            stats, cfg, U, A, lam_new, resid_new, gamma, primal
        )
        if cfg.telemetry:
            dtype = U.dtype
            # staleness<=1 phases read current-round views (live U or the
            # previous iterate — both count as fresh deliveries, matching
            # the tape executor's age==1 accounting); staleness>1 serves
            # k-round-old snapshots, i.e. every message arrives stale
            fresh = 2.0 * g.n_edges if staleness <= 1 else 0.0
            diag["msgs_delivered"] = jnp.asarray(fresh, dtype)
            diag["msgs_stale"] = jnp.asarray(
                2.0 * g.n_edges - fresh, dtype)
            diag["msgs_dropped"] = jnp.zeros((), dtype)
            audit_view = U_start if staleness == 0 else hist[0]
            diag["agg_rejected"] = (
                es.ex.audit(audit_view) if es.ex.agg is not None
                else jnp.zeros((), dtype)
            )
        if staleness > 0:
            hist = jnp.concatenate([hist[1:], U[None]], axis=0)
        return (U, A, lam_new, hist), diag

    def init_fn():
        return RunState(
            U=es.init.U, A=es.init.A, lam=es.init.lam,
            k=jnp.zeros((), jnp.int32), hist=hist0,
        )

    def segment_fn(state, n):
        # carry = the monolithic (U, A, lam, hist) — the staleness window
        # rides along (zero-depth when staleness=0), so a segment boundary
        # preserves the delayed views exactly
        (U, A, lam, hist), diags = jax.lax.scan(
            step, (state.U, state.A, state.lam, state.hist), None, length=n
        )
        if cfg.telemetry:
            model = modeled_floats_per_iter(
                "colored", L=stats.G.shape[-1], r=cfg.r, n_edges=g.n_edges
            )
            diags["comm_floats"] = jnp.full((n,), float(model), stats.G.dtype)
        return state._replace(
            U=U, A=A, lam=lam, hist=hist, k=state.k + n
        ), diags

    return Runner("colored", cfg, init_fn, segment_fn)


def _make_southwell_runner(
    stats: SufficientStats,
    g: Graph,
    cfg: ConsensusConfig,
    schedule: tuple[tuple[int, ...], ...],
) -> Runner:
    """Runner for the adaptive Gauss-Southwell sweep (``fit_colored(order=…)``).

    Each iteration scores every color class by the summed squared residual
    of its incident edges on the CURRENT iterate and runs the classes
    largest-violation-first.  The chosen order is traced data, so classes
    are padded to a common width ``K`` with an out-of-range sentinel agent
    ``m``: gathers clamp the sentinel (the garbage row is computed but
    discarded), writebacks use scatter ``mode="drop"`` so the sentinel rows
    never land.  Numerics per phase otherwise mirror ``fit_colored``'s
    staleness=0 path (live full-graph ``neighbor_sum`` regathered between
    phases, one shared :func:`dual_step` per iteration).
    """
    import numpy as np

    es = _edge_setup(stats, g, cfg)
    stats = es.stats
    m = stats.G.shape[0]
    n_cls = len(schedule)
    K = max(len(cls) for cls in schedule)
    pad_np = np.full((n_cls, K), m, np.int32)       # m = dropped sentinel
    cls_of = np.empty(m, np.int64)
    for p, cls in enumerate(schedule):
        pad_np[p, : len(cls)] = cls
        for t in cls:
            cls_of[t] = p
    # class-edge incidence: a proper coloring puts each edge's endpoints in
    # two different classes, so each edge scores both
    inc_np = np.zeros((n_cls, g.n_edges), np.float32)
    for j, (s, e) in enumerate(g.edges):
        inc_np[cls_of[s], j] = 1.0
        inc_np[cls_of[e], j] = 1.0
    pad_idx = jnp.asarray(pad_np)
    clamp_idx = jnp.minimum(pad_idx, m - 1)
    inc = jnp.asarray(inc_np)

    def step(state, _):
        U, A, lam = state
        U_start = U
        ct_lam_full = es.ct_transpose(lam)
        edge_sq = jnp.sum(es.edge_diff(U) ** 2, axis=(-2, -1))   # (E,)
        # ties (e.g. iteration 0's zero residuals) keep schedule order:
        # argsort is stable, so the all-tied case equals order="fixed"
        sweep = jnp.argsort(-(inc @ edge_sq))                    # (n_cls,)
        for p in range(n_cls):
            c = sweep[p]
            idx, idxc = pad_idx[c], clamp_idx[c]
            stats_c = SufficientStats(
                G=stats.G[idxc], R=stats.R[idxc],
                n=stats.n[idxc], t2=stats.t2[idxc],
            )
            precomp_c = (
                None if es.precomp is None
                else jax.tree_util.tree_map(lambda x: x[idxc], es.precomp)
            )
            msgs = NeighborMsgs(
                es.neighbor_sum(U)[idxc], ct_lam_full[idxc],
                es.deg[idxc], es.tau_t[idxc], es.zeta_t[idxc],
            )
            U_c, A_c = es.body(
                stats_c, AgentState(U[idxc], A[idxc], None), msgs, precomp_c
            )
            U = U.at[idx].set(U_c, mode="drop")
            A = A.at[idx].set(A_c, mode="drop")
        resid_old = es.edge_diff(U_start)
        resid_new = es.edge_diff(U)
        lam_new, gamma, primal = dual_step(lam, resid_old, resid_new, cfg)
        diag = _iteration_diag(
            stats, cfg, U, A, lam_new, resid_new, gamma, primal
        )
        if cfg.telemetry:
            dtype = U.dtype
            # every phase regathers from the live U: all fresh deliveries
            diag["msgs_delivered"] = jnp.asarray(2.0 * g.n_edges, dtype)
            diag["msgs_stale"] = jnp.zeros((), dtype)
            diag["msgs_dropped"] = jnp.zeros((), dtype)
            diag["agg_rejected"] = (
                es.ex.audit(U_start) if es.ex.agg is not None
                else jnp.zeros((), dtype)
            )
        return (U, A, lam_new), diag

    def init_fn():
        return RunState(
            U=es.init.U, A=es.init.A, lam=es.init.lam,
            k=jnp.zeros((), jnp.int32),
        )

    def segment_fn(state, n):
        (U, A, lam), diags = jax.lax.scan(
            step, (state.U, state.A, state.lam), None, length=n
        )
        if cfg.telemetry:
            model = modeled_floats_per_iter(
                "colored", L=stats.G.shape[-1], r=cfg.r, n_edges=g.n_edges
            )
            diags["comm_floats"] = jnp.full((n,), float(model), stats.G.dtype)
        return state._replace(U=U, A=A, lam=lam, k=state.k + n), diags

    return Runner("colored", cfg, init_fn, segment_fn)


# --------------------------------------------------------------------------
# Executor 5: event-driven asynchrony (delay/drop/straggler event tapes)
# --------------------------------------------------------------------------


def fit_async(
    stats: SufficientStats,
    g: Graph,
    cfg: ConsensusConfig,
    tape,
    *,
    aged_duals: bool = False,
) -> tuple[DenseState, dict]:
    """Executor 5: the ``repro.netsim`` event-tape executor.

    Drives the same :func:`agent_update` body under simulated asynchrony —
    per-edge random delays, dropped messages (the receiver keeps its last
    delivered view), compute stragglers — precompiled into a fixed-shape
    ``EventTape`` so the whole run is one ``jax.lax.scan``.  Parity
    oracles: ``netsim.zero_delay_tape`` is bitwise :func:`fit_dense`;
    ``netsim.constant_tape(k)`` reproduces ``fit_colored(staleness=k)``.
    See ``repro.netsim.executor`` (imported lazily: the engine stays free
    of a netsim dependency cycle) for the tape semantics.
    """
    from repro.netsim.executor import fit_async as _netsim_fit_async

    return _netsim_fit_async(stats, g, cfg, tape, aged_duals=aged_duals)


# --------------------------------------------------------------------------
# Executors 2 and 4: shard_map + ppermute (one agent per mesh shard)
# --------------------------------------------------------------------------


def torus_edges(sizes: Sequence[int]) -> set:
    """Directed edge set of the ring/torus :func:`fit_sharded` realizes.

    This is the topology contract of :func:`ring_iteration`, kept next to
    it: agents are the row-major flattening of the agent-axis grid, and
    along each axis every coordinate owns the edge to its +1 neighbor (a
    size-2 axis is the degenerate ring with a SINGLE edge, not a doubled
    pair).  Entry points use it to reject graphs the sharded executor
    would silently replace.
    """
    import itertools

    sizes = list(sizes)
    strides = [1] * len(sizes)
    for i in range(len(sizes) - 2, -1, -1):
        strides[i] = strides[i + 1] * sizes[i + 1]

    def flat(coord):
        return sum(c * s for c, s in zip(coord, strides))

    edges = set()
    for ax_i, n_ax in enumerate(sizes):
        for coord in itertools.product(*(range(s) for s in sizes)):
            if n_ax == 2 and coord[ax_i] == 1:
                continue
            nb = list(coord)
            nb[ax_i] = (coord[ax_i] + 1) % n_ax
            edges.add((flat(coord), flat(nb)))
    return edges


def graph_matches_torus(g: Graph, sizes: Sequence[int]) -> bool:
    """True iff ``g`` is the mesh ring/torus UP TO PER-EDGE ORIENTATION.

    The consensus problem is orientation-invariant (flipping an edge flips
    the sign of its dual and nothing else), so entry points must not reject
    e.g. ``Graph(m=4, edges=((1, 0), (1, 2), (2, 3), (3, 0)))`` — the same
    undirected ring as ``torus_edges([4])`` with one edge written backwards.
    Compares undirected edge SETS (a duplicated edge in either orientation
    is not the simple torus and fails the match).
    """
    und = {frozenset(e) for e in g.edges}
    if len(und) != len(g.edges):
        return False
    return und == {frozenset(e) for e in torus_edges(sizes)}


def _local_objective(
    stats_t: SufficientStats, U: jax.Array, A: jax.Array,
    cfg: ConsensusConfig, m_total: int,
) -> jax.Array:
    """ONE agent's contribution to the primal objective (eq. 12) from its
    shard-local stats alone — requires the ``n``/``t2`` leaves to be
    threaded through the shard_map (they make ``||T_t||^2`` available
    without revisiting data).  Summed over agents this equals
    :func:`objective_from_stats` exactly."""
    UtGU = mm(U.T, mm(stats_t.G, U))
    quad = jnp.sum(mm(UtGU, A) * A)                 # tr(A^T U^T G U A)
    cross = jnp.sum(mm(U.T, stats_t.R) * A)         # tr(A^T U^T R)
    t2 = jnp.asarray(stats_t.t2, jnp.float32)
    return (
        0.5 * (quad - 2.0 * cross + t2)
        + 0.5 * (cfg.mu1 / m_total) * jnp.sum(U**2)
        + 0.5 * cfg.mu2 * jnp.sum(A**2)
    )


def _assemble_sharded_diags(diags: dict, n_edges: int, lr_size: int) -> dict:
    """Combine the per-shard per-iteration (iters, m) diagnostic columns the
    shard_map returns into the shared executor diagnostics contract.  The
    per-edge sums are NOT psummed in-shard (each shard reports only the
    edges it owns), so the cross-shard sum here counts every edge once."""
    obj = diags["obj"].sum(axis=1)
    lag_pen = diags["lag_pen"].sum(axis=1)
    primal = diags["primal_sq"].sum(axis=1)
    gamma = diags["gamma_sum"].sum(axis=1) / n_edges
    gamma_min = diags["gamma_min"].min(axis=1)
    out = {
        "objective": obj,
        "lagrangian": obj + lag_pen,
        "consensus": jnp.sqrt(primal / (n_edges * lr_size)),
        "gamma": gamma,
        "gamma_min": gamma_min,
        "primal_sq": primal,
    }
    # telemetry columns (cfg.telemetry runs only): counts sum across
    # shards, the worst residual is the max over shards
    if "resid_max" in diags:
        out["resid_max"] = diags["resid_max"].max(axis=1)
    for key in ("agg_rejected", "msgs_delivered", "msgs_stale",
                "msgs_dropped"):
        if key in diags:
            out[key] = diags[key].sum(axis=1)
    return out


def _ring_recv_from_next(x, axis_name):
    """Receive x from agent t+1 on the ring (source i sends to i-1)."""
    n = jax.lax.axis_size(axis_name)
    return jax.lax.ppermute(x, axis_name, [(i, (i - 1) % n) for i in range(n)])


def _ring_recv_from_prev(x, axis_name):
    n = jax.lax.axis_size(axis_name)
    return jax.lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def ring_iteration(
    state: AgentState,
    stats: SufficientStats,
    agent_axes: Sequence[str],
    cfg: ConsensusConfig,
    m_total: int,
    precomp=None,
) -> tuple[AgentState, dict]:
    """One ADMM round for the shard-local agent (runs inside shard_map).

    Pure message plumbing around :func:`agent_update`: gather neighbor
    subspaces/duals over the per-axis rings, run the shared body, exchange
    the fresh U once more for the edge-dual step.  Per iteration each agent
    moves 3 ppermute(U) + 1 ppermute(lambda) per agent axis — the paper's
    O(k L r) communication volume on nearest-neighbor ICI links.

    A size-2 axis is the degenerate ring: ``ring(2)`` has a SINGLE edge
    (0, 1), so each agent has degree 1 (not 2), the next/prev ppermutes
    would deliver the same neighbor twice (counted once here), and only
    agent 0 owns the axis edge — agent 1's dual slot is masked to zero.
    This keeps ``fit_sharded`` on a 2-agent mesh in exact agreement with
    ``fit_dense`` on ``ring(2)``.
    """
    U, A, lam = state
    dtype = U.dtype
    # Ring degree per axis: 2 neighbors, except the degenerate 2-agent ring
    # whose single edge gives degree 1.
    ax_sizes = [jax.lax.axis_size(ax) for ax in agent_axes]
    for ax, n_ax in zip(agent_axes, ax_sizes):
        if n_ax < 2:
            raise ValueError(f"agent axis {ax!r} needs >= 2 shards, got {n_ax}")
    deg = jnp.asarray(
        sum(1.0 if n_ax == 2 else 2.0 for n_ax in ax_sizes), dtype
    )
    tau_t = jnp.asarray(cfg.tau, dtype) + deg
    zeta_t = jnp.asarray(cfg.zeta, dtype)

    # --- gather neighbor subspaces and incoming edge duals --------------
    robust_agg = resolve_aggregator(cfg)
    neigh = jnp.zeros_like(U)
    ct_lam = jnp.zeros_like(U)
    views = []
    u_next_old = []
    own_edge = []
    for ax_i, (ax, n_ax) in enumerate(zip(agent_axes, ax_sizes)):
        u_next = _ring_recv_from_next(U, ax)            # U_{t+1}^k
        lam_prev = _ring_recv_from_prev(lam[ax_i], ax)  # dual of edge (t-1, t)
        if n_ax == 2:
            # single edge: the one neighbor arrives on both permutes —
            # count it once, and only agent 0 owns the edge dual
            neigh = neigh + u_next
            views.append(u_next)
            own = (jax.lax.axis_index(ax) == 0).astype(dtype)
        else:
            u_prev = _ring_recv_from_prev(U, ax)        # U_{t-1}^k
            neigh = neigh + u_next + u_prev
            views.extend((u_next, u_prev))
            own = jnp.asarray(1.0, dtype)
        # C_t^T lambda: +lam on own (s-side) edge, -lam on incoming (e-side).
        ct_lam = ct_lam + lam[ax_i] - lam_prev
        u_next_old.append(u_next)
        own_edge.append(own)
    if robust_agg is not None:
        # the shared aggregator contract (repro.core.exchange): received
        # views + own U as candidates, all-ones mask (every ring neighbor
        # is live), center rescaled back to the degree-weighted sum
        neigh = exchange.stack_ring_candidates(views, U, deg, robust_agg,
                                               dtype)
    agg_rejected = jnp.zeros((), dtype)
    if cfg.telemetry and robust_agg is not None:
        # neigh = deg * agg(V, Mv) above, so neigh/deg is the exact robust
        # center the aggregation used
        V = jnp.stack(list(views) + [U], axis=0)
        Mv = jnp.ones((V.shape[0],), dtype)
        agg_rejected = jnp.sum(
            exchange.aggregator_audit(V, Mv, neigh / deg)
        )

    # --- the shared per-agent body ---------------------------------------
    msgs = NeighborMsgs(neigh, ct_lam, deg, tau_t, zeta_t)
    U_new, A_new = agent_update(
        stats, AgentState(U, A, lam), msgs, cfg,
        m_total=m_total, precomp=precomp,
    )

    # --- shared dual step on the owned edge (t, t+1) per axis ------------
    # Per-edge diagnostics are accumulated over OWNED edges only (masked by
    # own_edge), so a plain cross-shard sum outside counts each edge once.
    lam_new = []
    primal_sq = jnp.zeros((), dtype)
    gamma_sum = jnp.zeros((), dtype)
    gamma_min = jnp.asarray(jnp.inf, dtype)
    lag_pen = jnp.zeros((), dtype)
    resid_max = jnp.zeros((), dtype)
    for ax_i, ax in enumerate(agent_axes):
        u_next_new = _ring_recv_from_next(U_new, ax)
        resid_new = U_new - u_next_new                  # \hat C_i U^{k+1}
        resid_old = U - u_next_old[ax_i]                # \hat C_i U^k
        lam_ax, gamma, primal = dual_step(lam[ax_i], resid_old, resid_new, cfg)
        own = own_edge[ax_i]
        lam_new.append(own * lam_ax)
        primal_sq = primal_sq + own * primal
        gamma_sum = gamma_sum + own * gamma
        gamma_min = jnp.minimum(
            gamma_min, jnp.where(own > 0, gamma, jnp.inf)
        )
        lag_pen = lag_pen + own * (
            jnp.sum(lam_ax * resid_new) + 0.5 * cfg.rho * jnp.sum(resid_new**2)
        )
        if cfg.telemetry:
            resid_max = jnp.maximum(
                resid_max,
                jnp.where(own > 0, jnp.max(jnp.abs(resid_new)), 0.0),
            )
    lam_new = jnp.stack(lam_new)

    diag = {
        "primal_sq": primal_sq,
        "gamma_sum": gamma_sum,
        "gamma_min": gamma_min,
        "lag_pen": lag_pen,
    }
    if cfg.telemetry:
        diag["resid_max"] = resid_max
        diag["agg_rejected"] = agg_rejected
        # every ring view arrives fresh each iteration (synchronous
        # ppermute): deg deliveries per shard, nothing stale or dropped
        diag["msgs_delivered"] = deg
        diag["msgs_stale"] = jnp.zeros((), dtype)
        diag["msgs_dropped"] = jnp.zeros((), dtype)
    return AgentState(U_new, A_new, lam_new), diag


def _place_by_agent(stats: SufficientStats, mesh, axes_t, cfg):
    """(G, R, n, t2, precomp) laid out one agent block per shard of
    ``axes_t``.

    Scalar n/t2 (the (G, R)-only construction) broadcast to per-agent
    leaves so they shard alongside G/R.  Stats that already arrive placed
    by agent (``produce_stats(..., mesh=)``) are not moved; anything else
    is scattered ONCE here, not resharded on every segment.  ``precomp``
    is the U-solver's hoisted set-up (:func:`hoist_precomp`) as a tuple of
    agent-placed leaves — ``(eigenvalues, eigenvectors)`` for sylvester,
    ``()`` otherwise — computed once per runner rather than inside every
    segment program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = stats.G.shape[0]
    sh = NamedSharding(mesh, P(axes_t))
    n_all = jnp.broadcast_to(jnp.asarray(stats.n, jnp.float32), (m,))
    t2_all = jnp.broadcast_to(jnp.asarray(stats.t2, jnp.float32), (m,))
    G_all, R_all, n_all, t2_all = jax.device_put(
        (stats.G, stats.R, n_all, t2_all), sh
    )
    pre_all = _sharded_eigh(mesh, axes_t)(G_all) if _hoists_eigh(cfg) else ()
    return G_all, R_all, n_all, t2_all, pre_all


@functools.lru_cache(maxsize=None)
def _sharded_eigh(mesh, axes_t):
    """eigh of every shard's own G blocks, placed by agent: one compiled
    program per mesh layout, shared by every sharded runner built on it
    (the batched eigh is the long compile of the sylvester solver)."""
    from jax.sharding import PartitionSpec as P

    spec = P(axes_t)
    return jax.jit(jax.shard_map(
        lambda G: tuple(jnp.linalg.eigh(G)), mesh=mesh, check_vma=False,
        in_specs=spec, out_specs=(spec, spec),
    ))


def _shard_precomp(pre_blk):
    """This shard's hoisted precomp from its ``_place_by_agent`` block."""
    return tuple(x[0] for x in pre_blk) or None


def _make_sharded_runner(
    stats: SufficientStats,
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    cfg: ConsensusConfig,
) -> Runner:
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = stats.G.shape[0]
    sizes = [mesh.shape[ax] for ax in agent_axes]
    n_agents = functools.reduce(lambda a, b: a * b, sizes, 1)
    if m != n_agents:
        raise ValueError(f"m={m} must equal prod(agent axes)={n_agents}")
    L, d, r = stats.G.shape[-1], stats.R.shape[-1], cfg.r
    dtype = stats.G.dtype
    axes_t = tuple(agent_axes)
    spec_batched = P(axes_t)
    n_axes = len(agent_axes)
    G_all, R_all, n_all, t2_all, pre_all = _place_by_agent(
        stats, mesh, axes_t, cfg
    )

    def init_fn():
        # the stacked all-ones/zeros state placed shard-per-agent; feeding
        # it through in_specs makes it device-varying inside the body, the
        # same type the in-body pcast used to establish
        sh = NamedSharding(mesh, spec_batched)
        return RunState(
            U=jax.device_put(jnp.ones((m, L, r), dtype), sh),
            A=jax.device_put(jnp.ones((m, r, d), dtype), sh),
            lam=jax.device_put(jnp.zeros((m, n_axes, L, r), dtype), sh),
            k=jnp.zeros((), jnp.int32),
        )

    def shardings_fn():
        sh = NamedSharding(mesh, spec_batched)
        return RunState(
            U=sh, A=sh, lam=sh, k=NamedSharding(mesh, P())
        )

    def segment_fn(state, n):
        def body(G_blk, R_blk, n_blk, t2_blk, pre_blk, U_blk, A_blk,
                 lam_blk):
            stats_t = SufficientStats(
                G=G_blk[0], R=R_blk[0], n=n_blk[0], t2=t2_blk[0]
            )
            precomp = _shard_precomp(pre_blk)

            def step(carry, _):
                new, diag = ring_iteration(
                    carry, stats_t, agent_axes, cfg, m, precomp
                )
                diag["obj"] = _local_objective(stats_t, new.U, new.A, cfg, m)
                return new, diag

            final, diags = jax.lax.scan(
                step, AgentState(U_blk[0], A_blk[0], lam_blk[0]), None,
                length=n,
            )
            # (iters,) per-shard columns -> (iters, 1) so the out_spec can
            # lay every shard's contribution side by side for the combine
            diags = jax.tree_util.tree_map(lambda x: x[:, None], diags)
            return final.U[None], final.A[None], final.lam[None], diags

        shard_fn = jax.shard_map(
            body,
            mesh=mesh,
            check_vma=False,
            in_specs=(spec_batched,) * 8,
            out_specs=(
                spec_batched, spec_batched, spec_batched, P(None, axes_t),
            ),
        )
        U, A, lam, diags = shard_fn(
            G_all, R_all, n_all, t2_all, pre_all, state.U, state.A,
            state.lam,
        )
        diags = _assemble_sharded_diags(
            diags, len(torus_edges(sizes)), L * cfg.r
        )
        if cfg.telemetry:
            model = modeled_floats_per_iter(
                "sharded", L=L, r=cfg.r, m=m, n_axes=n_axes
            )
            diags["comm_floats"] = jnp.full((n,), float(model), dtype)
        return state._replace(U=U, A=A, lam=lam, k=state.k + n), diags

    return Runner("sharded", cfg, init_fn, segment_fn, shardings_fn)


def fit_sharded(
    stats: SufficientStats,
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    cfg: ConsensusConfig,
):
    """Run consensus ADMM with one agent per shard of ``mesh[agent_axes]``.

    The consensus graph is the ring/torus induced by the agent axes; the
    same :func:`agent_update` body as :func:`fit_dense` runs per shard.
    Stats stay sharded on the agent axes — ALL FOUR leaves (G, R, n, t2),
    so the primal objective is computable on-device from stats alone — and
    only U_t (and the edge duals) ever cross shard boundaries, the paper's
    privacy/communication model.

    Returns (U (m,L,r), A (m,r,d), diagnostics) with U/A sharded over agent
    axes and diagnostics carrying the shared executor contract
    ('objective', 'lagrangian', 'consensus', 'gamma', 'gamma_min',
    'primal_sq' — see :func:`_iteration_diag`).
    """
    state, diags = _make_sharded_runner(stats, mesh, agent_axes, cfg).run()
    return state.U, state.A, diags


# --------------------------------------------------------------------------
# Executor 4: shard_map over ANY connected Graph (compiled edge schedule)
# --------------------------------------------------------------------------


def _make_sharded_graph_runner(
    stats: SufficientStats,
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    g: Graph,
    cfg: ConsensusConfig,
    *,
    schedule: Sequence[Sequence[int]] | None = None,
    tape=None,
    aged_duals: bool = False,
) -> Runner:
    """Runner for :func:`fit_sharded_graph` — consensus ADMM over ANY
    connected ``Graph`` with one agent per mesh shard (the edge-schedule
    compiler executor).

    ``compile_edge_schedule`` decomposes ``g``'s edge list into ≤ Δ+1
    matchings (Misra-Gries proper edge coloring); each matching is ONE
    partial ``jax.lax.ppermute`` round on the flattened agent axes (both
    directions of a matched pair ride the same permutation; idle shards
    receive zeros).  Summing the rounds reproduces ``fit_dense``'s
    edge-list ``neighbor_sum`` / ``ct_transpose`` / ``dual_step`` semantics
    exactly: agent ``t`` (the row-major flattening of its agent-axis
    coordinates) holds stats shard ``t``, and the dual of edge ``(s, e)``
    lives on shard ``s`` (slot table from the compiler), mirroring the
    dense executor's source-side dual layout.

    ``schedule`` (a vertex-class partition, e.g. ``g.chromatic_schedule()``)
    runs the color phases INSIDE shard_map — sharded Gauss-Seidel: every
    phase re-exchanges the live ``U`` and applies the shared
    :func:`agent_update` under the phase mask, so later classes see earlier
    classes' fresh subspaces, exactly like :func:`fit_colored` with
    ``staleness=0``.  ``schedule=None`` is the single-phase Jacobian sweep
    (the :func:`fit_dense` parity oracle).  Communication per iteration is
    ``rounds * (phases + 1)`` U-ppermutes (the phase-0 gather doubles as
    the dual step's resid_old exchange) + ``rounds`` dual-ppermutes, with
    ``rounds ≤ Δ+1``.

    Returns ``(U (m,L,r), A (m,r,d), diagnostics)`` — the same output and
    diagnostics contract as :func:`fit_sharded`.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.graph import compile_edge_schedule

    m = stats.G.shape[0]
    sizes = [mesh.shape[ax] for ax in agent_axes]
    n_agents = functools.reduce(lambda a, b: a * b, sizes, 1)
    if m != n_agents:
        raise ValueError(f"m={m} must equal prod(agent axes)={n_agents}")
    if g.m != m:
        raise ValueError(f"graph has m={g.m} agents but stats carry m={m}")
    if schedule is not None:
        schedule = tuple(tuple(int(t) for t in cls) for cls in schedule)
        _validate_schedule(schedule, m)
    else:
        schedule = jacobian_schedule(m)
    n_phases = len(schedule)

    sched = compile_edge_schedule(g)
    n_rounds = sched.n_rounds
    L, d, r = stats.G.shape[-1], stats.R.shape[-1], cfg.r
    dtype = stats.G.dtype
    axes_t = tuple(agent_axes)

    G_all, R_all, n_all, t2_all, pre_all = _place_by_agent(
        stats, mesh, axes_t, cfg
    )
    deg_all = jnp.asarray(g.degrees(), dtype)                    # (m,)
    # proximal weights resolved EXACTLY like the dense executor (scalar tau
    # -> tau + d_t, per-agent (m,) arrays passed through) and shipped as
    # sharded operands so each shard reads its own entry
    tau_all, zeta_all = _resolve_tau_zeta(cfg, deg_all, m, dtype)
    tau_all = jnp.broadcast_to(tau_all, (m,))
    slot_all = jnp.asarray(sched.slot, jnp.int32)                # (m, rounds)
    own_all = jnp.asarray(sched.own, dtype)                      # (m, rounds)
    pmask_all = jnp.zeros((m, n_phases), dtype)                  # (m, phases)
    for p, cls in enumerate(schedule):
        pmask_all = pmask_all.at[jnp.asarray(cls, jnp.int32), p].set(1.0)
    robust_agg = resolve_aggregator(cfg)
    # the masked-ppermute exchange backend over the compiled schedule:
    # bidirectional round permutes, the round-participation mask (idle
    # shards receive ppermute zeros, which the robust aggregators must
    # EXCLUDE rather than treat as candidates), dual shipping, and the
    # in-mesh tape driver — see repro.core.exchange.ShardedGraphExchange
    sgx = exchange.ShardedGraphExchange(g, sched, axes_t, dtype, robust_agg)
    rmask_all = sgx.rmask_all                                    # (m, rounds)

    # --- optional in-mesh tape replay (EventTape / AdversaryTape) ---------
    if aged_duals and tape is None:
        raise ValueError("aged_duals=True needs tape= (the replayed tape)")
    is_adv = getattr(tape, "attack", None) is not None
    if tape is not None:
        from repro.netsim.events import validate_tape

        validate_tape(tape, g, cfg.iters)
        if n_phases != 1:
            raise ValueError(
                "in-mesh tape replay supports only the Jacobian sweep "
                "(schedule=None); Gauss-Seidel phases have no tape "
                "semantics"
            )
        import numpy as np

        depth = tape.depth
        tbl = sgx.tape_tables(tape)
        send_age_np, live_np = tbl["send_age"], tbl["live"]
        member_np, member_prev_np = tbl["member"], tbl["member_prev"]
        active_np = np.asarray(tape.active, np.float32)
        ages_np = np.asarray(tape.age)
        if is_adv:
            attack_np = np.asarray(tape.attack)
            noise_np = np.asarray(tape.noise)
            offset_np = np.asarray(tape.offset)
        scalar_tau = jnp.asarray(cfg.tau).ndim == 0

    def init_fn():
        # stacked all-ones/zeros state placed shard-per-agent; arriving
        # through in_specs it is device-varying inside the body, the same
        # type the in-body pcast used to establish.  Tape mode adds the
        # per-shard published-U ring buffer (m, depth, L, r) — agent axis
        # LEADING so the same P(axes_t) spec shards it — pre-filled with
        # U^0 (the "nothing delivered yet" / drop fallback), and the aged-
        # dual ring (m, depth, n_slots, L, r) of zero initial duals.
        sh = NamedSharding(mesh, P(axes_t))
        hist0 = lam_hist0 = None
        if tape is not None:
            hist0 = jax.device_put(
                jnp.ones((m, depth, L, r), dtype), sh
            )
            if aged_duals:
                lam_hist0 = jax.device_put(
                    jnp.zeros((m, depth, sched.n_slots, L, r), dtype), sh
                )
        return RunState(
            U=jax.device_put(jnp.ones((m, L, r), dtype), sh),
            A=jax.device_put(jnp.ones((m, r, d), dtype), sh),
            lam=jax.device_put(
                jnp.zeros((m, sched.n_slots, L, r), dtype), sh
            ),
            k=jnp.zeros((), jnp.int32),
            hist=hist0,
            lam_hist=lam_hist0,
        )

    def shardings_fn():
        sh = NamedSharding(mesh, P(axes_t))
        return RunState(
            U=sh, A=sh, lam=sh, k=NamedSharding(mesh, P()),
            hist=sh if tape is not None else None,
            lam_hist=sh if (tape is not None and aged_duals) else None,
        )

    def body(G_blk, R_blk, n_blk, t2_blk, pre_blk, deg_blk, tau_blk,
             zeta_blk, slot_blk, own_blk, pmask_blk, rmask_blk, U_blk, A_blk,
             lam_blk, *, n_seg):
        stats_t = SufficientStats(
            G=G_blk[0], R=R_blk[0], n=n_blk[0], t2=t2_blk[0]
        )
        precomp = _shard_precomp(pre_blk)
        deg_t, tau_t, zeta_t = deg_blk[0], tau_blk[0], zeta_blk[0]
        slots, own, pmask = slot_blk[0], own_blk[0], pmask_blk[0]
        rmask = rmask_blk[0]
        U0, A0, lam0 = U_blk[0], A_blk[0], lam_blk[0]

        def step(carry, _):
            U, A, lam = carry
            U_start = U
            # C_t^T lambda: + the duals this shard owns (unowned slots stay
            # zero), - every incoming dual, shipped source->dest per round
            ct_lam = sgx.ship_ct_lam(lam, slots, own)
            u_start_nb = sgx.exchange(U_start)  # also resid_old for duals
            nb = u_start_nb
            agg_rejected = jnp.zeros((), dtype)
            if cfg.telemetry and robust_agg is not None:
                # reduce_views returns deg_t * agg(V, Mv), so dividing the
                # degree back out recovers the exact robust center audited
                neigh0 = sgx.reduce_views(u_start_nb, U_start, deg_t, rmask)
                agg_rejected = sgx.audit_views(
                    u_start_nb, U_start, rmask,
                    neigh0 / jnp.maximum(deg_t, 1.0),
                )
            for p in range(n_phases):
                if p > 0:
                    nb = sgx.exchange(U)        # live U: Gauss-Seidel phases
                neigh = sgx.reduce_views(nb, U, deg_t, rmask)
                msgs = NeighborMsgs(neigh, ct_lam, deg_t, tau_t, zeta_t)
                U_upd, A_upd = agent_update(
                    stats_t, AgentState(U, A, lam), msgs, cfg,
                    m_total=m, precomp=precomp,
                )
                mk = pmask[p]
                U = jnp.where(mk > 0, U_upd, U)
                A = jnp.where(mk > 0, A_upd, A)

            # dual step on owned edges; diagnostics masked to owned edges so
            # the host-side cross-shard sum counts each edge once
            u_new_nb = sgx.exchange(U)
            primal_sq = jnp.zeros((), dtype)
            gamma_sum = jnp.zeros((), dtype)
            gamma_min = jnp.asarray(jnp.inf, dtype)
            lag_pen = jnp.zeros((), dtype)
            resid_max = jnp.zeros((), dtype)
            for rr in range(n_rounds):
                resid_new = U - u_new_nb[rr]            # C_i U^{k+1} on src
                resid_old = U_start - u_start_nb[rr]    # C_i U^k on src
                lam_rr = lam[slots[rr]]
                lam_upd, gamma, primal = dual_step(
                    lam_rr, resid_old, resid_new, cfg
                )
                o = own[rr]
                lam = lam.at[slots[rr]].set(jnp.where(o > 0, lam_upd, lam_rr))
                primal_sq = primal_sq + o * primal
                gamma_sum = gamma_sum + o * gamma
                gamma_min = jnp.minimum(
                    gamma_min, jnp.where(o > 0, gamma, jnp.inf)
                )
                lag_pen = lag_pen + o * (
                    jnp.sum(lam_upd * resid_new)
                    + 0.5 * cfg.rho * jnp.sum(resid_new**2)
                )
                if cfg.telemetry:
                    resid_max = jnp.maximum(
                        resid_max,
                        jnp.where(o > 0, jnp.max(jnp.abs(resid_new)), 0.0),
                    )
            diag = {
                "obj": _local_objective(stats_t, U, A, cfg, m),
                "lag_pen": lag_pen,
                "primal_sq": primal_sq,
                "gamma_sum": gamma_sum,
                "gamma_min": gamma_min,
            }
            if cfg.telemetry:
                diag["resid_max"] = resid_max
                diag["agg_rejected"] = agg_rejected
                # every scheduled round delivers a fresh view (synchronous
                # compiled schedule): rmask counts this shard's receptions
                diag["msgs_delivered"] = jnp.sum(rmask)
                diag["msgs_stale"] = jnp.zeros((), dtype)
                diag["msgs_dropped"] = jnp.zeros((), dtype)
            return AgentState(U, A, lam), diag

        final, diags = jax.lax.scan(
            step, AgentState(U0, A0, lam0), None, length=n_seg
        )
        diags = jax.tree_util.tree_map(lambda x: x[:, None], diags)
        return final.U[None], final.A[None], final.lam[None], diags

    def tape_body(*ops, n_seg):
        """In-mesh tape replay: the Jacobian sweep with aged, sender-
        corrupted, liveness-masked neighbor views served from each shard's
        OWN published-U ring buffer (exchange.ShardedGraphExchange tape
        driver).  Mirrors fit_async tick semantics: membership join
        warm-start, straggler freeze, synchronous true-residual duals with
        dead-edge masking, optional aged-dual shipping."""
        (G_blk, R_blk, n_blk, t2_blk, pre_blk, deg_blk, tau_blk, zeta_blk,
         slot_blk, own_blk, U_blk, A_blk, lam_blk, hist_blk) = ops[:14]
        idx = 14
        lam_hist_blk = None
        if aged_duals:
            lam_hist_blk = ops[idx]
            idx += 1
        rmask_t = None
        if cfg.telemetry:
            # (rounds,) schedule mask of this shard — distinguishes rounds
            # never scheduled from scheduled-but-dead (dropped) receptions
            rmask_t = ops[idx][0]
            idx += 1
        age_b, live_b, act_b = ops[idx:idx + 3]
        idx += 3
        if is_adv:
            code_b, noise_b, mem_b, memp_b = ops[idx:idx + 4]
            idx += 4
        ticks = ops[idx]
        stats_t = SufficientStats(
            G=G_blk[0], R=R_blk[0], n=n_blk[0], t2=t2_blk[0]
        )
        precomp = _shard_precomp(pre_blk)
        deg_t, tau_t, zeta_t = deg_blk[0], tau_blk[0], zeta_blk[0]
        slots, own = slot_blk[0], own_blk[0]
        init_u = jnp.ones((L, r), dtype)        # the all-ones U^0 publish
        tau0 = jnp.asarray(cfg.tau, dtype)
        offset_c = jnp.asarray(offset_np, dtype) if is_adv else None

        def step(carry, xs_t):
            if aged_duals:
                U, A, lam, hist, lam_hist = carry
            else:
                U, A, lam, hist = carry
                lam_hist = None
            if is_adv:
                (age_row, live_row, act_t, k,
                 code, noise_t, mem_t, memp_t) = xs_t
            else:
                age_row, live_row, act_t, k = xs_t
                code = noise_t = None
            # send-side aged + corrupted exchange from each sender's OWN
            # ring buffer; receptions masked by per-round edge liveness
            recv = sgx.tape_exchange(
                hist, k, age_row, depth, code=code, noise_t=noise_t,
                offset=offset_c, init_u=init_u,
            )
            deg_eff = jnp.sum(live_row)         # live degree (exact fp32)
            agg_rejected = jnp.zeros((), dtype)
            if robust_agg is None:
                # round-order sum; `* live_row[rr]` is an exact bitwise
                # pass-through (x * 1.0) on a zero-adversary tape
                neigh = functools.reduce(
                    jnp.add,
                    [recv[rr] * live_row[rr] for rr in range(n_rounds)],
                )
                center = neigh / jnp.maximum(deg_eff, 1.0)
            else:
                V = jnp.stack(list(recv) + [U], axis=0)
                Mv = jnp.concatenate([live_row, jnp.ones((1,), dtype)])
                center = robust_agg(V, Mv)
                neigh = deg_eff * center
                if cfg.telemetry:
                    agg_rejected = jnp.sum(
                        exchange.aggregator_audit(V, Mv, center)
                    )
            tau_eff = (
                tau0 + deg_eff if (is_adv and scalar_tau) else tau_t
            )
            if aged_duals:
                ct_lam = sgx.tape_ct_lam(
                    lam, slots, own, live_row,
                    aged={
                        "lam_hist": lam_hist, "k": k, "age_row": age_row,
                        "depth": depth, "code": code, "noise": noise_t,
                        "offset": offset_c,
                    },
                )
            else:
                ct_lam = sgx.tape_ct_lam(lam, slots, own, live_row)
            if is_adv:
                # a (re)joining agent warm-starts from the aggregate of
                # its live neighbors (kept at U when joining in isolation)
                join = (mem_t * (1.0 - memp_t)) > 0
                U_base = jnp.where(join & (deg_eff > 0), center, U)
            else:
                U_base = U
            msgs = NeighborMsgs(
                neigh, ct_lam, deg_eff if is_adv else deg_t, tau_eff,
                zeta_t,
            )
            U_upd, A_upd = agent_update(
                stats_t, AgentState(U_base, A, lam), msgs, cfg,
                m_total=m, precomp=precomp,
            )
            U_new = jnp.where(act_t > 0, U_upd, U_base)  # straggler freeze
            A_new = jnp.where(act_t > 0, A_upd, A)
            # synchronous dual bookkeeping on the TRUE residuals (fresh
            # exchanges, like fit_async's edge_diff) with dead edges
            # masked to zero so their duals freeze exactly
            nb_old = sgx.exchange(U_base)
            nb_new = sgx.exchange(U_new)
            primal_sq = jnp.zeros((), dtype)
            gamma_sum = jnp.zeros((), dtype)
            gamma_min = jnp.asarray(jnp.inf, dtype)
            lag_pen = jnp.zeros((), dtype)
            resid_max = jnp.zeros((), dtype)
            for rr in range(n_rounds):
                resid_new = (U_new - nb_new[rr]) * live_row[rr]
                resid_old = (U_base - nb_old[rr]) * live_row[rr]
                lam_rr = lam[slots[rr]]
                lam_upd, gamma, primal = dual_step(
                    lam_rr, resid_old, resid_new, cfg
                )
                o = own[rr]
                lam = lam.at[slots[rr]].set(
                    jnp.where(o > 0, lam_upd, lam_rr)
                )
                primal_sq = primal_sq + o * primal
                gamma_sum = gamma_sum + o * gamma
                gamma_min = jnp.minimum(
                    gamma_min, jnp.where(o > 0, gamma, jnp.inf)
                )
                lag_pen = lag_pen + o * (
                    jnp.sum(lam_upd * resid_new)
                    + 0.5 * cfg.rho * jnp.sum(resid_new**2)
                )
                if cfg.telemetry:
                    resid_max = jnp.maximum(
                        resid_max,
                        jnp.where(o > 0, jnp.max(jnp.abs(resid_new)), 0.0),
                    )
            hist = hist.at[jnp.mod(k, depth)].set(U_new)
            if aged_duals:
                lam_hist = lam_hist.at[jnp.mod(k, depth)].set(lam)
            diag = {
                "obj": _local_objective(stats_t, U_new, A_new, cfg, m),
                "lag_pen": lag_pen,
                "primal_sq": primal_sq,
                "gamma_sum": gamma_sum,
                "gamma_min": gamma_min,
            }
            if cfg.telemetry:
                fresh = (age_row == 1).astype(dtype)
                diag["resid_max"] = resid_max
                diag["agg_rejected"] = agg_rejected
                # live receptions split by age (age==1 is a fresh current-
                # round view, matching fit_async's accounting); scheduled
                # rounds whose edge is dead this tick count as dropped
                diag["msgs_delivered"] = jnp.sum(live_row * fresh)
                diag["msgs_stale"] = jnp.sum(live_row * (1.0 - fresh))
                diag["msgs_dropped"] = jnp.sum(rmask_t - live_row)
            carry = (U_new, A_new, lam, hist)
            if aged_duals:
                carry = carry + (lam_hist,)
            return carry, diag

        carry0 = (U_blk[0], A_blk[0], lam_blk[0], hist_blk[0])
        if aged_duals:
            carry0 = carry0 + (lam_hist_blk[0],)
        xs = (age_b[:, 0], live_b[:, 0], act_b[:, 0], ticks)
        if is_adv:
            xs = xs + (code_b[:, 0], noise_b[:, 0], mem_b[:, 0],
                       memp_b[:, 0])
        final, diags = jax.lax.scan(step, carry0, xs, length=n_seg)
        diags = jax.tree_util.tree_map(lambda x: x[:, None], diags)
        outs = tuple(x[None] for x in final)
        return outs + (diags,)

    spec_batched = P(axes_t)

    def _revalidate_suffix(k0, n):
        """A resumed mid-tape segment re-checks the suffix it will replay
        (the async runner's contract, same here)."""
        from repro.netsim.events import EventTape as _ET, validate_tape

        if is_adv:
            from repro.netsim.adversary import AdversaryTape as _AT

            validate_tape(
                _AT(
                    age=ages_np[k0:k0 + n], active=active_np[k0:k0 + n],
                    attack=attack_np[k0:k0 + n],
                    noise=noise_np[k0:k0 + n], offset=offset_np,
                    member=member_np[k0:k0 + n],
                ),
                g, start=k0,
            )
        else:
            validate_tape(
                _ET(age=ages_np[k0:k0 + n], active=active_np[k0:k0 + n]),
                g, start=k0,
            )

    def segment_fn(state, n):
        if tape is None:
            shard_fn = jax.shard_map(
                functools.partial(body, n_seg=n),
                mesh=mesh,
                check_vma=False,
                in_specs=(spec_batched,) * 15,
                out_specs=(
                    spec_batched, spec_batched, spec_batched,
                    P(None, axes_t),
                ),
            )
            U, A, lam, diags = shard_fn(
                G_all, R_all, n_all, t2_all, pre_all, deg_all, tau_all,
                zeta_all, slot_all, own_all, pmask_all, rmask_all,
                state.U, state.A, state.lam
            )
            diags = _assemble_sharded_diags(diags, g.n_edges, L * cfg.r)
            if cfg.telemetry:
                model = modeled_floats_per_iter(
                    "sharded_graph", L=L, r=cfg.r, n_edges=g.n_edges
                )
                diags["comm_floats"] = jnp.full((n,), float(model), dtype)
            return state._replace(U=U, A=A, lam=lam, k=state.k + n), diags

        k0 = int(jax.device_get(state.k))
        if k0 + n > cfg.iters:
            raise ValueError(
                f"segment [{k0}, {k0 + n}) runs past the tape "
                f"({cfg.iters} ticks)"
            )
        if k0 > 0 and n > 0:
            _revalidate_suffix(k0, n)
        ops = [
            G_all, R_all, n_all, t2_all, pre_all, deg_all, tau_all,
            zeta_all, slot_all, own_all, state.U, state.A, state.lam,
            state.hist,
        ]
        specs = [spec_batched] * 14
        if aged_duals:
            ops.append(state.lam_hist)
            specs.append(spec_batched)
        if cfg.telemetry:
            ops.append(rmask_all)
            specs.append(spec_batched)
        # per-tick rows sliced [k0, k0 + n) host-side and threaded with
        # the ABSOLUTE tick, so ring-buffer slots (k - age) mod depth are
        # segment-invariant and mid-tape resume replays bitwise
        ops += [
            jnp.asarray(send_age_np[k0:k0 + n], jnp.int32),
            jnp.asarray(live_np[k0:k0 + n], dtype),
            jnp.asarray(active_np[k0:k0 + n], dtype),
        ]
        specs += [P(None, axes_t)] * 3
        if is_adv:
            ops += [
                jnp.asarray(attack_np[k0:k0 + n], jnp.int32),
                jnp.asarray(noise_np[k0:k0 + n], dtype),
                jnp.asarray(member_np[k0:k0 + n], dtype),
                jnp.asarray(member_prev_np[k0:k0 + n], dtype),
            ]
            specs += [P(None, axes_t)] * 4
        ops.append(jnp.arange(k0, k0 + n, dtype=jnp.int32))
        specs.append(P(None))
        out_specs = [spec_batched] * (5 if aged_duals else 4)
        out_specs.append(P(None, axes_t))
        shard_fn = jax.shard_map(
            functools.partial(tape_body, n_seg=n),
            mesh=mesh,
            check_vma=False,
            in_specs=tuple(specs),
            out_specs=tuple(out_specs),
        )
        res = shard_fn(*ops)
        if aged_duals:
            U, A, lam, hist, lam_hist, diags = res
        else:
            U, A, lam, hist, diags = res
            lam_hist = None
        diags = _assemble_sharded_diags(diags, g.n_edges, L * cfg.r)
        diags["tape_cursor"] = jnp.arange(k0, k0 + n, dtype=jnp.int32)
        if cfg.telemetry:
            model = modeled_floats_per_iter(
                "sharded_graph", L=L, r=cfg.r, n_edges=g.n_edges
            )
            diags["comm_floats"] = jnp.full((n,), float(model), dtype)
        return RunState(
            U=U, A=A, lam=lam, k=state.k + n, hist=hist,
            lam_hist=lam_hist,
        ), diags

    return Runner("sharded_graph", cfg, init_fn, segment_fn, shardings_fn)


def fit_sharded_graph(
    stats: SufficientStats,
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    g: Graph,
    cfg: ConsensusConfig,
    *,
    schedule: Sequence[Sequence[int]] | None = None,
    tape=None,
    aged_duals: bool = False,
):
    """Consensus ADMM over ANY connected ``Graph`` on the mesh — one
    ``run_segment`` of :func:`_make_sharded_graph_runner` (see its
    docstring for the edge-schedule compilation and Gauss-Seidel phase
    semantics) driven to completion.  ``tape=`` replays an ``EventTape`` /
    ``AdversaryTape`` INSIDE the mesh (the exchange layer's tape driver;
    requires the Jacobian sweep, i.e. ``schedule=None``).  Returns
    ``(U, A, diagnostics)``, the :func:`fit_sharded` contract (plus
    ``tape_cursor`` rows when a tape is replayed).
    """
    runner = _make_sharded_graph_runner(
        stats, mesh, agent_axes, g, cfg, schedule=schedule, tape=tape,
        aged_duals=aged_duals,
    )
    state, diags = runner.run()
    return state.U, state.A, diags


def make_runner(
    stats: SufficientStats,
    g: Graph | None = None,
    cfg: ConsensusConfig | None = None,
    *,
    executor: str = "dense",
    mesh: jax.sharding.Mesh | None = None,
    agent_axes: Sequence[str] | None = None,
    schedule: Sequence[Sequence[int]] | None = None,
    staleness: int = 0,
    order: str = "fixed",
    tape=None,
    aged_duals: bool = False,
) -> Runner:
    """Build the segmented :class:`Runner` for any of the five executors.

    The single construction site behind every ``fit_*`` and the
    checkpointable ``fit(..., checkpoint_dir=...)`` path:

      executor="dense"          needs (stats, g, cfg)
      executor="colored"        + schedule/staleness/order
      executor="async"          + tape (aged_duals optional); g required
      executor="sharded"        needs (stats, cfg) + mesh/agent_axes
      executor="sharded_graph"  + g (+ optional vertex schedule, or a
                                tape= for in-mesh EventTape/AdversaryTape
                                replay — mutually exclusive)

    ``tape=`` on ``executor="sharded"`` delegates to the graph-compiled
    executor (the ring/torus fast path has no tape driver) and therefore
    requires ``g`` — the Graph whose edge order the tape was sampled on.

    ``runner.run()`` reproduces the corresponding ``fit_*`` exactly;
    ``runner.run_segment`` splits the same computation at checkpointable
    boundaries (see :class:`Runner` for the bitwise guarantee).
    """
    if cfg is None:
        raise ValueError("make_runner requires a ConsensusConfig")
    tr = obs_trace.current()
    if tr is not None:
        with tr.span("compile", executor=executor):
            return _dispatch_runner(
                stats, g, cfg, executor=executor, mesh=mesh,
                agent_axes=agent_axes, schedule=schedule,
                staleness=staleness, order=order, tape=tape,
                aged_duals=aged_duals,
            )
    return _dispatch_runner(
        stats, g, cfg, executor=executor, mesh=mesh, agent_axes=agent_axes,
        schedule=schedule, staleness=staleness, order=order, tape=tape,
        aged_duals=aged_duals,
    )


def _dispatch_runner(
    stats, g, cfg, *, executor, mesh, agent_axes, schedule, staleness,
    order, tape, aged_duals,
) -> Runner:
    if executor == "dense":
        return _make_dense_runner(stats, g, cfg)
    if executor == "colored":
        return _colored_runner(
            stats, g, cfg, schedule=schedule, staleness=staleness, order=order
        )
    if executor == "async":
        from repro.netsim.executor import make_async_runner

        return make_async_runner(stats, g, cfg, tape, aged_duals=aged_duals)
    if executor == "sharded":
        if tape is not None or aged_duals:
            if g is None:
                raise ValueError(
                    "executor='sharded' with tape= needs g= — the Graph "
                    "whose edge order the tape was sampled on (the replay "
                    "runs on the graph-compiled executor)"
                )
            return _make_sharded_graph_runner(
                stats, mesh, agent_axes, g, cfg, schedule=schedule,
                tape=tape, aged_duals=aged_duals,
            )
        return _make_sharded_runner(stats, mesh, agent_axes, cfg)
    if executor == "sharded_graph":
        return _make_sharded_graph_runner(
            stats, mesh, agent_axes, g, cfg, schedule=schedule,
            tape=tape, aged_duals=aged_duals,
        )
    raise ValueError(
        f"unknown executor {executor!r}; expected one of 'dense', "
        f"'colored', 'async', 'sharded', 'sharded_graph'"
    )
