"""DMTL-ELM — decentralized multi-task ELM (paper §III, Algorithm 2) and its
first-order variant FO-DMTL-ELM (Algorithm 3): the dense-graph entry point.

Problem (eq. 12):
    min_{U, A} sum_t ( 1/2 ||H_t U_t A_t - T_t||^2 + mu1/(2m) ||U_t||^2
                       + mu2/2 ||A_t||^2 )      s.t.  sum_t C_t U_t = 0,
with edge-consensus constraints over a connected graph G, solved by a hybrid
Jacobian (across agents) / Gauss-Seidel (U then A within an agent) proximal
multi-block ADMM.

Since the refactor to the stats-first engine (``repro.core.engine``), this
module holds no update math of its own: ``dmtl_elm_fit`` reduces the data to
:class:`~repro.core.engine.SufficientStats` via the single Gram producer and
dispatches into ``engine.fit_dense`` — the vmap + dense-incidence executor
wrapped around the ONE shared ``engine.agent_update`` body.  The shard_map
executors (``repro.core.sharded_dmtl`` / ``engine.fit_sharded`` for the
mesh ring/torus, ``engine.fit_sharded_graph`` for any connected graph via
the compiled ppermute edge schedule), the Gauss-Seidel sweeps
(``engine.fit_colored``) and the event-driven network simulator
(``engine.fit_async`` / ``repro.netsim``) wrap the *same* body, so all
execution modes agree by construction.

Solver choice (cfg.u_solver — the ``engine.U_SOLVERS`` registry):
  * "kron"      — the paper's eq. (19) Kronecker inverse (faithful; O(L^3 r^3));
  * "sylvester" — exact O(L^3 + r^3) double-eigendecomposition; eigh(G_t) is
                  hoisted out of the ADMM scan (iteration cost O(L^2 r + r^3));
  * "cg"        — matrix-free conjugate gradients, matmul-only;
  * "pcg"       — CG with the Gram-diagonal (Jacobi) preconditioner, the
                  backbone-scale choice when diag(G) carries the conditioning;
  * FO mode (cfg.first_order=True) needs no solve at all (eq. 23).
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.engine import ConsensusConfig, DenseState
from repro.core.graph import Graph
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace

# Public names kept for API compatibility: the config and stacked-state types
# now live in the engine.
DMTLELMConfig = ConsensusConfig
DMTLELMState = DenseState


def augmented_lagrangian(
    H, T, U, A, lam, S, mu1, mu2, rho,
) -> jax.Array:
    """Paper eq. (13). S: signed incidence (E, m)."""
    m = H.shape[0]
    resid = jnp.einsum("mnl,mlr,mrd->mnd", H, U, A) - T
    f = 0.5 * jnp.sum(resid**2)
    g1 = 0.5 * mu1 / m * jnp.sum(U**2)
    g2 = 0.5 * mu2 * jnp.sum(A**2)
    CU = jnp.einsum("em,mlr->elr", S, U)  # edge residuals U_s - U_e
    lin = jnp.sum(lam * CU)
    quad = 0.5 * rho * jnp.sum(CU**2)
    return f + g1 + g2 + lin + quad


def consensus_residual(U: jax.Array, S: jax.Array) -> jax.Array:
    """RMS edge disagreement ||C U|| / sqrt(E L r)."""
    CU = jnp.einsum("em,mlr->elr", S, U)
    return jnp.sqrt(jnp.mean(CU**2))


def dmtl_objective(H, T, U, A, mu1, mu2) -> jax.Array:
    """The primal objective of eq. (12) (no dual/penalty terms)."""
    m = H.shape[0]
    resid = jnp.einsum("mnl,mlr,mrd->mnd", H, U, A) - T
    return (
        0.5 * jnp.sum(resid**2)
        + 0.5 * mu1 / m * jnp.sum(U**2)
        + 0.5 * mu2 * jnp.sum(A**2)
    )


def dmtl_elm_fit(
    H: jax.Array,
    T: jax.Array,
    g: Graph,
    cfg: DMTLELMConfig,
    feature_map=None,
) -> tuple[DMTLELMState, dict]:
    """Run Algorithm 2 (or Algorithm 3 if cfg.first_order) to cfg.iters.

    H: (m, N, L); T: (m, N, d). Returns final state + diagnostics dict with
    per-iteration 'objective' (primal, eq. 12), 'lagrangian' (eq. 13) and
    'consensus' residuals.  The Gram reduction honors
    ``cfg.stats_precision`` ("bf16" streams H/T tiles at half HBM traffic
    with fp32 accumulators, "int8" per-tile-quantized 1-byte tiles) and
    ``cfg.stats_producer`` — with ``stats_producer="fused"`` the first
    argument is the RAW input X (m, N, d_in) and ``feature_map=`` (the
    frozen hidden layer, applied inside the Gram kernel) is required.
    """
    stats = engine.produce_stats(
        H, T, producer=cfg.stats_producer, feature_map=feature_map,
        precision=cfg.stats_precision,
    )
    return engine.fit_dense(stats, g, cfg)


def fit(
    H: jax.Array,
    T: jax.Array,
    g: Graph,
    cfg: DMTLELMConfig,
    *,
    executor: str = "dense",
    mesh: "jax.sharding.Mesh | None" = None,
    agent_axes=None,
    schedule=None,
    staleness: int = 0,
    order: str = "fixed",
    tape=None,
    channel=None,
    aged_duals: bool = False,
    feature_map=None,
    checkpoint_dir=None,
    checkpoint_every: int = 0,
    resume: bool = False,
    telemetry: bool = False,
    trace_dir=None,
    health=None,
):
    """One entry point, five executors over the SAME ``agent_update`` body.

    * ``executor="dense"``   — Jacobian sweep, vmap + edge-list gathering
      (``engine.fit_dense``); the paper's synchronous scheme.
    * ``executor="colored"`` — Gauss-Seidel colored sweeps
      (``engine.fit_colored``); ``schedule`` overrides the greedy
      ``g.chromatic_schedule()``, ``staleness`` delays neighbor messages
      by k rounds, and ``order="gauss_southwell"`` resweeps the classes
      largest-primal-residual-first each iteration (see the engine
      docstring for the trade-offs).
    * ``executor="sharded"`` — one agent per shard of ``mesh[agent_axes]``
      (``engine.fit_sharded`` / ``engine.fit_sharded_graph``).  ANY
      connected ``g`` is accepted: when ``g`` is the mesh ring/torus (up
      to per-edge orientation — the consensus problem is orientation-
      invariant) the fast nearest-neighbor ring path runs; any other
      topology is compiled to a ≤ Δ+1-round ppermute edge schedule by
      ``engine.fit_sharded_graph``.  ``schedule`` (e.g.
      ``g.chromatic_schedule()``) runs phase-masked Gauss-Seidel sweeps
      inside shard_map via the compiler path.  ``tape=`` / ``channel=``
      replay a recorded lossy (and optionally Byzantine / churning)
      network IN-MESH via the compiled-schedule tape driver
      (``repro.core.exchange.ShardedGraphExchange``): per-shard ring
      buffers of published iterates age-select what each ppermute ships,
      so the sharded run agrees with ``executor="async"`` on the same
      tape (bitwise on zero-delay tapes, psum-reduction-order tolerance
      otherwise).  ``aged_duals=True`` ships duals through the lossy
      channel too.
    * ``executor="async"``   — event-driven asynchrony
      (``engine.fit_async`` / ``repro.netsim``): pass either a precompiled
      ``tape=`` (an ``EventTape``) or a ``channel=`` (a ``ChannelModel``,
      sampled here over ``cfg.iters`` ticks of ``g``); ``aged_duals=True``
      additionally ships the received duals through the lossy channel.

    The stats pass honors ``cfg.stats_producer``: with ``"fused"`` the
    first argument is the RAW per-agent input X (m, N, d_in) and
    ``feature_map=`` is required — the frozen ELM hidden layer runs inside
    the Gram kernel, so H never materializes (``engine.produce_stats``).
    With ``executor="sharded"`` the stats pass runs per shard of
    ``mesh[agent_axes]``: each device reduces its own agents' rows, and the
    statistics reach the runner already placed by agent.

    Executor-specific kwargs are validated: ``staleness``/``order`` only
    apply to "colored", ``schedule`` to "colored"/"sharded",
    ``mesh``/``agent_axes`` only to "sharded", ``tape``/``channel``/
    ``aged_duals`` only to "async" or "sharded", and ``feature_map`` only to
    ``cfg.stats_producer="fused"``; passing them elsewhere raises rather
    than silently ignoring them.

    Checkpointable execution (ANY executor): ``checkpoint_dir=`` drives
    the run through ``repro.checkpoint.run_checkpointed`` — the engine's
    segmented ``RunState`` core saves a resumable snapshot (state + full
    diagnostics prefix) every ``checkpoint_every`` iterations (0 = once,
    at the end), and ``resume=True`` restarts from the latest snapshot
    when one exists.  A resumed run returns the final state and FULL
    diagnostics trajectory bitwise identical to the uninterrupted run —
    the engine's segment property, which holds for all five executors and
    both dual modes.

    Observability (``repro.obs``): ``telemetry=True`` sets
    ``cfg.telemetry`` — the per-iteration comm/aggregator counters ride
    the diagnostics dict (see the engine docstring's "Telemetry
    extension"); ``trace_dir=`` activates host-side span tracing around
    the stats pass, runner compile, and every segment, then writes
    ``trace.json`` (Chrome trace format — load it in Perfetto),
    ``spans.jsonl``, and a run report (``report.md`` / ``report.json``)
    under that directory; ``health=`` (``True`` or a
    ``repro.obs.health.HealthConfig``) arms the post-segment run-health
    monitor — it requires ``checkpoint_dir=`` because the check runs at
    checkpoint segment boundaries, stops a NaN/diverging/stalled run
    early, and stamps a machine-readable ``dnf_reason`` into the final
    snapshot's metadata.

    dense/colored/async return ``(DMTLELMState, diagnostics)``; sharded
    returns the engine's ``(U, A, diagnostics)`` sharded-output contract.
    All executors report the same diagnostics keys ('objective',
    'lagrangian', 'consensus', 'gamma', 'gamma_min', 'primal_sq').
    """
    # All validation happens BEFORE the Gram reduction: a bad call must not
    # pay the O(m N L^2) stats pass just to raise.
    if cfg.stats_producer not in engine.STATS_PRODUCERS:
        raise ValueError(
            f"unknown cfg.stats_producer {cfg.stats_producer!r}; expected "
            f"one of {engine.STATS_PRODUCERS}"
        )
    if cfg.stats_producer == "fused" and feature_map is None:
        raise ValueError(
            "cfg.stats_producer='fused' needs feature_map= (the frozen "
            "ELMFeatureMap applied inside the Gram kernel)"
        )
    if cfg.stats_producer != "fused" and feature_map is not None:
        raise ValueError(
            "feature_map= only applies to cfg.stats_producer='fused', got "
            f"stats_producer={cfg.stats_producer!r}"
        )
    if cfg.aggregator not in engine.AGGREGATORS:
        raise ValueError(
            f"unknown cfg.aggregator {cfg.aggregator!r}; registered: "
            f"{sorted(engine.AGGREGATORS)}"
        )
    if executor not in ("dense", "sharded", "colored", "async"):
        raise ValueError(
            f"unknown executor {executor!r}; expected 'dense', 'sharded', "
            f"'colored' or 'async'"
        )
    if executor not in ("colored", "sharded") and schedule is not None:
        raise ValueError(
            "schedule= only applies to executor='colored' or 'sharded', "
            f"got executor={executor!r}"
        )
    if executor != "colored" and staleness != 0:
        raise ValueError(
            f"staleness= only applies to executor='colored', "
            f"got executor={executor!r}"
        )
    if executor != "colored" and order != "fixed":
        raise ValueError(
            f"order= only applies to executor='colored', "
            f"got executor={executor!r}"
        )
    if executor != "sharded" and (mesh is not None or agent_axes is not None):
        raise ValueError(
            f"mesh=/agent_axes= only apply to executor='sharded', "
            f"got executor={executor!r}"
        )
    if executor not in ("async", "sharded") and (
        tape is not None or channel is not None or aged_duals
    ):
        raise ValueError(
            f"tape=/channel=/aged_duals= only apply to executor='async' or "
            f"'sharded', got executor={executor!r}"
        )
    if executor == "async":
        if (tape is None) == (channel is None):
            raise ValueError(
                "executor='async' needs exactly one of tape= (a precompiled "
                "EventTape) or channel= (a ChannelModel to sample)"
            )
        if channel is not None:
            tape = channel.sample(g, cfg.iters)
    if executor == "sharded":
        if tape is not None and channel is not None:
            raise ValueError(
                "executor='sharded' takes at most one of tape= (a "
                "precompiled EventTape/AdversaryTape) or channel= (a "
                "ChannelModel to sample)"
            )
        if channel is not None:
            tape = channel.sample(g, cfg.iters)
        if aged_duals and tape is None:
            raise ValueError(
                "aged_duals=True needs a tape= or channel= to replay"
            )
    if checkpoint_dir is None and (checkpoint_every or resume):
        raise ValueError(
            "checkpoint_every=/resume= need checkpoint_dir= to point at "
            "the snapshot directory"
        )
    if checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0, got {checkpoint_every}"
        )
    if health is not None and health is not False and checkpoint_dir is None:
        raise ValueError(
            "health= monitoring runs at checkpoint segment boundaries; "
            "pass checkpoint_dir= (and checkpoint_every=) to arm it"
        )
    use_graph_path = False
    if executor == "sharded":
        if mesh is None or agent_axes is None:
            raise ValueError(
                "executor='sharded' needs mesh= and agent_axes="
            )
        sizes = [mesh.shape[a] for a in agent_axes]
        n_agents = 1
        for s in sizes:
            n_agents *= s
        if g.m != n_agents:
            raise ValueError(
                f"graph has m={g.m} agents but prod(agent axes)={n_agents}"
            )
        # orientation-insensitive: a ring written with a flipped edge is the
        # same consensus problem (the dual just changes sign) and takes the
        # fast ppermute ring path; everything else goes to the compiler
        # in-mesh tape replay runs only on the compiled-schedule path (the
        # torus fast path has no per-edge round structure to mask)
        use_graph_path = (
            schedule is not None
            or tape is not None
            or any(s < 2 for s in sizes)
            or not engine.graph_matches_torus(g, sizes)
        )
    if telemetry:
        cfg = dataclasses.replace(cfg, telemetry=True)
    tracer = None
    trace_ctx = contextlib.nullcontext()
    if trace_dir is not None:
        tracer = obs_trace.Tracer()
        trace_ctx = obs_trace.use(tracer)
    exec_name = executor
    if executor == "sharded":
        exec_name = "sharded_graph" if use_graph_path else "sharded"
    with trace_ctx:
        # sharded executors: each device reduces its own agents' rows
        stats = engine.produce_stats(
            H, T, producer=cfg.stats_producer, feature_map=feature_map,
            precision=cfg.stats_precision, mesh=mesh, agent_axes=agent_axes,
        )
        runner = engine.make_runner(
            stats, g, cfg, executor=exec_name, mesh=mesh,
            agent_axes=agent_axes, schedule=schedule, staleness=staleness,
            order=order, tape=tape, aged_duals=aged_duals,
        )
        if checkpoint_dir is not None:
            from repro.checkpoint import run_checkpointed

            state, diags = run_checkpointed(
                runner, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                health=health,
            )
        else:
            state, diags = runner.run()
    if tracer is not None:
        tracer.export(trace_dir)
        obs_report.write(
            trace_dir, diags, tracer.spans,
            meta={
                "executor": exec_name, "m": g.m, "n_edges": g.n_edges,
                "iters": cfg.iters, "aggregator": cfg.aggregator,
                "telemetry": bool(cfg.telemetry),
            },
        )
    if executor == "sharded":
        return state.U, state.A, diags
    return DenseState(state.U, state.A, state.lam), diags


def dmtl_elm_predict(U_t: jax.Array, A_t: jax.Array, H: jax.Array) -> jax.Array:
    return H @ U_t @ A_t
