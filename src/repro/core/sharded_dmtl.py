"""Sharded DMTL-ELM: one agent per mesh shard, consensus over the ICI torus.

This is the TPU-native realization of Algorithm 2: agents live along one or
more mesh axes (e.g. ``("data",)`` single-pod or ``("pod", "data")``
multi-pod) and the consensus graph is the corresponding **ring / torus** —
along every agent axis, agent t owns the directed edge (t, t+1 mod n) and
exchanges its local subspace ``U_t`` with ring neighbors via
``jax.lax.ppermute``, the paper's "share with the neighbouring agents" step
mapped onto nearest-neighbor ICI links.

Since the refactor to the stats-first engine, all update math lives in
``repro.core.engine``: ``engine.ring_iteration`` is the per-shard message
plumbing around the ONE shared ``engine.agent_update`` body (the same body
the dense vmap executor runs), ``engine.fit_sharded`` is the torus
shard_map-building driver, and ``engine.fit_sharded_graph`` compiles ANY
connected ``Graph`` to a ≤ Δ+1-round ppermute edge schedule (pass ``g=`` to
either entry point below to run a non-torus topology on the mesh).  This
module keeps the thin, historically-named entry points:
``dmtl_fit_from_stats`` (streaming-statistics path used by
``repro.core.heads``) and ``dmtl_elm_fit_sharded`` (raw-data path).

Per ADMM iteration each agent communicates 3 x ppermute(U) +
1 x ppermute(lambda) per agent axis on the torus fast path — the paper's
O(k L r) communication volume (EXPERIMENTS.md reproduces the Fig. 6
trade-off from these counts); the compiled-graph path costs
``rounds * (phases + 1)`` U-ppermutes + ``rounds`` dual-ppermutes with
``rounds ≤ Δ+1`` (the phase-0 gather doubles as the dual resid_old
exchange).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Sequence

import jax

from repro.core import engine
from repro.core.engine import AgentState as ShardedDMTLState  # noqa: F401
from repro.core.engine import ConsensusConfig as DMTLELMConfig
from repro.core.engine import SufficientStats, ring_iteration  # noqa: F401
from repro.core.graph import Graph
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace


def _dispatch_sharded(stats, mesh, agent_axes, cfg, g: Optional[Graph], *,
                      tape=None, channel=None, aged_duals: bool = False,
                      checkpoint_dir=None, checkpoint_every: int = 0,
                      resume: bool = False, telemetry: bool = False,
                      trace_dir=None, health=None):
    """Torus fast path when ``g`` is None or matches the mesh torus (up to
    edge orientation); the compiled edge-schedule executor otherwise.
    ``tape=`` / ``channel=`` force the compiled path and replay the lossy
    network in-mesh (``repro.core.exchange``); an explicit ``g`` is
    required then — the tape is indexed by g's edge list.
    ``checkpoint_dir=`` drives the run through
    ``repro.checkpoint.run_checkpointed`` (periodic resumable snapshots,
    restored onto the mesh via ``Runner.state_shardings()``).
    ``telemetry=`` / ``trace_dir=`` / ``health=`` arm the observability
    layer exactly as in ``repro.core.dmtl_elm.fit``."""
    if tape is not None and channel is not None:
        raise ValueError("pass at most one of tape= or channel=")
    if (tape is not None or channel is not None) and g is None:
        raise ValueError(
            "tape=/channel= need an explicit g= (the tape is indexed by "
            "the graph's edge list, not the mesh torus)"
        )
    if channel is not None:
        tape = channel.sample(g, cfg.iters)
    if aged_duals and tape is None:
        raise ValueError("aged_duals=True needs a tape= or channel=")
    if health is not None and health is not False and checkpoint_dir is None:
        raise ValueError(
            "health= monitoring runs at checkpoint segment boundaries; "
            "pass checkpoint_dir= (and checkpoint_every=) to arm it"
        )
    torus = g is None
    if not torus and tape is None:
        sizes = [mesh.shape[ax] for ax in agent_axes]
        torus = (
            all(s >= 2 for s in sizes)
            and engine.graph_matches_torus(g, sizes)
        )
    if telemetry:
        cfg = dataclasses.replace(cfg, telemetry=True)
    tracer = None
    trace_ctx = contextlib.nullcontext()
    if trace_dir is not None:
        tracer = obs_trace.Tracer()
        trace_ctx = obs_trace.use(tracer)
    exec_name = "sharded" if torus else "sharded_graph"
    with trace_ctx:
        runner = engine.make_runner(
            stats, g, cfg,
            executor=exec_name,
            mesh=mesh, agent_axes=agent_axes,
            tape=tape, aged_duals=aged_duals,
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
                "executor": exec_name, "m": stats.G.shape[0],
                "iters": cfg.iters, "aggregator": cfg.aggregator,
                "telemetry": bool(cfg.telemetry),
            },
        )
    return state.U, state.A, diags


def dmtl_fit_from_stats(
    G_all: jax.Array,
    HtT_all: jax.Array,
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    cfg: DMTLELMConfig,
    *,
    n: "jax.Array | None" = None,
    t2: "jax.Array | None" = None,
    g: Optional[Graph] = None,
    tape=None,
    channel=None,
    aged_duals: bool = False,
    checkpoint_dir=None,
    checkpoint_every: int = 0,
    resume: bool = False,
    telemetry: bool = False,
    trace_dir=None,
    health=None,
):
    """ADMM over precomputed per-agent Gram stats.

    G_all: (m, L, L) = H_t^T H_t; HtT_all: (m, L, d) = H_t^T T_t. This is the
    streaming-data entry point used by the backbone-scale multi-task head
    (repro.core.heads): agents accumulate Gram statistics over batches (with
    the Pallas ``gram`` kernel on TPU) and solve by consensus ADMM — the
    dataset itself never moves between agents, exactly the paper's privacy /
    communication constraint.

    ``n`` (per-agent sample counts) and ``t2`` (per-agent sum of squared
    targets) are threaded through the shard_map when given, so the
    'objective'/'lagrangian' diagnostics are exact; without them the fit is
    unchanged but those diagnostics are offset by the (constant) ||T||^2
    term.  ``g`` selects a non-torus consensus topology (compiled to a
    ppermute edge schedule); None keeps the mesh ring/torus.
    ``tape=`` (an EventTape / AdversaryTape) or ``channel=`` (a
    ChannelModel sampled over cfg.iters) replays a lossy network in-mesh
    via the exchange-layer tape driver — requires an explicit ``g``;
    ``aged_duals=True`` ships duals through the lossy channel too.
    ``checkpoint_dir=``/``checkpoint_every=``/``resume=`` make the run
    preemption-safe (see ``repro.checkpoint.run_checkpointed``);
    ``telemetry=``/``trace_dir=``/``health=`` arm the observability layer
    (``repro.obs``; same semantics as ``repro.core.dmtl_elm.fit``).
    """
    stats = SufficientStats(
        G=G_all, R=HtT_all,
        n=0.0 if n is None else n, t2=0.0 if t2 is None else t2,
    )
    return _dispatch_sharded(
        stats, mesh, agent_axes, cfg, g, tape=tape, channel=channel,
        aged_duals=aged_duals, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, resume=resume,
        telemetry=telemetry, trace_dir=trace_dir, health=health,
    )


def dmtl_elm_fit_sharded(
    H: jax.Array,
    T: jax.Array,
    mesh: jax.sharding.Mesh,
    agent_axes: Sequence[str],
    cfg: DMTLELMConfig,
    *,
    g: Optional[Graph] = None,
    tape=None,
    channel=None,
    aged_duals: bool = False,
    checkpoint_dir=None,
    checkpoint_every: int = 0,
    resume: bool = False,
    telemetry: bool = False,
    trace_dir=None,
    health=None,
):
    """Driver: H (m, N, L), T (m, N, d) sharded over agent axes; scan ADMM.

    Returns (U (m,L,r), A (m,r,d), diagnostics) with leading axis sharded the
    same way. ``m`` must equal the product of the agent-axis sizes.  ``g``
    selects a non-torus consensus topology (compiled to a ppermute edge
    schedule by ``engine.fit_sharded_graph``); None keeps the ring/torus.
    ``tape=`` or ``channel=`` replays a lossy / Byzantine network in-mesh
    (requires an explicit ``g``); ``aged_duals=True`` ages the shipped
    duals too.  ``checkpoint_dir=``/``checkpoint_every=``/``resume=`` make
    the run preemption-safe (see ``repro.checkpoint.run_checkpointed``);
    ``telemetry=``/``trace_dir=``/``health=`` arm the observability layer
    (``repro.obs``; same semantics as ``repro.core.dmtl_elm.fit``).
    """
    stats = engine.sufficient_stats(H, T, precision=cfg.stats_precision)
    return _dispatch_sharded(
        stats, mesh, agent_axes, cfg, g, tape=tape, channel=channel,
        aged_duals=aged_duals, checkpoint_dir=checkpoint_dir,
        checkpoint_every=checkpoint_every, resume=resume,
        telemetry=telemetry, trace_dir=trace_dir, health=health,
    )
