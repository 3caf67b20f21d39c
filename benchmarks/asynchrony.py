"""Async suite: the convergence-vs-delay×drop frontier of the netsim
executor (``engine.fit_async``) across consensus topologies.

For every topology the synchronous Jacobian run (``fit_dense``) sets the
yardstick — its iteration-100 objective plus 0.1% of the initial gap (the
``run_sweeps`` convention) — and each (delay scale × drop rate) cell of the
sampled ``ChannelModel`` grid reports how many simulated rounds the async
run needs to close that gap (``-1`` = DNF, with a machine-readable
``dnf_reason`` column from ``repro.obs.health.classify_run``).  Topologies
cover the mesh-native ring, the paper's star and Fig. 2(a) graphs, and the
new log-diameter overlays (``expander``/``hypercube``) the Liu et al. 2017
line motivates: the frontier shows how much delay/drop budget each
topology's mixing speed buys.

Writes ``experiments/benchmarks/async_frontier.csv`` (the CI artifact) and
emits the usual ``name,us_per_call,derived`` lines.  ``BENCH_SMOKE=1``
shrinks the grid/horizon for the CI bench-smoke job.

``run_mesh`` adds the sharded-replay rows: the SAME sampled tapes
replayed in-mesh by the exchange-layer tape driver
(``fit(executor="sharded", tape=...)`` on 8 emulated devices in a
subprocess, so the device count pins before jax initializes) next to
their ``fit_async`` oracle — per cell it reports both
iterations-to-target AND the agreement delta (max |ΔU|, max |Δobj|),
the committed evidence that in-mesh replay reproduces the simulator to
psum-reduction-order tolerance → ``mesh_async_frontier.csv``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.core import (
    DMTLELMConfig, expander, fit_dense, hypercube, paper_fig2a, ring, star,
    sufficient_stats,
)
from repro.core.engine import fit_async
from repro.data.synthetic import paper_uniform
from repro.netsim import ChannelModel, gap_target, iters_to_target, tape_summary
from repro.obs.health import classify_run

from benchmarks.common import emit, timed, write_csv


def _grid(smoke: bool):
    if smoke:
        return (0.0, 2.0), (0.0, 0.3), 80, 60
    return (0.0, 1.0, 3.0), (0.0, 0.2, 0.5), 300, 100


def run():
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    scales, drops, iters, target_at = _grid(smoke)
    L, d, r = 10, 3, 2
    topologies = [
        ("ring", ring(8)),
        ("star", star(8)),
        ("fig2a", paper_fig2a()),
        ("expander_d3", expander(8, 3, seed=0)),
        ("hypercube_3", hypercube(3)),
    ]
    rows = []
    for topo_i, (name, g) in enumerate(topologies):
        H, T = paper_uniform(jax.random.PRNGKey(17), m=g.m, N=40, L=L, d=d)
        stats = sufficient_stats(H, T)
        cfg = DMTLELMConfig(r=r, tau=2.0, zeta=1.0, delta=10.0, iters=iters)
        (_, diag_j), t_j = timed(lambda: fit_dense(stats, g, cfg))
        obj_j = np.asarray(diag_j["objective"])
        target = gap_target(obj_j, at=target_at)
        base_iters = iters_to_target(obj_j, target)
        emit(f"async/{name}/sync_baseline", t_j * 1e6,
             f"target={target:.4f};iters_to_target={base_iters}")
        # the straggler point rides the geometric channel mid-grid
        cells = [("geometric", s, p, 0.0) for s in scales for p in drops]
        cells.append(("geometric", scales[1], drops[1], 0.3))
        cells.append(("heavy_tail", scales[-1], 0.0, 0.0))
        for cell_i, (dist, scale, drop, straggle) in enumerate(cells):
            ch = ChannelModel(
                delay=dist, scale=scale, drop=drop,
                straggler_prob=straggle, seed=1000 * topo_i + cell_i,
            )
            tape = ch.sample(g, iters)
            summ = tape_summary(tape)
            # each cell runs twice: fresh duals vs duals shipped through
            # the same lossy channel (aged_duals) — the grid column shows
            # how much dual staleness costs on top of message staleness
            for aged in (False, True):
                (_, diag_a), t_a = timed(
                    lambda: fit_async(stats, g, cfg, tape, aged_duals=aged))
                obj_a = np.asarray(diag_a["objective"])
                it_a = iters_to_target(obj_a, target)
                cons = float(np.asarray(diag_a["consensus"])[-1])
                # the -1 DNF sentinel gets a machine-readable reason:
                # "" (reached) / "nan" / "objective_divergence" /
                # "consensus_stall" / "horizon" (repro.obs.health)
                why = classify_run(diag_a, it_a >= 0)
                rows.append([
                    name, g.m, g.n_edges, dist, scale, drop, straggle,
                    int(aged), summ["mean_age"], summ["max_age"],
                    summ["active_frac"], target, base_iters, it_a, why,
                    float(obj_a[-1]), cons,
                ])
                emit(
                    f"async/{name}/{dist}_s{scale}_p{drop}_st{straggle}"
                    + ("_aged" if aged else ""),
                    t_a * 1e6,
                    f"iters_to_target={it_a};"
                    f"mean_age={summ['mean_age']:.2f};"
                    f"final_consensus={cons:.2e}",
                )
    write_csv("async_frontier",
              ["topology", "m", "edges", "delay_dist", "delay_scale",
               "drop", "straggler_prob", "aged_duals", "mean_age",
               "max_age", "active_frac", "target_obj", "sync_iters",
               "async_iters", "dnf_reason", "final_obj",
               "final_consensus"], rows)


_MESH_SCRIPT = textwrap.dedent(
    """
    import os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"  # CPU emulation, never the chip
    import json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import engine
    from repro.core.graph import expander, ring
    from repro.data.synthetic import paper_uniform
    from repro.netsim import ChannelModel, gap_target, iters_to_target

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    iters, target_at = (80, 60) if smoke else (300, 100)
    topologies = [("expander_d3", expander(8, 3, seed=0))]
    cells = [(2.0, 0.3, 0.0)]
    if not smoke:
        topologies.insert(0, ("ring", ring(8)))
        cells = [(1.0, 0.2, 0.0), (3.0, 0.5, 0.0), (1.0, 0.2, 0.3)]
    L, d, r = 10, 3, 2
    mesh = Mesh(np.array(jax.devices()[:8]), ("agents",))
    rows = []
    for topo_i, (name, g) in enumerate(topologies):
        H, T = paper_uniform(jax.random.PRNGKey(17), m=g.m, N=40, L=L, d=d)
        stats = engine.sufficient_stats(H, T)
        cfg = engine.ConsensusConfig(r=r, tau=2.0, zeta=1.0, delta=10.0,
                                     iters=iters)
        _, diag_j = engine.fit_dense(stats, g, cfg)
        target = gap_target(np.asarray(diag_j["objective"]), at=target_at)
        for cell_i, (scale, drop, straggle) in enumerate(cells):
            tape = ChannelModel(
                delay="geometric", scale=scale, drop=drop,
                straggler_prob=straggle, seed=1000 * topo_i + cell_i,
            ).sample(g, iters)
            for aged in (False, True):
                st_a, dg_a = engine.fit_async(stats, g, cfg, tape,
                                              aged_duals=aged)
                t0 = time.perf_counter()
                runner = engine.make_runner(
                    stats, g, cfg, executor="sharded_graph", mesh=mesh,
                    agent_axes=("agents",), tape=tape, aged_duals=aged)
                st_s, dg_s = runner.run()
                jax.block_until_ready(st_s.U)
                t_mesh = time.perf_counter() - t0
                obj_a = np.asarray(dg_a["objective"])
                obj_s = np.asarray(dg_s["objective"])
                rows.append({
                    "topology": name, "m": g.m,
                    "delay_scale": scale, "drop": drop,
                    "straggler_prob": straggle, "aged_duals": int(aged),
                    "target_obj": target,
                    "async_iters": iters_to_target(obj_a, target),
                    "mesh_iters": iters_to_target(obj_s, target),
                    "delta_U": float(jnp.max(jnp.abs(st_a.U - st_s.U))),
                    "delta_obj": float(np.max(np.abs(obj_a - obj_s))),
                    "mesh_seconds": t_mesh,
                })
    print("MESH_ROWS:" + json.dumps(rows))
    """
)

_MESH_HEADER = ["topology", "m", "delay_scale", "drop", "straggler_prob",
                "aged_duals", "target_obj", "async_iters", "mesh_iters",
                "delta_U", "delta_obj", "mesh_seconds"]


def run_subprocess_rows(script: str) -> list:
    """Run an 8-emulated-device bench cell in a subprocess (the device
    count must pin before jax initializes) and parse its MESH_ROWS JSON."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh bench subprocess failed:\n{proc.stdout}\n{proc.stderr}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("MESH_ROWS:"):
            return json.loads(line[len("MESH_ROWS:"):])
    raise RuntimeError(f"no MESH_ROWS line:\n{proc.stdout}")


def run_mesh():
    """The in-mesh replay rows (module docstring): fit_async vs the
    sharded tape driver on the same tapes → mesh_async_frontier.csv."""
    rows = run_subprocess_rows(_MESH_SCRIPT)
    for row in rows:
        emit(
            f"async_mesh/{row['topology']}/geometric_s{row['delay_scale']}"
            f"_p{row['drop']}_st{row['straggler_prob']}"
            + ("_aged" if row["aged_duals"] else ""),
            row["mesh_seconds"] * 1e6,
            f"mesh_iters={row['mesh_iters']};"
            f"async_iters={row['async_iters']};"
            f"delta_U={row['delta_U']:.2e};delta_obj={row['delta_obj']:.2e}",
        )
    write_csv("mesh_async_frontier", _MESH_HEADER,
              [[row[k] for k in _MESH_HEADER] for row in rows])
