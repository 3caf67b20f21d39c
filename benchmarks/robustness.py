"""Robustness suite: the consensus-vs-attack frontier of the Byzantine
async executor (``fit_async`` over ``AdversaryTape``) across aggregators
and topologies.

For every topology the clean synchronous Jacobian run (``fit_dense``) sets
the yardstick — its iteration-``target_at`` objective plus 0.1% of the
initial gap (the ``run_sweeps`` convention) — and each (attack kind ×
attack rate × n_byzantine × aggregator) cell reports how many simulated
rounds the attacked run needs to close that gap (``-1`` = DNF, with a
machine-readable ``dnf_reason`` column from
``repro.obs.health.classify_run`` — a run the attack blows up to NaN and
one it merely stalls are different frontier facts).  The SAME sampled
adversary tape is replayed under every aggregator, so a row pair differs
ONLY in the defense: the frontier is the committed evidence that the
robust aggregators (``trimmed_mean`` / ``coordinate_median`` /
``krum_like``) buy convergence the plain mean loses once a Byzantine
agent fires at rate >= 1/m.  One cell per grid runs membership churn
(an agent leaves and rejoins mid-run) to pin the elastic-membership path
end to end.

Writes ``experiments/benchmarks/robustness_frontier.csv`` (the CI
artifact) and appends one dated ``bench_history/v1`` summary line to
``BENCH_history.jsonl``.  ``BENCH_SMOKE=1`` shrinks the grid/horizon for
the CI bench-smoke job.

``run_mesh`` adds the mesh Byzantine cells: the SAME adversary tape
(attacks + churn over a lossy channel) replayed by ``fit_async`` AND by
the in-mesh exchange-layer tape driver (8 emulated devices, subprocess),
per aggregator — each row carries both iterations-to-target and the
executor agreement delta (max |ΔU|, max |Δobj|) →
``mesh_robustness.csv`` plus its own dated ``BENCH_history.jsonl`` entry
under the ``robustness_mesh`` key.
"""

from __future__ import annotations

import dataclasses
import datetime
import json
import os
import textwrap

import jax
import numpy as np

from repro.core import DMTLELMConfig, expander, fit_dense, ring, star, \
    sufficient_stats
from repro.core.engine import fit_async
from repro.data.synthetic import paper_uniform
from repro.netsim import AdversaryModel, gap_target, iters_to_target
from repro.obs.health import classify_run

from benchmarks.common import OUT_DIR, emit, timed, write_csv


def _grid(smoke: bool):
    """(topologies, aggregators, cells, iters, target_at).

    Each cell is ``(kind, n_byzantine, attack_rate, churn)`` — the attack
    plan sampled once per (topology, cell) and replayed under every
    aggregator.  The churn cell schedules the LAST agent to leave a
    quarter in and rejoin at halftime.
    """
    if smoke:
        topologies = [("ring", ring(8)), ("expander_d3", expander(8, 3, seed=0))]
        aggregators = ("mean", "coordinate_median")
        iters, target_at = 80, 60
        cells = [
            ("sign_flip", 1, 1.0, ()),
            ("none", 0, 0.0, ((7, iters // 4, iters // 2),)),
        ]
        return topologies, aggregators, cells, iters, target_at
    topologies = [
        ("ring", ring(8)),
        ("star", star(8)),
        ("expander_d3", expander(8, 3, seed=0)),
    ]
    aggregators = ("mean", "trimmed_mean", "coordinate_median", "krum_like")
    iters, target_at = 300, 100
    cells = [
        ("none", 0, 0.0, ()),
        ("sign_flip", 1, 0.25, ()),
        ("sign_flip", 1, 1.0, ()),
        ("gaussian_noise", 1, 1.0, ()),
        ("colluding_offset", 2, 1.0, ()),
        ("none", 0, 0.0, ((7, iters // 4, iters // 2),)),
        ("sign_flip", 1, 0.25, ((7, iters // 4, iters // 2),)),
    ]
    return topologies, aggregators, cells, iters, target_at


def _append_history(summary: dict, key: str = "robustness") -> None:
    """One dated ``bench_history/v1`` line next to the frontier CSV — the
    same append-only idiom as ``kernels.write_bench_snapshot``, so the
    robustness trajectory is diffable across PRs."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    entry = {
        "schema": "bench_history/v1",
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "results": {key: summary},
    }
    with (OUT_DIR / "BENCH_history.jsonl").open("a") as f:
        f.write(json.dumps(entry, sort_keys=False) + "\n")


def run():
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    topologies, aggregators, cells, iters, target_at = _grid(smoke)
    L, d, r = 10, 3, 2
    rows = []
    summary: dict = {}
    for topo_i, (name, g) in enumerate(topologies):
        H, T = paper_uniform(jax.random.PRNGKey(17), m=g.m, N=40, L=L, d=d)
        stats = sufficient_stats(H, T)
        cfg = DMTLELMConfig(r=r, tau=2.0, zeta=1.0, delta=10.0, iters=iters)
        (_, diag_j), t_j = timed(lambda: fit_dense(stats, g, cfg))
        obj_j = np.asarray(diag_j["objective"])
        target = gap_target(obj_j, at=target_at)
        sync_iters = iters_to_target(obj_j, target)
        emit(f"robust/{name}/sync_baseline", t_j * 1e6,
             f"target={target:.4f};iters_to_target={sync_iters}")
        for cell_i, (kind, n_byz, rate, churn) in enumerate(cells):
            adv = AdversaryModel(
                n_byzantine=n_byz,
                attack_rate=rate,
                kinds=(kind,) if kind != "none" else ("sign_flip",),
                churn=churn,
                seed=1000 * topo_i + cell_i,
            )
            # ONE tape per cell: every aggregator defends the same attack
            tape = adv.sample(g, iters, L=L, r=r)
            member_frac = float(np.asarray(tape.member).mean())
            for agg in aggregators:
                cfg_a = dataclasses.replace(cfg, aggregator=agg)
                (_, diag_a), t_a = timed(
                    lambda: fit_async(stats, g, cfg_a, tape))
                obj_a = np.asarray(diag_a["objective"])
                it_a = iters_to_target(obj_a, target)
                cons = float(np.asarray(diag_a["consensus"])[-1])
                # machine-readable DNF reason for the -1 sentinel: an
                # attack that NaNs the run and one that merely stalls it
                # are different frontier facts (repro.obs.health)
                why = classify_run(diag_a, it_a >= 0)
                rows.append([
                    name, g.m, g.n_edges, agg, kind, n_byz, rate,
                    int(bool(churn)), member_frac, target, sync_iters,
                    it_a, why, float(obj_a[-1]), cons,
                ])
                cell_tag = (f"{kind}_r{rate}_b{n_byz}"
                            + ("_churn" if churn else ""))
                emit(f"robust/{name}/{agg}/{cell_tag}", t_a * 1e6,
                     f"iters_to_target={it_a};final_obj={obj_a[-1]:.4f};"
                     f"final_consensus={cons:.2e}")
                summary.setdefault(name, {})[f"{agg}/{cell_tag}"] = it_a
    write_csv("robustness_frontier",
              ["topology", "m", "edges", "aggregator", "attack_kind",
               "n_byzantine", "attack_rate", "churn", "member_frac",
               "target_obj", "sync_iters", "iters_to_target", "dnf_reason",
               "final_obj", "final_consensus"], rows)
    _append_history(summary)


_MESH_SCRIPT = textwrap.dedent(
    """
    import os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"  # CPU emulation, never the chip
    import dataclasses, json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import engine
    from repro.core.graph import expander
    from repro.data.synthetic import paper_uniform
    from repro.netsim import (
        AdversaryModel, ChannelModel, gap_target, iters_to_target,
    )

    smoke = bool(os.environ.get("BENCH_SMOKE"))
    iters, target_at = (80, 60) if smoke else (300, 100)
    aggregators = ("mean", "coordinate_median")
    cells = [("sign_flip", 1, 1.0, ())]
    if not smoke:
        cells += [
            ("gaussian_noise", 1, 1.0, ()),
            ("sign_flip", 1, 0.25, ((7, iters // 4, iters // 2),)),
        ]
    L, d, r = 10, 3, 2
    g = expander(8, 3, seed=0)
    mesh = Mesh(np.array(jax.devices()[:8]), ("agents",))
    H, T = paper_uniform(jax.random.PRNGKey(17), m=g.m, N=40, L=L, d=d)
    stats = engine.sufficient_stats(H, T)
    cfg = engine.ConsensusConfig(r=r, tau=2.0, zeta=1.0, delta=10.0,
                                 iters=iters)
    _, diag_j = engine.fit_dense(stats, g, cfg)
    target = gap_target(np.asarray(diag_j["objective"]), at=target_at)
    base = ChannelModel(delay="geometric", scale=1.0, drop=0.1,
                        seed=3).sample(g, iters)
    rows = []
    for cell_i, (kind, n_byz, rate, churn) in enumerate(cells):
        tape = AdversaryModel(
            n_byzantine=n_byz, attack_rate=rate, kinds=(kind,),
            churn=churn, seed=100 + cell_i,
        ).sample(g, iters, L=L, r=r, base=base)
        for agg in aggregators:
            cfg_a = dataclasses.replace(cfg, aggregator=agg)
            st_a, dg_a = engine.fit_async(stats, g, cfg_a, tape)
            t0 = time.perf_counter()
            runner = engine.make_runner(
                stats, g, cfg_a, executor="sharded_graph", mesh=mesh,
                agent_axes=("agents",), tape=tape)
            st_s, dg_s = runner.run()
            jax.block_until_ready(st_s.U)
            t_mesh = time.perf_counter() - t0
            obj_a = np.asarray(dg_a["objective"])
            obj_s = np.asarray(dg_s["objective"])
            rows.append({
                "topology": "expander_d3", "m": g.m, "aggregator": agg,
                "attack_kind": kind, "n_byzantine": n_byz,
                "attack_rate": rate, "churn": int(bool(churn)),
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

_MESH_HEADER = ["topology", "m", "aggregator", "attack_kind", "n_byzantine",
                "attack_rate", "churn", "target_obj", "async_iters",
                "mesh_iters", "delta_U", "delta_obj", "mesh_seconds"]


def run_mesh():
    """The mesh Byzantine cells (module docstring): same adversary tape on
    fit_async vs the in-mesh tape driver, agreement delta per cell →
    mesh_robustness.csv + a dated history entry."""
    from benchmarks.asynchrony import run_subprocess_rows

    rows = run_subprocess_rows(_MESH_SCRIPT)
    summary: dict = {}
    for row in rows:
        cell_tag = (f"{row['attack_kind']}_r{row['attack_rate']}"
                    f"_b{row['n_byzantine']}"
                    + ("_churn" if row["churn"] else ""))
        emit(f"robust_mesh/{row['topology']}/{row['aggregator']}/{cell_tag}",
             row["mesh_seconds"] * 1e6,
             f"mesh_iters={row['mesh_iters']};"
             f"async_iters={row['async_iters']};"
             f"delta_U={row['delta_U']:.2e};delta_obj={row['delta_obj']:.2e}")
        summary[f"{row['aggregator']}/{cell_tag}"] = {
            "mesh_iters": row["mesh_iters"],
            "async_iters": row["async_iters"],
            "delta_U": row["delta_U"],
        }
    write_csv("mesh_robustness", _MESH_HEADER,
              [[row[k] for k in _MESH_HEADER] for row in rows])
    _append_history(summary, key="robustness_mesh")
