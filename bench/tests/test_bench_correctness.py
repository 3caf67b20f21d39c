"""The check that decides ``correct``, driven on the CPU at a small size with
each cell's own limits: a sound run passes, the control fails, and so does
a run whose timed path is broken underneath (a state left unchanged, half
of each batch left out with the rest doubled, an answer altered where it
is produced, the exchange between devices left out).  The harness's look
for a chip is skipped: ``run.execute`` runs on the devices it is given."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference, run, spec

SEED = 2**33 + 11
SMALL = dict(m=4, N=512, d_in=64, L=128)


def small(name: str) -> spec.Cell:
    c = spec.load(name)
    traffic = dict(c.traffic)
    if traffic["feed"] == "stream":
        traffic["chunk_rows"] = 128
    return dataclasses.replace(c, config=dict(c.config, **SMALL),
                               traffic=traffic)


def execute(cell):
    return run.execute(cell, SEED, 0.3, False, jax.devices()[: cell.chips])


ONE_CHIP = [w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"] if w["chips"] == 1]


def _half_batch(orig):
    def produce(batch, T, **kw):
        st = orig(batch[:, ::2], T[:, ::2], **kw)
        return st._replace(G=2 * st.G, R=2 * st.R, n=2 * st.n, t2=2 * st.t2)
    return produce


def _altered(orig):
    """Agent 0's statistics, as the Gram pass produces them, 0.01% too
    large."""
    def produce(batch, T, **kw):
        st = orig(batch, T, **kw)
        return st._replace(G=st.G.at[0].multiply(1 + 1e-4),
                           R=st.R.at[0].multiply(1 + 1e-4))
    return produce


def _unchanged(orig):
    """Every ADMM step hands back the (U, A) it was given."""
    def update(stats, state, msgs, cfg, **kw):
        return state.U, state.A
    return update


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_a_sound_run_is_correct(cell):
    result = execute(small(cell))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", ONE_CHIP)
@pytest.mark.parametrize("fault", ["half_batch", "altered", "unchanged"])
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from repro.core import engine

    if fault == "unchanged":
        monkeypatch.setattr(engine, "agent_update",
                            _unchanged(engine.agent_update))
    else:
        wrap = _half_batch if fault == "half_batch" else _altered
        monkeypatch.setattr(engine, "produce_stats",
                            wrap(engine.produce_stats))
    result = execute(small(cell))
    assert not result["correct"], result["checks"]


def _control_stats(job):
    """The reference's statistics with every product from three bf16 passes
    (what Precision.HIGH computes on a TPU; the CPU ignores the setting)."""
    mm = reference._mm_high()
    W, b = np.asarray(job.W), np.asarray(job.b)
    G = R = t2 = 0.0
    for count, X, T in run.reference_parts(job):
        X, T = np.asarray(X, np.float32), np.asarray(T, np.float32)
        H = 1.0 / (1.0 + np.exp(-(mm(X, W) + b)))
        Ht = np.swapaxes(H, 1, 2)
        G = G + count * mm(Ht, H).astype(np.float64)
        R = R + count * mm(Ht, T).astype(np.float64)
        t2 = t2 + count * np.sum(T.astype(np.float64) ** 2, (1, 2))
    return G, R, t2


@pytest.mark.parametrize("cell", [c for c in ONE_CHIP
                                  if spec.load(c).traffic["feed"] == "resident"])
def test_the_control_is_not_correct(cell):
    from bench import fitjob

    c = small(cell)
    job = fitjob.FitJob(c.config, c.traffic, SEED, jax.devices()[:1],
                        fitjob.Spans(False))
    ref = run.reference_stats(job)
    ref_fit = run.reference_fit(job, ref, 1, eig=np.linalg.eigh(ref[0]))
    ctl = _control_stats(job)
    gaps = run.gaps(ctl[:2], run.reference_fit(job, ctl, 1, precision="high"),
                    ref, ref_fit)
    ok, checks = check.judge(gaps, c.limits)
    assert not ok, checks


def test_the_agent_graph_is_the_configurations_on_both_sides(monkeypatch):
    """A star configuration runs on a star and is checked against a star:
    checked against the ring it is not correct, and a graph the benchmark
    has no reference for is refused."""
    from bench import fitjob

    cell = small("danube-resident-sylvester")
    cell = dataclasses.replace(cell, config=dict(cell.config, graph="star"))
    assert execute(cell)["correct"]
    as_ring = reference.edges
    monkeypatch.setattr(reference, "edges",
                        lambda graph, m: as_ring("ring", m))
    assert not execute(cell)["correct"]
    unknown = dict(cell.config, graph="torus")
    with pytest.raises(ValueError, match="torus"):
        fitjob.graph(unknown)
    with pytest.raises(ValueError, match="torus"):
        as_ring("torus", 4)


FOUR_DEVICE_SCRIPT = """
import dataclasses, json, sys
sys.path.insert(0, {root!r})
import jax
from repro.core import engine
from bench.tests import test_bench_correctness as t
cell = t.small({cell!r})
cell = dataclasses.replace(cell, chips=4,
                           config=dict(cell.config, executor="sharded"))
out = {{}}
for fault in ("none", "no_exchange"):
    if fault == "no_exchange":
        engine._ring_recv_from_next = lambda x, axis_name: x
        engine._ring_recv_from_prev = lambda x, axis_name: x
    out[fault] = t.execute(cell)["correct"]
print(json.dumps(out))
"""


@pytest.mark.parametrize("cell", [c for c in ONE_CHIP
                                  if spec.load(c).traffic["feed"] == "resident"])
def test_sharded_run_without_its_exchange_is_not_correct(cell):
    """The cell's agents one per device, on four emulated devices (the
    sharded ring executor), with the cell's limits."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c",
         FOUR_DEVICE_SCRIPT.format(root=str(spec.ROOT), cell=cell)],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"none": True, "no_exchange": False}
