"""The dense executor's segment program is built once per key and reused.

* A refit over other statistics of the same shapes, graph and config
  traces and lowers nothing, records ``program="hit"`` on its ``segment``
  span, and gives, bit for bit, what a program built afresh gives.
* A change of graph, segment length, L, r or of a config field the
  program reads builds a new program (``program="miss"``), so a stale one
  is never served.
* The program holds no array of a fit: the fit's G is freed with its
  runner and outputs.
"""

from __future__ import annotations

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.graph import Graph, ring, star
from repro.obs import trace as obs_trace

M = 4


def _stats(seed, L=8, N=16, d=2):
    rng = np.random.default_rng(seed)
    H = jnp.asarray(rng.normal(size=(M, N, L)) / np.sqrt(L), jnp.float32)
    T = jnp.asarray(rng.normal(size=(M, N, d)), jnp.float32)
    return engine.produce_stats(H, T)


def _cfg(**kw):
    return engine.ConsensusConfig(
        r=kw.pop("r", 2), iters=kw.pop("iters", 3), tau=kw.pop("tau", 2.0),
        zeta=1.0, **kw)


def _run(stats, g, cfg):
    """``(U, A, lam, diags)`` on the host and the fit's ``segment`` span."""
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        state, diags = engine.make_runner(stats, g, cfg).run()
    (seg,) = [s for s in tracer.spans if s["name"] == "segment"]
    return jax.device_get((state.U, state.A, state.lam, diags)), seg


def _fresh(stats, g, cfg):
    """The same run on an empty segment-program cache."""
    engine.segment_program_cache_clear()
    out, seg = _run(stats, g, cfg)
    assert seg["args"]["program"] == "miss"
    return out


def _assert_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("tau", ["scalar", "per_agent"])
@pytest.mark.parametrize("first_order", [False, True])
@pytest.mark.parametrize("solver", ["sylvester", "pcg"])
def test_refit_of_the_same_shapes_reuses_the_program(solver, first_order,
                                                     tau):
    tau_v = 2.0 if tau == "scalar" else np.asarray([4.0, 4.5, 5.0, 5.5],
                                                   np.float32)
    cfg = _cfg(u_solver=solver, first_order=first_order, tau=tau_v)
    g = ring(M)
    engine.segment_program_cache_clear()
    _, first = _run(_stats(0), g, cfg)
    assert first["args"]["program"] == "miss"
    # other statistics of the same shapes, graph and config
    out, second = _run(_stats(1), g, cfg)
    assert second["args"]["program"] == "hit"
    compiles = second.get("compiles", {})
    assert "trace" not in compiles and "lower" not in compiles
    assert engine.segment_program_cache_info() == {"hits": 1, "misses": 1}
    _assert_bitwise(out, _fresh(_stats(1), g, cfg))


# each case: (graph, stats, config) of the base run -> of the changed one
_SAME_EDGE_COUNT = Graph(m=M, edges=((0, 1), (0, 2), (0, 3), (1, 2)))
CHANGES = {
    "graph": dict(g=star(M)),
    "graph_same_edge_count": dict(g=_SAME_EDGE_COUNT),
    "n": dict(cfg=dict(iters=4)),
    "L": dict(L=6),
    "r": dict(cfg=dict(r=3)),
    "aggregator": dict(cfg=dict(aggregator="trimmed_mean")),
    "telemetry": dict(cfg=dict(telemetry=True)),
    "prox": dict(cfg=dict(prox="standard")),
}


@pytest.mark.parametrize("change", sorted(CHANGES))
def test_a_change_of_key_builds_a_new_program(change):
    spec = CHANGES[change]
    engine.segment_program_cache_clear()
    _run(_stats(0), ring(M), _cfg())
    g = spec.get("g", ring(M))
    stats = _stats(1, L=spec.get("L", 8))
    cfg = _cfg(**spec.get("cfg", {}))
    out, seg = _run(stats, g, cfg)
    assert seg["args"]["program"] == "miss"
    assert seg["compiles"]["lower"]["jit(scan)"][0] == 1
    assert engine.segment_program_cache_info() == {"hits": 0, "misses": 2}
    _assert_bitwise(out, _fresh(stats, g, cfg))


def test_each_segment_length_is_built_once():
    stats, g, cfg = _stats(0), ring(M), _cfg(iters=6)
    engine.segment_program_cache_clear()
    runner = engine.make_runner(stats, g, cfg)
    state = runner.init_state()
    for n in (2, 2, 1, 1):
        state, _ = runner.run_segment(state, n)
    assert engine.segment_program_cache_info() == {"hits": 2, "misses": 2}


def test_iters_bounds_the_runner_not_the_program():
    stats, g = _stats(0), ring(M)
    engine.segment_program_cache_clear()
    engine.make_runner(stats, g, _cfg(iters=3)).run()
    runner = engine.make_runner(stats, g, _cfg(iters=5))
    runner.run_segment(runner.init_state(), 3)
    assert engine.segment_program_cache_info() == {"hits": 1, "misses": 1}


def test_the_program_holds_no_array_of_a_fit():
    g, cfg = ring(M), _cfg()
    engine.segment_program_cache_clear()
    stats = _stats(0)
    G = weakref.ref(stats.G)
    runner = engine.make_runner(stats, g, cfg)
    out = runner.run()
    jax.block_until_ready(out)
    del stats, runner, out
    gc.collect()
    assert G() is None
    # the program it built is still there for the next fit
    engine.make_runner(_stats(1), g, cfg).run()
    assert engine.segment_program_cache_info() == {"hits": 1, "misses": 1}


def test_a_new_round_body_or_solver_is_a_new_program(monkeypatch):
    stats, g = _stats(0), ring(M)
    engine.segment_program_cache_clear()
    base, _ = _run(stats, g, _cfg())
    # the round body as the module holds it when the runner is built
    monkeypatch.setattr(engine, "agent_update",
                        lambda stats, state, msgs, cfg, **kw: (state.U,
                                                               state.A))
    frozen, seg = _run(stats, g, _cfg())
    assert seg["args"]["program"] == "miss"
    np.testing.assert_array_equal(frozen[0], np.ones_like(frozen[0]))
    monkeypatch.undo()
    # a solver registered anew under the name the config gives
    monkeypatch.setitem(engine.U_SOLVERS, "sylvester",
                        engine.U_SOLVERS["kron"])
    out, seg = _run(stats, g, _cfg())
    assert seg["args"]["program"] == "miss"
    assert engine.segment_program_cache_info() == {"hits": 0, "misses": 3}
    _assert_bitwise(out, _run(stats, g, _cfg(u_solver="kron"))[0])
    assert not np.array_equal(out[0], base[0])
