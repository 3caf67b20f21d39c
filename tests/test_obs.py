"""Observability layer (repro.obs): span tracing, telemetry counters,
aggregator audit ground truth, health monitors, and run reports.

The contract under test, in three tiers:

* OFF is free: ``cfg.telemetry=False`` (the default) leaves every
  executor's diagnostics dict — keys AND bits — exactly as before (the
  golden sha256 battery pins the traced computation; here we pin the
  contract surface).
* ON is truthful: the comm counters match the executor's actual message
  schedule (analytic cross-checks against the tape and the compiled
  schedule's floats-per-iteration model), and the aggregator audit
  correlates with the AdversaryTape's ground-truth attack ticks.
* The host side composes: tracer spans nest and export to
  Chrome-trace-format JSON, health verdicts classify NaN / divergence /
  stall trajectories, and the run report folds diags + spans into
  markdown/JSON.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from repro.core.dmtl_elm import DMTLELMConfig, fit
from repro.core.graph import complete, ring
from repro.netsim.adversary import AdversaryModel
from repro.netsim.events import constant_tape
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.counters import modeled_floats_per_iter
from repro.obs.health import HealthConfig, check_health, classify_run

TELEMETRY_KEYS = {
    "resid_max", "agg_rejected", "msgs_delivered", "msgs_stale",
    "msgs_dropped", "comm_floats",
}
BASE_KEYS = {
    "objective", "lagrangian", "consensus", "gamma", "gamma_min",
    "primal_sq",
}


def _data(m=8, N=16, L=8, d=2, seed=0):
    rng = np.random.default_rng(seed)
    H = rng.normal(size=(m, N, L)).astype(np.float32)
    T = rng.normal(size=(m, N, d)).astype(np.float32)
    return H, T


# --------------------------------------------------------------------------
# Tracer + Chrome trace export
# --------------------------------------------------------------------------


def test_tracer_spans_nest_and_export(tmp_path):
    tr = obs_trace.Tracer()
    with obs_trace.use(tr):
        with obs_trace.span("outer", tag="a"):
            with obs_trace.span("inner"):
                pass
            with obs_trace.span("inner"):
                pass
        with obs_trace.span("second"):
            pass
    assert [s["name"] for s in tr.spans] == [
        "inner", "inner", "outer", "second",
    ]
    depths = {s["name"]: s["depth"] for s in tr.spans}
    assert depths["outer"] == 0 and depths["inner"] == 1
    outer = next(s for s in tr.spans if s["name"] == "outer")
    assert outer["args"] == {"tag": "a"}
    paths = tr.export(tmp_path)
    n_events = obs_trace.validate_trace(paths["trace"])
    assert n_events == 4
    doc = json.loads((tmp_path / "trace.json").read_text())
    assert all(ev["ph"] == "X" for ev in doc["traceEvents"])
    rows = [json.loads(line)
            for line in (tmp_path / "spans.jsonl").read_text().splitlines()]
    assert len(rows) == 4


def test_span_is_noop_without_active_tracer():
    # module-level span() with no tracer installed must hand back the
    # shared null context (zero per-call allocation on the hot path)
    assert obs_trace.current() is None
    ctx = obs_trace.span("anything")
    assert ctx is obs_trace._NULL
    with ctx:
        pass


def test_validate_trace_rejects_overlapping_siblings(tmp_path):
    bad = {
        "traceEvents": [
            {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0,
             "pid": 1, "tid": 0},
            {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0,
             "pid": 1, "tid": 0},
        ],
        "displayTimeUnit": "ms",
    }
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="overlap"):
        obs_trace.validate_trace(p)


def test_benchmarks_common_reexports_obs_timed():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        from benchmarks import common
    finally:
        sys.path.pop(0)
    assert common.timed is obs_trace.timed


# --------------------------------------------------------------------------
# Telemetry-off: the diag contract is untouched
# --------------------------------------------------------------------------


def test_telemetry_off_keys_and_bits_unchanged():
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=6)
    _, off = fit(H, T, g, cfg)
    assert set(off) == BASE_KEYS
    _, on = fit(H, T, g, cfg, telemetry=True)
    assert set(on) == BASE_KEYS | TELEMETRY_KEYS
    for k in BASE_KEYS:
        np.testing.assert_array_equal(np.asarray(off[k]), np.asarray(on[k]))


# --------------------------------------------------------------------------
# Telemetry-on: counters match the executors' actual schedules
# --------------------------------------------------------------------------


def test_dense_counters_match_schedule():
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=6)
    _, dg = fit(H, T, g, cfg, telemetry=True)
    E = g.n_edges
    assert np.all(np.asarray(dg["msgs_delivered"]) == 2.0 * E)
    assert np.all(np.asarray(dg["msgs_stale"]) == 0.0)
    assert np.all(np.asarray(dg["msgs_dropped"]) == 0.0)
    assert np.all(np.asarray(dg["agg_rejected"]) == 0.0)
    model = modeled_floats_per_iter("dense", L=8, r=2, n_edges=E)
    assert np.all(np.asarray(dg["comm_floats"]) == model)
    assert np.all(np.asarray(dg["resid_max"]) >= 0.0)


def test_colored_staleness_counts_stale_deliveries():
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=6)
    E = g.n_edges
    _, fresh = fit(H, T, g, cfg, executor="colored", telemetry=True)
    assert np.all(np.asarray(fresh["msgs_delivered"]) == 2.0 * E)
    assert np.all(np.asarray(fresh["msgs_stale"]) == 0.0)
    _, stale = fit(
        H, T, g, cfg, executor="colored", staleness=2, telemetry=True
    )
    assert np.all(np.asarray(stale["msgs_delivered"]) == 0.0)
    assert np.all(np.asarray(stale["msgs_stale"]) == 2.0 * E)


def test_async_counters_match_tape_ages():
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=8)
    E = g.n_edges
    # constant_tape(k=2): every directed delivery is k rounds old — all
    # 2E receptions count stale, none fresh, none dropped
    tape = constant_tape(cfg.iters, g, 2)
    _, dg = fit(H, T, g, cfg, executor="async", tape=tape, telemetry=True)
    ages = np.asarray(tape.age)              # (iters, 2, E)
    exp_fresh = (ages == 1).sum(axis=(1, 2)).astype(np.float64)
    exp_stale = (ages > 1).sum(axis=(1, 2)).astype(np.float64)
    np.testing.assert_array_equal(
        np.asarray(dg["msgs_delivered"], np.float64), exp_fresh
    )
    np.testing.assert_array_equal(
        np.asarray(dg["msgs_stale"], np.float64), exp_stale
    )
    assert np.all(np.asarray(dg["msgs_dropped"]) == 0.0)
    model = modeled_floats_per_iter("async", L=8, r=2, n_edges=E)
    assert np.all(np.asarray(dg["comm_floats"]) == model)


def test_async_dropped_counts_dead_edges():
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=8)
    adv = AdversaryModel(n_byzantine=0, leave_prob=0.3, mean_absence=3.0,
                         seed=5)
    tape = adv.sample(g, cfg.iters, L=8, r=2)
    _, dg = fit(H, T, g, cfg, executor="async", tape=tape, telemetry=True)
    member = np.asarray(tape.member)         # (iters, m)
    edges = np.asarray(g.edges)
    live = member[:, edges[:, 0]] * member[:, edges[:, 1]]   # (iters, E)
    exp_dropped = 2.0 * (1.0 - live).sum(axis=1)
    np.testing.assert_allclose(
        np.asarray(dg["msgs_dropped"], np.float64), exp_dropped
    )


# --------------------------------------------------------------------------
# Aggregator audit vs. AdversaryTape ground truth
# --------------------------------------------------------------------------


def test_aggregator_audit_matches_attack_ground_truth():
    H, T = _data()
    g = complete(8)   # degree 7: the 10x-median rule has room to fire
    cfg = DMTLELMConfig(r=2, iters=40, aggregator="coordinate_median")
    adv = AdversaryModel(
        n_byzantine=2, attack_rate=0.5, kinds=("sign_flip",), seed=3
    )
    tape = adv.sample(g, cfg.iters, L=8, r=cfg.r)
    _, dg = fit(H, T, g, cfg, executor="async", tape=tape, telemetry=True)
    rej = np.asarray(dg["agg_rejected"], np.float64)
    attacked = (np.asarray(tape.attack) != 0).any(axis=1)
    # soundness: a rejection NEVER fires on a tick with no attacker
    assert np.all(rej[~attacked] == 0.0)
    # sensitivity: once consensus has tightened the honest spread, a
    # sign-flipped candidate is always >10x the median distance — every
    # attacked tick in the late half is flagged
    late = np.arange(cfg.iters) >= cfg.iters // 2
    assert np.all(rej[attacked & late] > 0.0)
    assert rej.sum() > 0.0


def test_aggregator_audit_zero_on_zero_adversary_tape():
    H, T = _data()
    g = complete(8)
    cfg = DMTLELMConfig(r=2, iters=20, aggregator="coordinate_median")
    adv = AdversaryModel(n_byzantine=0, seed=3)
    tape = adv.sample(g, cfg.iters, L=8, r=cfg.r)
    _, dg = fit(H, T, g, cfg, executor="async", tape=tape, telemetry=True)
    assert float(np.asarray(dg["agg_rejected"]).sum()) == 0.0


def test_aggregator_audit_zero_for_mean_aggregator():
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=6)   # aggregator="mean": no audit target
    _, dg = fit(H, T, g, cfg, telemetry=True)
    assert float(np.asarray(dg["agg_rejected"]).sum()) == 0.0


# --------------------------------------------------------------------------
# Health monitors
# --------------------------------------------------------------------------


def test_check_health_nan():
    diags = {"objective": np.array([1.0, 0.9, np.nan, 0.7]),
             "consensus": np.zeros(4)}
    v = check_health(diags)
    assert not v["healthy"]
    assert v["dnf_reason"] == "nan"
    assert v["at_iter"] == 2


def test_check_health_divergence():
    obj = np.ones(10)
    obj[7] = 1e4
    v = check_health({"objective": obj, "consensus": np.zeros(10)})
    assert v["dnf_reason"] == "objective_divergence"
    assert v["at_iter"] == 7


def test_check_health_stall_needs_open_consensus():
    n = 60
    flat = {"objective": np.ones(n), "consensus": np.full(n, 0.5)}
    v = check_health(flat, HealthConfig(stall_window=10))
    assert v["dnf_reason"] == "consensus_stall"
    # the same flat objective with consensus BELOW the floor is just a
    # converged run, not a stall
    done = {"objective": np.ones(n), "consensus": np.full(n, 1e-9)}
    assert check_health(done, HealthConfig(stall_window=10))["healthy"]


def test_check_health_healthy_and_classify():
    obj = 1.0 / (1.0 + np.arange(60.0))
    diags = {"objective": obj, "consensus": np.full(60, 1e-2)}
    assert check_health(diags)["healthy"]
    assert classify_run(diags, reached_target=True) == ""
    assert classify_run(diags, reached_target=False) == "horizon"
    bad = {"objective": np.array([1.0, np.nan]), "consensus": np.zeros(2)}
    assert classify_run(bad, reached_target=False) == "nan"


def test_health_early_stop_stamps_dnf_reason(tmp_path):
    from repro.checkpoint import read_meta

    H, T = _data(m=4)
    g = ring(4)
    cfg = DMTLELMConfig(r=2, iters=10)
    # an aggressive config that trips on any real trajectory: any relative
    # improvement below stall_tol=10 counts as stalled
    hc = HealthConfig(stall_window=2, stall_tol=10.0, consensus_floor=0.0)
    _, dg = fit(
        H, T, g, cfg, checkpoint_dir=tmp_path, checkpoint_every=2,
        health=hc,
    )
    n_done = int(np.asarray(dg["objective"]).shape[0])
    assert n_done < cfg.iters                      # stopped early
    assert n_done % 2 == 0                         # at a segment boundary
    meta = read_meta(tmp_path)["metadata"]
    assert meta["dnf_reason"] == "consensus_stall"
    assert 0 <= int(meta["dnf_at_iter"]) < n_done


def test_health_requires_checkpoint_dir():
    H, T = _data(m=4)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        fit(H, T, ring(4), DMTLELMConfig(r=2, iters=4), health=True)


def test_healthy_monitored_run_is_bitwise_unmonitored(tmp_path):
    H, T = _data(m=4)
    g = ring(4)
    cfg = DMTLELMConfig(r=2, iters=6)
    state0, d0 = fit(H, T, g, cfg)
    # a lenient monitor that never trips: same trajectory, bit for bit
    hc = HealthConfig(stall_window=1000)
    state1, d1 = fit(
        H, T, g, cfg, checkpoint_dir=tmp_path, checkpoint_every=2,
        health=hc,
    )
    for k in d0:
        np.testing.assert_array_equal(np.asarray(d0[k]), np.asarray(d1[k]))
    np.testing.assert_array_equal(np.asarray(state0.U), np.asarray(state1.U))


# --------------------------------------------------------------------------
# fit(trace_dir=) end to end + run report
# --------------------------------------------------------------------------


def test_fit_trace_dir_emits_valid_trace_and_report(tmp_path):
    H, T = _data()
    g = ring(8)
    cfg = DMTLELMConfig(r=2, iters=6)
    _, dg = fit(H, T, g, cfg, telemetry=True, trace_dir=tmp_path)
    n_events = obs_trace.validate_trace(tmp_path / "trace.json")
    assert n_events >= 3
    rep = json.loads((tmp_path / "report.json").read_text())
    assert rep["health"]["healthy"]
    assert rep["iterations"] == cfg.iters
    assert rep["comm"]["msgs_delivered_total"] == 2.0 * g.n_edges * cfg.iters
    span_names = {r["name"] for r in rep["time_breakdown"]}
    assert {"stats", "runner_build", "segment"} <= span_names
    md = (tmp_path / "report.md").read_text()
    assert "# Run report" in md and "## Communication" in md


def test_report_render_without_spans():
    diags = {
        "objective": np.array([3.0, 2.0, 1.0]),
        "consensus": np.array([0.3, 0.2, 0.1]),
    }
    md, data = obs_report.render(diags, meta={"executor": "dense"})
    assert data["objective_final"] == 1.0
    assert data["health"]["healthy"]
    assert data["comm"] == {}
    assert "## Time breakdown" not in md


# --------------------------------------------------------------------------
# Sharded paths: counters + the analytic comm model (8-device subprocess)
# --------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.dmtl_elm import fit, DMTLELMConfig
from repro.core.graph import ring, star
from repro.netsim.events import zero_delay_tape
from repro.obs.counters import modeled_floats_per_iter

m, N, L, d, r = 8, 16, 8, 2, 2
rng = np.random.default_rng(0)
H = rng.normal(size=(m, N, L)).astype(np.float32)
T = rng.normal(size=(m, N, d)).astype(np.float32)
cfg = DMTLELMConfig(r=r, iters=5)
mesh = Mesh(np.array(jax.devices()).reshape(m), ("agents",))
keys = {"resid_max", "agg_rejected", "msgs_delivered", "msgs_stale",
        "msgs_dropped", "comm_floats"}

g = ring(m)
U, A, dg = fit(H, T, g, cfg, executor="sharded", mesh=mesh,
               agent_axes=("agents",), telemetry=True)
assert keys <= set(dg), set(dg)
assert np.all(np.asarray(dg["msgs_delivered"]) == 2.0 * g.n_edges)
assert np.all(np.asarray(dg["comm_floats"])
              == modeled_floats_per_iter("sharded", L=L, r=r, m=m, n_axes=1))

g2 = star(m)
U, A, dg = fit(H, T, g2, cfg, executor="sharded", mesh=mesh,
               agent_axes=("agents",), telemetry=True)
assert keys <= set(dg)
assert np.all(np.asarray(dg["msgs_delivered"]) == 2.0 * g2.n_edges)
# the acceptance pin: the telemetry comm model IS the schedule bench's
# analytic floats-per-iteration accounting (5 E L r on the compiled path)
assert np.all(np.asarray(dg["comm_floats"]) == 5 * g2.n_edges * L * r)
assert np.all(np.asarray(dg["comm_floats"])
              == modeled_floats_per_iter("sharded_graph", L=L, r=r,
                                         n_edges=g2.n_edges))

tape = zero_delay_tape(cfg.iters, g)
U, A, dg = fit(H, T, g, cfg, executor="sharded", mesh=mesh,
               agent_axes=("agents",), tape=tape, telemetry=True)
assert keys <= set(dg)
assert np.all(np.asarray(dg["msgs_delivered"]) == 2.0 * g.n_edges)
assert np.all(np.asarray(dg["msgs_stale"]) == 0.0)
assert np.all(np.asarray(dg["msgs_dropped"]) == 0.0)
print("SHARDED_TELEMETRY_OK")
"""


def test_sharded_counters_and_comm_model_8dev(tmp_path):
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = tmp_path / "sharded_obs.py"
    script.write_text(_SHARDED_SCRIPT)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    root = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    out = subprocess.run(
        [sys.executable, str(script)], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED_TELEMETRY_OK" in out.stdout


# --------------------------------------------------------------------------
# The analytic comm model itself
# --------------------------------------------------------------------------


def test_modeled_floats_per_iter_values_and_errors():
    assert modeled_floats_per_iter("dense", L=8, r=2, n_edges=10) == 480
    assert modeled_floats_per_iter("sharded", L=8, r=2, m=8, n_axes=2) == 1024
    assert (
        modeled_floats_per_iter("sharded_graph", L=8, r=2, n_edges=10) == 800
    )
    with pytest.raises(ValueError, match="n_edges"):
        modeled_floats_per_iter("dense", L=8, r=2)
    with pytest.raises(ValueError, match="unknown executor"):
        modeled_floats_per_iter("quantum", L=8, r=2, n_edges=1)


def test_sharded_graph_model_matches_topology_bench_accounting():
    # benchmarks/topology.py prices the compiled schedule at
    # E * L * r * (2*2 + 1) floats/iter — the telemetry model must agree
    g = complete(6)
    L, r = 16, 4
    assert modeled_floats_per_iter(
        "sharded_graph", L=L, r=r, n_edges=g.n_edges
    ) == g.n_edges * L * r * (2 * 2 + 1)
