"""The program's spans on the profiler's clock (``repro.obs.trace``).

* Each span of an installed tracer is also a ``jax.profiler``
  annotation ``obs:<name>``, so a profiler trace holds the program's spans
  beside the device ops, nested as the tracer recorded them.
* ``use`` counts compile events into the innermost open span, and leaves
  ``jax.monitoring`` as it found it; the ``segment`` span says whether
  its program was built (``miss``) or reused (``hit``).
* The stream's ``stats`` span holds one ``feed_wait`` per batch and
  changes no bit; with no tracer the stream never blocks.
* The ADMM round's named scopes reach the lowered program.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.dmtl_elm import DMTLELMConfig, fit
from repro.core.graph import ring
from repro.data.pipeline import stream_sufficient_stats
from repro.obs import trace as obs_trace


def _data(m=4, N=16, L=8, d=2, seed=0):
    rng = np.random.default_rng(seed)
    H = rng.normal(size=(m, N, L)).astype(np.float32)
    T = rng.normal(size=(m, N, d)).astype(np.float32)
    return H, T


def _listeners():
    from jax._src import monitoring

    return (monitoring.get_event_time_span_listeners(),
            monitoring.get_event_duration_listeners())


def _scopes(text: str) -> set:
    """The scope names in a lowered module's op locations: ``exchange``
    of ``exchange/add``, ``u_solve`` of ``vmap(u_solve)/dot_general``."""
    return {part.split("(")[-1].rstrip(")")
            for loc in re.findall(r'loc\("([^"]+)"', text)
            for part in loc.split("/")[:-1]}


def _profile_spans(xplane_path) -> list[list]:
    """``[name, start_ns, end_ns]`` of every ``obs:`` event in a profiler
    trace's host planes, in order of start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [[ev.name[len(obs_trace.PREFIX):], ev.start_ns,
                     ev.start_ns + ev.duration_ns]
                    for ev in line.events
                    if ev.name.startswith(obs_trace.PREFIX)]
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def _by_name(tracer, name):
    return [s for s in tracer.spans if s["name"] == name]


# --------------------------------------------------------------------------
# spans in the profiler's trace
# --------------------------------------------------------------------------


def test_fit_spans_land_in_the_profiler_trace_nested(tmp_path):
    H, T = _data()
    cfg = DMTLELMConfig(r=2, iters=3)
    tracer = obs_trace.Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with obs_trace.use(tracer):
            with obs_trace.span("fit"):
                fit(H, T, ring(4), cfg)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    prof = _profile_spans(path)
    names = [n for n, _, _ in prof]
    for name in ("fit", "stats", "runner_build", "sync", "segment"):
        assert name in names, names
    # on the profiler's clock: everything opens inside "fit" and in the
    # fit's order (stats, runner_build, sync of run, sync of run_segment,
    # segment)
    (outer,) = [ev for ev in prof if ev[0] == "fit"]
    inner = [ev for ev in prof if ev[0] != "fit"]
    assert all(outer[1] <= s and e <= outer[2] for _, s, e in inner)
    assert [n for n, _, _ in inner] == [
        "stats", "runner_build", "sync", "sync", "segment"]
    # the tracer's records agree: the same spans, each the child of "fit"
    (fit_rec,) = _by_name(tracer, "fit")
    assert fit_rec["parent"] is None and fit_rec["depth"] == 0
    recs = sorted((s for s in tracer.spans if s["name"] != "fit"),
                  key=lambda s: s["id"])
    assert [s["name"] for s in recs] == [n for n, _, _ in inner]
    assert all(s["parent"] == fit_rec["id"] and s["depth"] == 1
               for s in recs)


def test_export_carries_parent_in_args(tmp_path):
    tr = obs_trace.Tracer()
    with obs_trace.use(tr):
        with obs_trace.span("outer"):
            with obs_trace.span("inner", k=1):
                pass
    tr.export(tmp_path)
    assert obs_trace.validate_trace(tmp_path / "trace.json") == 2
    events = json.loads((tmp_path / "trace.json").read_text())["traceEvents"]
    args = {e["name"]: e["args"] for e in events}
    (outer,) = _by_name(tr, "outer")
    assert args["outer"]["parent"] is None
    assert args["inner"] == {"k": 1, "parent": outer["id"]}


# --------------------------------------------------------------------------
# the compile counter
# --------------------------------------------------------------------------


def test_compile_counter_puts_the_scan_trace_on_segment():
    H, T = _data()
    cfg = DMTLELMConfig(r=2, iters=3)
    stats = engine.produce_stats(jnp.asarray(H), jnp.asarray(T))
    # an empty segment-program cache: this fit builds the program
    engine.segment_program_cache_clear()
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        runner = engine.make_runner(stats, ring(4), cfg)
        runner.run()
    (seg,) = _by_name(tracer, "segment")
    assert seg["args"]["program"] == "miss"
    traces = seg["compiles"]["trace"]
    assert traces["scan"][0] >= 1 and traces["scan"][1] >= 0.0
    assert "jit(scan)" in seg["compiles"]["lower"]
    # nothing of the scan is put down to the spans around it
    for s in tracer.spans:
        if s["name"] != "segment":
            assert "scan" not in s.get("compiles", {}).get("trace", {})


def test_compile_counter_finds_nothing_to_build_on_a_refit():
    H, T = _data()
    cfg = DMTLELMConfig(r=2, iters=3)
    engine.segment_program_cache_clear()
    engine.make_runner(engine.produce_stats(jnp.asarray(H), jnp.asarray(T)),
                       ring(4), cfg).run()
    # other statistics of the same shapes, graph and config
    H2, T2 = _data(seed=1)
    stats = engine.produce_stats(jnp.asarray(H2), jnp.asarray(T2))
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        engine.make_runner(stats, ring(4), cfg).run()
    (seg,) = _by_name(tracer, "segment")
    assert seg["args"]["program"] == "hit"
    compiles = seg.get("compiles", {})
    assert "trace" not in compiles and "lower" not in compiles
    assert engine.segment_program_cache_info() == {"hits": 1, "misses": 1}


def test_compile_counter_counts_nothing_for_a_compiled_call():
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((4,))
    f(x).block_until_ready()
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        with obs_trace.span("again"):
            f(x).block_until_ready()
    (again,) = _by_name(tracer, "again")
    assert "compiles" not in again
    assert tracer.compiles == {}


def test_compile_counter_listeners_come_and_go_with_use():
    before = _listeners()
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        spans_on, durations_on = _listeners()
        assert len(spans_on) == len(before[0]) + 1
        assert len(durations_on) == len(before[1]) + 1
        with obs_trace.use(None):   # tracing off inside: nothing counted
            jax.jit(lambda x: x + 3.0)(jnp.ones((3,)))
    assert _listeners() == before
    assert tracer.compiles == {}
    # off: span() is the shared null context and no listener of obs exists
    assert obs_trace.current() is None
    assert obs_trace.span("x") is obs_trace._NULL
    assert not any(isinstance(getattr(cb, "__self__", None), obs_trace.Tracer)
                   for cb in before[0] + before[1])


def test_compile_counter_outside_any_span_goes_to_the_tracer():
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        jax.jit(lambda x: x * 5.0 - 1.0)(jnp.ones((5,)))
    assert tracer.compiles["trace"]["<lambda>"][0] == 1
    assert tracer.spans == []


def test_run_report_counts_compiles_per_top_level_span(tmp_path):
    H, T = _data()
    cfg = DMTLELMConfig(r=2, iters=3)
    # an empty segment-program cache: the fit builds its program
    engine.segment_program_cache_clear()
    fit(H, T, ring(4), cfg, trace_dir=tmp_path)
    rep = json.loads((tmp_path / "report.json").read_text())
    rows = {r["name"]: r for r in rep["time_breakdown"]}
    assert rows["segment"]["compiles"]["trace"] >= 1
    assert rows["segment"]["compiles"]["compile"] >= 1
    assert rows["segment"]["programs"] == {"miss": 1}
    assert rows["sync"]["count"] == 2
    assert "compile events" in (tmp_path / "report.md").read_text()
    # a refit of the same shapes reuses it
    fit(H, T, ring(4), cfg, trace_dir=tmp_path)
    rep = json.loads((tmp_path / "report.json").read_text())
    rows = {r["name"]: r for r in rep["time_breakdown"]}
    assert rows["segment"]["programs"] == {"hit": 1}
    assert "trace" not in rows["segment"]["compiles"]
    assert "| hit 1 |" in (tmp_path / "report.md").read_text()


# --------------------------------------------------------------------------
# the stream: feed_wait spans, no block when off, no bit changed
# --------------------------------------------------------------------------


def _parts(n=3, m=2, B=8, L=6, d=2):
    rng = np.random.default_rng(1)
    return [(jnp.asarray(rng.normal(size=(m, B, L)), jnp.float32),
             jnp.asarray(rng.normal(size=(m, B, d)), jnp.float32))
            for _ in range(n)]


@pytest.mark.parametrize("compensated", [False, True])
def test_stream_feed_wait_per_batch_and_no_block(monkeypatch, compensated):
    parts = _parts()
    calls = []
    real = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    off = stream_sufficient_stats(iter(parts), chunk=4,
                                  compensated=compensated)
    # tracing off: the stream adds no synchronisation
    assert calls == []
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        on = stream_sufficient_stats(iter(parts), chunk=4,
                                     compensated=compensated)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    outer = [s for s in _by_name(tracer, "stats") if s["depth"] == 0]
    assert len(outer) == 1 and outer[0]["args"]["batches"] == len(parts)
    waits = _by_name(tracer, "feed_wait")
    # one per batch, and the pull that finds the stream's end
    assert len(waits) == len(parts) + 1
    assert all(w["parent"] == outer[0]["id"] for w in waits)
    # tracing on: the stats pass of every batch blocks inside its span
    assert len(calls) >= len(parts)


# --------------------------------------------------------------------------
# named scopes in the ADMM round
# --------------------------------------------------------------------------


def test_dense_step_lowering_names_the_round_scopes():
    H, T = _data()
    stats = engine.produce_stats(jnp.asarray(H), jnp.asarray(T))
    runner = engine.make_runner(stats, ring(4),
                                DMTLELMConfig(r=2, iters=2))
    text = jax.jit(runner.segment_fn, static_argnums=1).lower(
        runner.init_state(), 2).as_text(debug_info=True)
    assert {"u_solve", "a_update", "exchange"} <= _scopes(text)


_SHARDED_SCOPES = r"""
import re
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import engine
from repro.core.dmtl_elm import DMTLELMConfig
from repro.core.graph import ring, star

m = 4
rng = np.random.default_rng(0)
H = jnp.asarray(rng.normal(size=(m, 8, 6)), jnp.float32)
T = jnp.asarray(rng.normal(size=(m, 8, 2)), jnp.float32)
stats = engine.produce_stats(H, T)
mesh = Mesh(np.array(jax.devices()).reshape(m), ("agents",))
cfg = DMTLELMConfig(r=2, iters=2)
for name, g in (("sharded", ring(m)), ("sharded_graph", star(m))):
    runner = engine.make_runner(stats, g, cfg, executor=name, mesh=mesh,
                                agent_axes=("agents",))
    text = jax.jit(runner.segment_fn, static_argnums=1).lower(
        runner.init_state(), 2).as_text(debug_info=True)
    scopes = {part.split("(")[-1].rstrip(")")
              for loc in re.findall(r'loc\("([^"]+)"', text)
              for part in loc.split("/")[:-1]}
    assert {"u_solve", "a_update", "exchange"} <= scopes, (name, scopes)
print("SCOPES_OK")
"""


def test_sharded_rounds_lowering_names_the_round_scopes(tmp_path):
    script = tmp_path / "scopes.py"
    script.write_text(_SHARDED_SCOPES)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    root = Path(__file__).resolve().parents[1]
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SCOPES_OK" in out.stdout
