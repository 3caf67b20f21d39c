"""The benchmark harness on the CPU: its work counts, the reduction of a
recorded device trace to metrics, finding a cell's files by name, and its
refusals.  Nothing here touches a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

from bench import check, spec, trace, work

DATA = Path(__file__).resolve().parent / "data"
ROOT = spec.ROOT
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_work_at_a_hand_computed_shape():
    # m=2 agents, N=4 rows, L=3, D=1: G's half is 3*4/2 = 6 entries of 4
    # products each (x2 FLOPs) per agent, R is 3 entries of 4 products
    assert work.gram_flops(2, 4, 3, 1) == 2 * (6 * 4 * 2 + 3 * 4 * 2)
    # H (4x3) and T (4x1) read once at 4 bytes, G (9) and R (3) written
    assert work.gram_bytes(2, 4, 3, 1, "fp32") == 2 * (16 * 4 + 12 * 4)
    assert work.gram_bytes(2, 4, 3, 1, "bf16") == 2 * (16 * 2 + 12 * 4)
    assert work.hidden_flops(2, 4, 5, 3) == 2 * 2 * 4 * 5 * 3
    assert work.admm_flops_per_iter(2, 3, 1) == 4 * 2 * 9
    assert work.fit_flops(2, 4, 5, 3, 1, 1, 10) == (
        work.hidden_flops(2, 4, 5, 3) + work.gram_flops(2, 4, 3, 1)
        + 10 * work.admm_flops_per_iter(2, 3, 1))


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000, 50, peak) == (10.0, "compute")
    assert work.roofline_s(100, 50, peak) == (5.0, "memory")


def test_unknown_device_kind_is_an_error(tmp_path):
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"cpu": {}}))
    with pytest.raises(KeyError):
        work.peaks("TPU v5 lite", other)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_finds_its_files_by_name(cell):
    c = spec.load(cell)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    assert c.chips == entry["chips"] == c.config["chips"]
    assert {"fit_s", "setup_s"} <= {m["name"] for m in c.end_to_end}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] == "fit_s"
        read = trace.load_metric_reader(m["name"], spec.BENCH / "metrics")
        assert callable(read)
    assert c.limits and set(c.limits) <= set(check.NUMBERS)
    assert all(v > 0 for v in c.limits.values())


def test_every_listed_file_exists_and_none_is_orphaned():
    metrics = {p.stem for p in (spec.BENCH / "metrics").glob("*.py")}
    assert metrics == {m["name"] for m in BENCHMARK["per_layer"]}
    configs = {c["file"] for c in BENCHMARK["configs"]}
    assert configs == {f"bench/configs/{p.name}"
                       for p in (spec.BENCH / "configs").glob("*.json")}
    for c in BENCHMARK["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    traffic = {w["traffic"] for w in BENCHMARK["workloads"]}
    assert traffic == {p.stem for p in (spec.BENCH / "traffic").glob("*.json")}


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="unknown workload"):
        spec.load("no-such-cell")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", str(2**33 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "refusing" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines()
                if ln.startswith("{")], proc.stdout


# --- the trace reduction, on a recorded trace --------------------------------


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_small.json").read_text())


def _ctx(recorded, **kw):
    base = dict(fits=recorded["fits"], window_s=recorded["trace"]["window"][1],
                iters=30, chips=len(recorded["trace"]["devices"]),
                work=recorded["work"], peak=work.peaks("TPU v5 lite"),
                spans=recorded["spans"], compiles=recorded["compiles"],
                trace=recorded["trace"])
    base.update(kw)
    return types.SimpleNamespace(**base)


def _read(name, ctx):
    return trace.load_metric_reader(name, spec.BENCH / "metrics")(ctx)


def test_union_merges_overlaps():
    assert trace.union([[3, 4], [0, 1], [0.5, 2], [4, 5]]) == [[0, 2], [3, 5]]
    assert trace.total(trace.union([[0, 2], [1, 3]])) == 3


def test_recorded_trace_reduces_to_its_expected_metrics(recorded):
    ctx = _ctx(recorded)
    for name, expected in recorded["expected"].items():
        got = _read(name, ctx)
        assert got == pytest.approx(expected, rel=1e-9), name


def test_shares_stay_within_100_percent(recorded):
    ctx = _ctx(recorded)
    for name in ("device_idle_share", "gram_roofline", "fit_mfu"):
        value = _read(name, ctx)
        assert value is None or 0.0 < value <= 100.0, (name, value)


def test_readers_find_nothing_without_a_trace(recorded):
    ctx = _ctx(recorded, trace=None)
    for name in ("device_idle_share", "gram_roofline", "eigh_ms_per_fit",
                 "admm_ms_per_iter"):
        assert _read(name, ctx) is None, name


def test_breakdown_names_gaps_by_host_span(recorded):
    gaps = trace.idle_gaps(recorded["trace"], recorded["spans"])
    assert gaps and len(gaps) <= 10
    names = {n for n, _ in gaps}
    assert names <= {s[0] for s in recorded["spans"]} | {"other"}
    ops = trace.top_ops(recorded["trace"])
    assert 0 < len(ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))


def test_readers_on_a_hand_made_trace():
    ops = [["%gram_batched.1 tpu_custom_call", 0.0, 1.0],
           ["%custom-call.2 EighTpu", 1.5, 1.7],
           ["%collective-permute-start.1", 3.0, 3.25]]
    mods = [["jit_gram_batched(1)", 0.0, 1.0], ["jit__lambda(2)", 1.4, 2.0],
            ["jit__unknown(3)", 2.5, 3.5]]
    lines = {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}
    tr = {"window": [0.0, 4.0],
          "devices": {"/device:TPU:0": lines, "/device:TPU:1": lines}}
    peak = work.peaks("TPU v5 lite")
    ctx = types.SimpleNamespace(
        fits=1, window_s=4.0, iters=2, chips=2, peak=peak, trace=tr,
        work={"gram_flops": 0.5 * peak["bf16_flops_per_s"], "gram_bytes": 1,
              "fit_flops": peak["bf16_flops_per_s"], "h2d_bytes": 2e9},
        spans=[["stats", 0.0, 1.2], ["h2d", 0.1, 0.3]],
        compiles=[["a", 0.1, 0.3], ["b", 0.2, 0.4]])
    expected = {
        "device_idle_share": 100.0 * (1 - 1.45 / 4.0),
        "eigh_ms_per_fit": 600.0,           # the program holding EighTpu
        "admm_ms_per_iter": 500.0,          # the one exchanging U, 2 iters
        "gram_roofline": 25.0,              # 0.5 s at peak over 2 chips, 1 s
        "compile_ms_per_fit": 300.0,        # union of overlapping events
        "stats_ms_per_fit": 1000.0,         # stats span less its h2d
        "h2d_gb_per_s": 10.0,
        "fit_mfu": 12.5,
    }
    for name, value in expected.items():
        assert _read(name, ctx) == pytest.approx(value, rel=1e-9), name
    assert trace.idle_gaps(tr, ctx.spans)[0][0] == "other"
    assert trace.top_ops(tr)[0] == ["%gram_batched.1 tpu_custom_call", 1.0]
