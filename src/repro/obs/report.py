"""Run reports: a markdown + JSON summary of one consensus run.

``render(diags, spans)`` folds the diagnostics trajectory (including the
``cfg.telemetry`` comm/aggregator counters when present), the tracer's
span list, and the health verdict into one human-readable markdown
document and a machine-readable dict; ``write`` persists both next to
the trace artifacts.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from repro.obs.health import HealthConfig, check_health

_COUNTER_KEYS = ("msgs_delivered", "msgs_stale", "msgs_dropped",
                 "agg_rejected", "comm_floats")


def _span_breakdown(spans) -> list[dict]:
    """Total wall time per span name, top-level spans only (depth 0), so
    nested segment/snapshot time is not double counted.  ``compiles``
    counts the compile events by kind (the tracer's compile counter) in those
    spans and every span under them; ``programs`` counts the segment
    programs reused (``hit``) and built (``miss``) there."""
    spans = list(spans or [])
    totals: dict[str, dict] = {}
    for s in spans:
        if s.get("depth", 0) != 0:
            continue
        row = totals.setdefault(s["name"], {"name": s["name"], "count": 0,
                                            "total_us": 0.0, "compiles": {},
                                            "programs": {}})
        row["count"] += 1
        row["total_us"] += float(s["dur"])
    by_id = {s["id"]: s for s in spans if "id" in s}
    for s in spans:
        program = s.get("args", {}).get("program")
        if not s.get("compiles") and program is None:
            continue
        root = s
        while root.get("parent") is not None:
            root = by_id[root["parent"]]
        row = totals[root["name"]]
        for kind, funs in s.get("compiles", {}).items():
            row["compiles"][kind] = row["compiles"].get(kind, 0) + sum(
                n for n, _ in funs.values())
        if program is not None:
            row["programs"][program] = row["programs"].get(program, 0) + 1
    return sorted(totals.values(), key=lambda r: -r["total_us"])


def render(
    diags: dict,
    spans=None,
    meta: dict | None = None,
    health_cfg: HealthConfig | None = None,
) -> tuple[str, dict]:
    """Returns ``(markdown, data)`` summarizing one run."""
    obj = np.asarray(diags["objective"], np.float64)
    cons = np.asarray(diags.get("consensus", []), np.float64)
    verdict = check_health(diags, health_cfg)
    data: dict = {
        "iterations": int(obj.size),
        "objective_first": float(obj[0]) if obj.size else None,
        "objective_final": float(obj[-1]) if obj.size else None,
        "consensus_final": float(cons[-1]) if cons.size else None,
        "health": verdict,
        "meta": dict(meta or {}),
        "time_breakdown": _span_breakdown(spans),
    }
    comm = {}
    for key in _COUNTER_KEYS:
        if key in diags:
            arr = np.asarray(diags[key], np.float64)
            comm[key + "_total"] = float(arr.sum())
    if "resid_max" in diags:
        comm["resid_max_final"] = float(
            np.asarray(diags["resid_max"], np.float64)[-1])
    data["comm"] = comm

    lines = ["# Run report", ""]
    if meta:
        lines += ["## Run", ""]
        lines += [f"- **{k}**: {v}" for k, v in sorted(meta.items())]
        lines += [""]
    lines += ["## Outcome", ""]
    status = "healthy" if verdict["healthy"] else (
        f"DNF (`{verdict['dnf_reason']}` at iteration {verdict['at_iter']})")
    lines += [
        f"- **iterations**: {data['iterations']}",
        f"- **objective**: {data['objective_first']} → "
        f"{data['objective_final']}",
        f"- **final consensus**: {data['consensus_final']}",
        f"- **health**: {status}",
        "",
    ]
    if comm:
        lines += ["## Communication", ""]
        lines += [f"- **{k}**: {v}" for k, v in sorted(comm.items())]
        lines += [""]
    if data["time_breakdown"]:
        lines += ["## Time breakdown (top-level spans)", "",
                  "| span | count | total ms | compile events "
                  "| segment programs |",
                  "| --- | ---: | ---: | --- | --- |"]
        lines += [
            f"| {r['name']} | {r['count']} | {r['total_us'] / 1e3:.3f} | "
            + ", ".join(f"{k} {n}" for k, n in sorted(r["compiles"].items()))
            + " | "
            + ", ".join(f"{k} {n}" for k, n in sorted(r["programs"].items()))
            + " |"
            for r in data["time_breakdown"]
        ]
        lines += [""]
    return "\n".join(lines), data


def write(
    trace_dir,
    diags: dict,
    spans=None,
    meta: dict | None = None,
    health_cfg: HealthConfig | None = None,
) -> dict:
    """Render and persist report.md + report.json under ``trace_dir``."""
    trace_dir = Path(trace_dir)
    trace_dir.mkdir(parents=True, exist_ok=True)
    md, data = render(diags, spans, meta, health_cfg)
    md_path = trace_dir / "report.md"
    json_path = trace_dir / "report.json"
    md_path.write_text(md)
    with json_path.open("w") as f:
        json.dump(data, f, indent=2)
    return {"markdown": md_path, "json": json_path, "data": data}
