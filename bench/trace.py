"""The profiler trace of a window, reduced to what the metric readers read.

A trace is kept as a plain dict, on one clock with the benchmark's own
spans (seconds since the window opened):

    {"window": [0.0, window_s],
     "devices": {"<device plane>": {"<line>": [[name, start, end], ...]}}}

The window's ``TraceAnnotation`` ("bench:window") anchors the trace's clock
to the host's: the host clock read just before it opened is time 0.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from pathlib import Path

ANCHOR = "bench:window"
# one plane per chip ("/device:TPU:0"); other device planes (such as
# "/device:CUSTOM:Megascale Trace") hold no ops of a chip
CHIP_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir) -> str:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {files}")
    return files[0]


def reduce_xplane(path, window_s: float) -> dict:
    """The chips' planes' events, shifted so the anchor annotation starts
    at 0."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    anchor = None
    devices = {}
    for plane in data.planes:
        if CHIP_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [[short_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns]
                            for ev in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = ev.start_ns
    if anchor is None:
        raise RuntimeError(f"no {ANCHOR!r} annotation in {path}")
    for lines in devices.values():
        for events in lines.values():
            for ev in events:
                ev[1] = (ev[1] - anchor) / 1e9
                ev[2] = (ev[2] - anchor) / 1e9
    return {"window": [0.0, float(window_s)], "devices": devices}


def short_name(name: str) -> str:
    """An op's name without its HLO text, keeping a custom call's target:
    ``%gram_batched.1 = (...) custom-call(...), custom_call_target=
    "tpu_custom_call", ...`` is ``%gram_batched.1 tpu_custom_call``."""
    head = name.split(" = ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head} {target.group(1)}" if target else head


def union(intervals) -> list[list[float]]:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted((float(s), float(e)) for s, e in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def events(trace: dict, line: str = OPS_LINE, match=None) -> dict:
    """{device: [[name, start, end], ...]} of one line, inside the window,
    optionally filtered by ``match(name)``."""
    lo, hi = trace["window"]
    out = {}
    for dev, lines in trace["devices"].items():
        evs = [[n, max(s, lo), min(e, hi)] for n, s, e in lines.get(line, ())
               if min(e, hi) > max(s, lo) and (match is None or match(n))]
        out[dev] = evs
    return out


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    per_dev = events(trace)
    if not per_dev:
        return 0.0
    return sum(total(union((s, e) for _, s, e in evs))
               for evs in per_dev.values()) / len(per_dev)


def device_time_s(trace: dict, match, line: str = OPS_LINE) -> float:
    """Union of the matching events' time, averaged over the devices."""
    per_dev = events(trace, line, match)
    if not per_dev:
        return 0.0
    return sum(total(union((s, e) for _, s, e in evs))
               for evs in per_dev.values()) / len(per_dev)


def program_time_s(trace: dict, pick) -> float:
    """Device time of the programs (events of the modules line) for which
    ``pick(module_name, names_of_its_ops)`` holds, averaged over devices."""
    mods = events(trace, MODULES_LINE)
    ops = events(trace)
    if not mods:
        return 0.0
    spent = 0.0
    for dev, evs in mods.items():
        dev_ops = sorted(ops.get(dev, ()), key=lambda ev: ev[1])
        starts = [ev[1] for ev in dev_ops]
        chosen = []
        for name, s, e in evs:
            inside = dev_ops[bisect.bisect_left(starts, s):
                             bisect.bisect_right(starts, e)]
            if pick(name, {op for op, _, _ in inside}):
                chosen.append((s, e))
        spent += total(union(chosen))
    return spent / len(mods)


def top_ops(trace: dict, n: int = 10) -> list:
    """The ``n`` op names with the most device time, averaged over devices."""
    per_dev = events(trace)
    acc = {}
    for evs in per_dev.values():
        for name, s, e in evs:
            acc[name] = acc.get(name, 0.0) + (e - s) / len(per_dev)
    return sorted(([k, v] for k, v in acc.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: dict, spans, n: int = 10) -> list:
    """Idle device time (first device) named by what the host was doing: the
    innermost span that covers each gap's midpoint, summed per name."""
    lo, hi = trace["window"]
    per_dev = events(trace)
    if not per_dev:
        return []
    busy = union((s, e) for _, s, e in next(iter(per_dev.values())))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    acc = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        covering = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = (min(covering, key=lambda sp: sp[2] - sp[1])[0]
                if covering else "other")
        acc[name] = acc.get(name, 0.0) + (e - s)
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[:n]


def load_metric_reader(name: str, metrics_dir: Path):
    """The ``read(ctx)`` function of ``metrics_dir/<name>.py``."""
    import importlib.util

    path = Path(metrics_dir) / f"{name}.py"
    module_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
