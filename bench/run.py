"""On-chip benchmark of the DMTL-ELM fit: one cell, one seed, one process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data and hidden-layer weights on the device from
``--seed`` and runs one warm-up fit; the window then runs whole fits back
to back for ``--seconds`` seconds (a fit that starts inside the window runs
to its end).  Once the window has closed, the last fit and one fit drawn
from the seed are checked against the plain reference (``bench/reference.py``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device`` and, traced, ``breakdown``;
its last key, ``checks``, gives each compared number beside its limit, as
do the last lines of standard error.

Refuses to run (exit 2, no result) unless JAX's devices are TPUs, as many
as the cell asks for.  JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` of the checkout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import (  # noqa: E402
    check, data, fitjob, reference, spec, trace, work)

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileLog:
    """jax.monitoring's compile events, as host-clock spans (wall time)."""

    def __init__(self):
        self.items: list[list] = []

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.items.append([event, start, end])

    def _duration(self, event, duration, **_):
        if event == CACHE_EVENT:
            now = time.time()
            self.items.append([event, now - duration, now])

    def since(self, origin: float) -> list[list]:
        return [[e, s - origin, t - origin] for e, s, t in self.items
                if s >= origin]


def use_compile_cache() -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def work_of(cell: spec.Cell) -> dict:
    c, t = cell.config, cell.traffic
    m, N, L, D = c["m"], c["N"], c["L"], c["D"]
    return {
        "fit_flops": work.fit_flops(m, N, c["d_in"], L, D, c["r"], t["iters"]),
        "gram_flops": work.gram_flops(m, N, L, D),
        "gram_bytes": work.gram_bytes(m, N, L, D, t["stats_precision"]),
        "h2d_bytes": (m * N * c["d_in"] * 2 + m * N * D * 4
                      if t["feed"] == "stream" else 0),
    }


def reference_parts(job) -> list:
    """[(times the stream reads it, X, T)] over the job's distinct data."""
    if job.traffic["feed"] == "resident":
        return [(1, job.X, job.T)]
    counts = np.bincount([job.chunk_of(i) for i in range(job.n_chunks)],
                         minlength=len(job.host_chunks))
    return [(n, X, T) for n, (X, T) in zip(counts, job.host_chunks)]


def reference_stats(job, precision="highest", rows=slice(None)):
    """(G, R, t2) float64 of the job's data by the plain reference, over
    each agent's ``rows`` of every part."""
    c = job.config
    m = c["m"]
    G = np.zeros((m, c["L"], c["L"]))
    R = np.zeros((m, c["L"], c["D"]))
    t2 = np.zeros(m)
    parts = reference_parts(job)
    dev = (next(iter(job.W.devices())) if job.mesh is None
           else job.mesh.devices.flat[0])
    W, b = jax.device_put((job.W, job.b), dev)
    for count, X, T in parts:
        for a in range(m):
            Xa, Ta = jax.device_put((X[a][rows], T[a][rows]), dev)
            Ga, Ra = reference.agent_stats(Xa, Ta, W, b, precision)
            G[a] += count * Ga
            R[a] += count * Ra
            t2[a] += count * float(np.sum(np.asarray(Ta, np.float64) ** 2))
    return G, R, t2


def reference_fit(job, ref, index: int, precision="float64", eig=None):
    """(U, A, objective per iteration) of fit ``index`` by the reference."""
    c = job.config
    U0, A0 = jax.device_get(job.start(job.keys.start, index))
    return reference.admm(
        *ref, reference.edges(c["graph"], c["m"]), U0, A0,
        iters=job.traffic["iters"], precision=precision, eig=eig,
        **{k: c[k] for k in ("mu1", "mu2", "tau", "zeta", "gamma_floor")})


def gaps(stats, fit, ref, ref_fit) -> dict:
    """The compared numbers of one fit: ``stats`` (G, R) and ``fit``
    (U, A, objective) against the reference's."""
    return {**check.stats_gaps(stats[0], stats[1], ref[0], ref[1]),
            **check.admm_gaps(fit[2], reference.heads(fit[0], fit[1]),
                              ref_fit[2], reference.heads(ref_fit[0],
                                                          ref_fit[1]),
                              ref[0])}


def reference_check(job, fits: list, limits: dict):
    """(correct, {name: {value, limit}}) over the compared fits."""
    ref = reference_stats(job)
    eig = np.linalg.eigh(ref[0])
    worst = None
    for fit in fits:
        g = gaps(jax.device_get((fit.stats.G, fit.stats.R)),
                 (fit.U, fit.A, fit.objective), ref,
                 reference_fit(job, ref, fit.index, eig=eig))
        worst = g if worst is None else check.worst(worst, g)
    print("readings " + " ".join(f"{k}={v!r}" for k, v in worst.items()),
          flush=True)
    return check.judge(worst, limits)


def per_layer_metrics(cell: spec.Cell, ctx) -> dict:
    out = {}
    for metric in cell.per_layer:
        read = trace.load_metric_reader(metric["name"],
                                        spec.BENCH / "metrics")
        value = read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            devices, t_process: float = T_PROCESS) -> dict:
    """Set-up, the window, the check; returns the result line's object.
    Runs on whatever ``devices`` it is given (the tests give CPUs)."""
    with CompileLog() as compiles:
        job = fitjob.FitJob(cell.config, cell.traffic, seed, devices,
                            fitjob.Spans(traced))
        job.fit(0)                                  # warm-up: every shape
        setup_s = time.perf_counter() - t_process
        print(f"setup_s={setup_s!r}", flush=True)

        reservoir = data.Reservoir(job.keys.sample)
        job.spans = spans = fitjob.Spans(traced)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
        if traced:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # host spans, no Python calls
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        attempted = failed = 0
        last = None
        t0 = time.perf_counter()
        spans.origin = time.time()
        with jax.profiler.TraceAnnotation(trace.ANCHOR):
            while time.perf_counter() - t0 < seconds:
                attempted += 1
                try:
                    last = job.fit(attempted)
                except Exception as e:  # a failed fit fails the run
                    print(f"fit {attempted} raised {type(e).__name__}: {e}",
                          file=sys.stderr, flush=True)
                    failed += 1
                    break
                if not (np.all(np.isfinite(last.objective))
                        and np.all(np.isfinite(last.U))
                        and np.all(np.isfinite(last.A))):
                    failed += 1
                reservoir.offer(attempted - 1, last)
        window_s = time.perf_counter() - t0
        if traced:
            jax.profiler.stop_trace()
    fits = attempted - failed
    peak = memory_peak(devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    print(f"window_s={window_s!r} fits={attempted} failed={failed} "
          f"fit_s={window_s / max(attempted, 1)!r}", flush=True)

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if traced:
        tr = trace.reduce_xplane(trace.find_xplane(trace_dir), window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)
        pk = work.peaks(devices[0].device_kind)
        w = work_of(cell)
        bound = work.roofline_s(w["gram_flops"], w["gram_bytes"], pk)
        print(f"gram roofline: {bound[1]}-bound, {bound[0]!r} s per fit at "
              f"peak ({w['gram_flops']} FLOP, {w['gram_bytes']} B)",
              flush=True)
        all_spans = spans.items + [["compile", s, e] for _, s, e
                                   in compiles.since(spans.origin)]
        ctx = types.SimpleNamespace(
            fits=fits, window_s=window_s, iters=cell.traffic["iters"],
            chips=len(devices), work=w, peak=pk, spans=spans.items,
            compiles=compiles.since(spans.origin), trace=tr)
        result["metrics"] = per_layer_metrics(cell, ctx)
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = window_s
        result["breakdown"] = {
            "device_ops": trace.top_ops(tr),
            "idle_gaps": trace.idle_gaps(tr, all_spans)}
    else:
        result["metrics"] = {
            "fit_s": {"value": window_s / max(fits, 1), "unit": "s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device

    # the check, once the window has closed and the peak has been read;
    # only the compared fits' outputs are kept
    compared = [last] if last is not None else []
    if reservoir.item is not None and reservoir.item is not last:
        compared.append(reservoir.item)
    del reservoir
    t_ref = time.perf_counter()
    if compared and not failed:
        ok, checks = reference_check(job, compared, cell.limits)
    else:
        ok, checks = False, {}
    print(f"reference_s={time.perf_counter() - t_ref!r}", flush=True)
    result["correct"] = bool(ok) and failed == 0 and fits > 0
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs TPU chips, but JAX's devices are "
              f"{devices[0].platform!r} ({devices[0].device_kind}); refusing "
              f"to run", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    print(f"device platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)} "
          f"cache={cache}", flush=True)
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices[: cell.chips])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
