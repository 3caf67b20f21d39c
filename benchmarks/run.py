"""Benchmark driver — one module per paper table/figure (DESIGN.md §7).

Prints ``name,us_per_call,derived`` CSV lines and writes per-figure CSVs to
experiments/benchmarks/.

  fig3   convergence curves (MTL-ELM / DMTL-ELM / FO-DMTL-ELM)
  fig4   consensus / accuracy evolution vs the centralized solution
  resume checkpointable-runtime overhead: segmented + snapshotted runs vs
         the monolithic scan and a mid-run restore, bitwise-parity
         asserted per row → resume_overhead.csv
  table1 generalization vs Local-ELM / MTFL / GO-MTL / DGSP / DNSP
  fig5   error vs hidden width L (set BENCH_FIG5=1; slower sweep)
  fig6   communication-vs-accuracy trade-off
  precision  ADMM convergence from fp32 vs bf16 Gram statistics
  schedule  comm-rounds-vs-topology: compiled ppermute edge schedules
            (rounds vs the Δ+1 bound, message volume per iteration),
            incl. the expander/hypercube log-diameter overlays
  async     convergence-vs-delay×drop frontier of the netsim event-tape
            executor (fit_async) across topologies → async_frontier.csv
            (BENCH_SMOKE=1 shrinks the grid for CI)
  async_mesh  the same tapes replayed IN-MESH by the exchange-layer tape
            driver (8 emulated devices, subprocess) vs their fit_async
            oracle, with agreement deltas → mesh_async_frontier.csv
  robustness  consensus-vs-attack frontier: Byzantine adversary tapes ×
            robust aggregators × topologies (+ membership-churn cells)
            → robustness_frontier.csv (BENCH_SMOKE=1 shrinks the grid)
  robustness_mesh  mesh Byzantine cells: same adversary tape on fit_async
            vs the in-mesh tape driver per aggregator →
            mesh_robustness.csv + a dated BENCH_history entry
  obs       observability overhead: telemetry-on vs -off fits per
            executor + the span-traced run (target < 5% on dense) →
            obs_overhead.csv + a dated BENCH_history entry
  roofline  aggregated dry-run roofline table (deliverable g) + the
            analytic Gram-engine roofline (tri vs dense vs two-matmul)
  kernels   Pallas-kernel correctness probes, op timings (labeled
            interpret off-TPU), the Gram FLOPs/HBM cost model, and the
            machine-readable BENCH_kernels.json perf-trajectory artifact
            (written under experiments/benchmarks/ AND at the repo root)
"""

import os
import sys
import traceback
from pathlib import Path


def _compile_cache() -> None:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else a fixed, git-ignored path in the
    checkout, so a rerun of the same checkout finds its compiled code."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    root = Path(__file__).resolve().parents[1]
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def main() -> None:
    _compile_cache()
    from benchmarks import (
        asynchrony, communication, consensus, convergence, generalization,
        kernels, observability, robustness, roofline, topology,
    )

    suites = [
        ("fig3", convergence.run),
        ("sweeps", convergence.run_sweeps),
        ("fig4", consensus.run),
        ("resume", consensus.run_resume),
        ("table1", generalization.run),
        ("fig6", communication.run),
        ("precision", convergence.run_precision),
        ("topology", topology.run),
        ("schedule", topology.run_schedule),
        ("async", asynchrony.run),
        ("async_mesh", asynchrony.run_mesh),
        ("robustness", robustness.run),
        ("robustness_mesh", robustness.run_mesh),
        ("obs", observability.run),
        ("kernels", kernels.run),
        ("roofline", roofline.run),
    ]
    if os.environ.get("BENCH_FIG5"):
        from repro.configs.paper import usps_like
        suites.insert(3, ("fig5", lambda: generalization.run_fig5(usps_like())))
    failed = []
    for name, fn in suites:
        try:
            fn()
        except Exception:
            failed.append(name)
            print(f"{name}/ERROR,0.0,{traceback.format_exc(limit=1)!r}",
                  file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark suites failed: {failed}")


if __name__ == "__main__":
    main()
