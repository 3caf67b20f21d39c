"""Readings that the limits in ``bench/limits/`` are set from, at a cell's own
size on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 [--controls K]

For each seed, in one process: the cell's set-up, a warm-up fit and ``F``
fits through the timed path (``--fits``, default 1; each starts from its own
state drawn from the seed, as the window's fits do), then the numbers that
``bench/check.py`` compares, of each fit against the plain reference
(``program``: the worst over those fits, which sets a limit's lower end;
``program_fits``: each fit's).  For the first ``K`` seeds also, of the
first of those fits:

* ``control``: the reference itself put in the program's place, computed
  one precision lower (every product of the statistics at HIGH, three bf16
  passes, on the device; the ADMM in float32 with three-pass products);
  it sets the upper end, and is judged against the cell's limits;
* ``half_batch``: the reference put in the program's place with half of
  each agent's rows left out and the statistics of the rest doubled (a
  fault of the timed path), also judged;
* looks at where the heads' gap comes from, none of them a limit's end:
  ``float32`` the reference's round in plain float32 on its own
  statistics; ``program_stats`` the float64 round on the program's
  statistics; ``program_eigh`` the float64 round on the reference's
  statistics with the eigh factors the program's U-solve uses (JAX's eigh
  on the device, of the program's G); ``eigh_error``, how far those factors
  and LAPACK's float32 ones are from an eigendecomposition;
* ``default_precision``: the program's statistics with its hidden layer at
  JAX's default matmul precision, as ``dmtl_elm.fit`` runs it when nothing
  sets one.

Prints one JSON line per seed, then the largest program reading and the
smallest control and fault readings of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, reference, run, spec  # noqa: E402


def eigh_error(G, eig) -> dict:
    """How far ``eig`` = (w, Q) is from an eigendecomposition of ``G``, at
    its worst over agents: max|G Q - Q diag(w)| / max|G| (backward) and
    max|Q^T Q - I| (orthogonality)."""
    import numpy as np

    G = np.asarray(G, np.float64)
    w, q = (np.asarray(x, np.float64) for x in eig)
    back = np.max(np.abs(G @ q - q * w[:, None, :]), axis=(1, 2))
    orth = np.abs(np.swapaxes(q, 1, 2) @ q - np.eye(q.shape[-1]))
    return {"backward": float(np.max(back / np.max(np.abs(G), axis=(1, 2)))),
            "orthogonality": float(np.max(orth))}


def readings(cell: spec.Cell, seed: int, devices, control: bool,
             n_fits: int = 1) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import fitjob

    t0 = time.perf_counter()
    job = fitjob.FitJob(cell.config, cell.traffic, seed, devices,
                        fitjob.Spans(False))
    job.fit(0)
    fits = [job.fit(i) for i in range(1, n_fits + 1)]
    fit = fits[0]
    t_fit = time.perf_counter() - t0
    ref = run.reference_stats(job)
    ref_eig = np.linalg.eigh(ref[0])
    each = []
    for f in fits:
        ref_f = run.reference_fit(job, ref, f.index, eig=ref_eig)
        each.append(run.gaps(jax.device_get((f.stats.G, f.stats.R)),
                             (f.U, f.A, f.objective), ref, ref_f))
        if f is fit:
            ref_fit = ref_f
    program = jax.device_get((fit.stats.G, fit.stats.R))
    worst = each[0]
    for g in each[1:]:
        worst = check.worst(worst, g)
    out = {"seed": seed, "program": worst, "program_fits": each}
    t_ref = time.perf_counter() - t0 - t_fit
    if control:
        def against_ref(stats, fit_of):
            return run.gaps(stats[:2], fit_of, ref, ref_fit)

        ctl = run.reference_stats(job, "high")
        out["control"] = against_ref(
            ctl, run.reference_fit(job, ctl, 1, precision="high"))
        half = run.reference_stats(job, rows=slice(None, None, 2))
        half = tuple(2.0 * x for x in half)
        out["half_batch"] = against_ref(half, run.reference_fit(job, half, 1))
        for name in ("control", "half_batch"):
            ok, _ = check.judge(out[name], cell.limits)
            out[name + "_correct"] = ok
        out["float32"] = against_ref(
            ref, run.reference_fit(job, ref, 1, precision="float32"))
        ps = program + (ref[2],)
        out["program_stats"] = against_ref(ps, run.reference_fit(job, ps, 1))
        eig = jax.device_get(jnp.linalg.eigh(fit.stats.G))
        out["program_eigh"] = against_ref(
            ref, run.reference_fit(job, ref, 1, eig=eig))
        out["eigh_error"] = {
            "program": eigh_error(program[0], eig),
            "float32": eigh_error(ref[0], reference.eigh(
                ref[0].astype(np.float32)))}
        job.hidden = jax.jit(lambda X, W, b: fitjob.ELMFeatureMap(W=W, b=b)(X))
        st = job.stats()
        out["default_precision"] = check.stats_gaps(
            *jax.device_get((st.G, st.R)), ref[0], ref[1])
    out["seconds"] = {"setup_and_fits": t_fit, "reference": t_ref,
                      "looks": time.perf_counter() - t0 - t_fit - t_ref}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=0,
                    help="how many of the seeds, the first, also read the "
                         "control, the fault and the looks")
    ap.add_argument("--fits", type=int, default=1,
                    help="fits per seed read against the reference")
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"calibrate: needs {cell.chips} TPU chips, JAX sees "
              f"{len(devices)} {devices[0].platform} devices", file=sys.stderr)
        return 2
    run.use_compile_cache()
    rows = []
    for i, seed in enumerate(args.seeds):
        rows.append(readings(cell, seed, devices[: cell.chips],
                             i < args.controls, args.fits))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"lower": {k: max(r["program"][k] for r in rows)
                         for k in rows[0]["program"]}}
    for name in ("control", "half_batch"):
        got = [r[name] for r in rows if name in r]
        if got:
            summary[name] = {k: min(g[k] for g in got) for k in got[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
