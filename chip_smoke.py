"""Smoke run of the DMTL-ELM stats-and-ADMM fit on a TPU.

One chip (the default) drives the main path once, through the entry points
a user calls, at the size the repo supports:

* raw features at the hidden width of the repo's ``qwen3-8b`` config
  (d_in = 4096) for m = 8 agents x N = 8192 samples, drawn from ``--seed``
  by ``data.synthetic.multitask_classification`` (the tasks share a latent
  class subspace), with D = 10 one-hot targets;
* the frozen ELM hidden layer (``make_feature_map``) maps them to L = 2048;
* the stats pass (``engine.produce_stats``) runs the triangular Gram kernel
  in fp32 and bf16, and G, R are checked against a HIGHEST-precision
  einsum on the device;
* ``dmtl_elm.fit(executor="dense")`` runs on ring(8) with r = 8, with the
  Sylvester and the PCG U-solve, from fp32 and from bf16 statistics; each
  trajectory must be finite and its objective and consensus residual must
  fall;
* the first ADMM iterations are rerun under
  ``jax.default_matmul_precision("highest")`` on the same statistics; the
  objective must not move by more than ``PRECISION_TOL`` (every matmul on
  the path computes what it computes on the CPU).

``--chips 4`` runs only the sharded path and what it is compared with:
m = 4 agents, one per chip, each agent's statistics produced on its own
chip (no device holds the (m, L, L) stack), then ``fit(executor=
"sharded")`` on ring(4) (the ring fast path) and on star(4) (the
compiled-schedule graph path), and each of those runners against
``engine.fit_dense`` on the same statistics from the same generic start
state, with the Sylvester U-solve, over a short horizon.

    python chip_smoke.py [--seed S]
    python chip_smoke.py --chips 4

Refuses to run (exit 2, no result line) unless JAX's first device is a TPU.
Prints one line per measurement, then as its last line one JSON object:
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  JAX's persistent
compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` of this checkout.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.qwen3_8b import make_config  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.dmtl_elm import DMTLELMConfig, fit  # noqa: E402
from repro.core.elm import ELMFeatureMap, make_feature_map  # noqa: E402
from repro.core.graph import ring, star  # noqa: E402
from repro.data.synthetic import multitask_classification  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST

# max |G - G_ref| / max |G_ref| (and the same for R) against the
# HIGHEST-precision einsum.  fp32 tiles accumulate in fp32: ~1e-6 expected,
# 1e-4 bound.  bf16 tiles carry 2^-8 relative rounding per entry of H:
# the op's documented bound is 3e-2.
STATS_TOL = {"fp32": 1e-4, "bf16": 3e-2}
# Largest relative move of the fp32-stats objective over PRECISION_ITERS
# ADMM iterations when the phase reruns with every matmul at HIGHEST.  One
# bf16 MXU pass (DEFAULT on a TPU) rounds each product to 2^-8 and moves
# it by ~1e-3; fp32-faithful matmuls leave reduction-order noise (~1e-7).
# Two iterations, because from U = ones the r columns of U coincide and
# later iterations amplify any difference at all (see SHARDED_OBJ_TOL).
PRECISION_TOL = 1e-4
PRECISION_ITERS = 2
# fit_sharded / fit_sharded_graph vs fit_dense on the same statistics,
# from the same generic start state (U, A drawn from the seed) with the
# Sylvester (direct, eigh) U-solve, over SHARDED_ITERS iterations: largest
# relative objective difference per iteration, and max |d(UA)| / max |UA|
# (the heads each agent predicts with; U alone is only defined up to a
# rotation that A undoes).  The executors share one agent_update body;
# reduction order and the batching of eigh differ.  On four emulated CPU
# devices (L = 256 and 512, 4 iterations) they agree to 2.6e-6 in
# objective and 9.2e-5 in UA.  The same runs with two agents' statistics
# swapped (a wrong neighbour mapping), or with the ring's incoming dual
# added instead of subtracted, are 7e-3 to 2e-2 off in objective and 0.49
# to 1.4 in UA.  The bounds sit about 100x above the agreement.  Neither PCG nor the
# all-ones start of fit() makes a usable comparison: fp32 PCG stops at its
# iteration cap short of its tolerance, and from U = ones the r columns of
# U coincide, so rounding decides when they separate.
SHARDED_OBJ_TOL = 1e-4
SHARDED_UA_TOL = 1e-2
SHARDED_ITERS = 4
# ADMM settings of every fit here: the backbone example's (r = 8, unit
# regularizers, tau = 2 + degree) with a fixed dual step (gamma = 1).  The
# adaptive gamma rule collapses at these statistics' scale (G ~ N) and the
# consensus residual then plateaus instead of falling.
ADMM = dict(mu1=1.0, mu2=1.0, tau=2.0, zeta=1.0, gamma_floor=1.0)


@dataclasses.dataclass(frozen=True)
class Size:
    m: int
    N: int
    d_in: int
    L: int
    D: int = 10
    r: int = 8
    iters: int = 30


ONE_CHIP = Size(m=8, N=8192, d_in=make_config().d_model, L=2048)
FOUR_CHIPS = Size(m=4, N=8192, d_in=make_config().d_model, L=2048,
                  iters=SHARDED_ITERS)


class Smoke:
    """Collects printed measurements, per-phase compile times and failed
    checks; every phase runs even after an earlier check failed."""

    def __init__(self):
        self.failures: list[str] = []
        self._compile = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile += duration

    def phase(self, name, fn, *args, **kwargs):
        """Run one phase; print its wall time and the XLA compile time
        spent inside it.  Returns fn's result (None if it raised)."""
        c0, t0 = self._compile, time.perf_counter()
        try:
            out = fn(*args, **kwargs)
            if not hasattr(out, "as_text"):      # a Compiled has no buffers
                jax.block_until_ready(out)
        except Exception as e:  # a failed phase fails the run, not the rest
            traceback.print_exc()
            self.fail(f"{name} raised {type(e).__name__}: {e}")
            out = None
        print(f"phase {name}: wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={self._compile - c0:.3f}", flush=True)
        return out

    def check(self, ok, msg):
        if not ok:
            self.fail(msg)

    def fail(self, msg):
        self.failures.append(msg)
        print(f"FAIL {msg}", file=sys.stderr, flush=True)


def _compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))


def _versions() -> str:
    out = [f"jax={jax.__version__}"]
    for pkg in ("jaxlib", "libtpu", "libtpu-nightly"):
        try:
            out.append(f"{pkg}={importlib.metadata.version(pkg)}")
        except importlib.metadata.PackageNotFoundError:
            pass
    return " ".join(out)


def make_data(key, size: Size, sharding=None):
    """(X, T): raw features (m, N, d_in) and one-hot targets (m, N, D)."""
    def gen(k):
        d = multitask_classification(
            k, m=size.m, n_train=size.N, n_test=1, n_in=size.d_in,
            n_global_classes=2 * size.D, n_cls=size.D,
        )
        return d.X_train, d.Y_train

    if sharding is None:
        return jax.jit(gen)(key)
    return jax.jit(gen, out_shardings=(sharding, sharding))(key)


def _hidden(X, W, b):
    """The frozen ELM hidden layer, its weights passed as arguments rather
    than baked into the program as constants."""
    return ELMFeatureMap(W=W, b=b)(X)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _traj_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _check_trajectory(s: Smoke, name, diags, U, A, falling=True):
    obj = np.asarray(diags["objective"])
    cons = np.asarray(diags["consensus"])
    finite = bool(np.all(np.isfinite(obj)) and np.all(np.isfinite(cons))
                  and np.all(np.isfinite(np.asarray(U)))
                  and np.all(np.isfinite(np.asarray(A))))
    peak = int(np.argmax(cons))
    print(f"fit {name}: objective {obj[0]!r} -> {obj[-1]!r} "
          f"consensus {cons[0]!r} -> peak {cons[peak]!r} at {peak} -> "
          f"{cons[-1]!r} finite={finite}", flush=True)
    s.check(finite, f"fit {name}: non-finite values in the trajectory")
    if not falling:     # a short comparison horizon: finite is enough
        return
    s.check(obj[-1] < obj[0], f"fit {name}: objective did not fall")
    # every agent starts from the same U, so the first residual is ~0 by
    # construction; the residual must fall from the peak the local fits
    # open up, and still be falling over the second half of the run
    s.check(cons[-1] < cons[peak] and cons[-1] <= cons[len(cons) // 2],
            f"fit {name}: consensus did not fall")


def _generic_start(runner, key):
    """The runner's k = 0 state with U and A drawn from ``key``."""
    st = runner.init_state()
    kU, kA = jax.random.split(key)
    U = jax.random.normal(kU, st.U.shape, st.U.dtype)
    A = jax.random.normal(kA, st.A.shape, st.A.dtype)
    sh = runner.state_shardings()
    if sh is not None:
        U, A = jax.device_put(U, sh.U), jax.device_put(A, sh.A)
    return st._replace(U=U, A=A)


def run_one_chip(s: Smoke, size: Size, seed: int, expect_kernel=True):
    """The main path on one device.  ``expect_kernel`` is False only for
    the CPU rehearsal, where the stats pass is the jnp reference."""
    kd, kf = jax.random.split(jax.random.PRNGKey(seed))
    data = s.phase("data", make_data, kd, size)
    fmap = make_feature_map(kf, size.d_in, size.L, dist="normal")
    H = s.phase("hidden", jax.jit(_hidden), data[0], fmap.W, fmap.b)
    T = data[1]
    del data
    ref = s.phase("stats_ref_highest", jax.jit(lambda H, T: (
        jnp.einsum("mnl,mnk->mlk", H, H, precision=HIGHEST),
        jnp.einsum("mnl,mnd->mld", H, T, precision=HIGHEST))), H, T)

    g = ring(size.m)
    stats_fp32 = None
    for prec in ("fp32", "bf16"):
        def stats_fn(H, T, prec=prec):
            return engine.produce_stats(H, T, precision=prec)

        compiled = s.phase(f"stats_{prec}_compile",
                           lambda: jax.jit(stats_fn).lower(H, T).compile())
        if compiled is not None:
            has_kernel = "tpu_custom_call" in compiled.as_text()
            print(f"stats {prec}: tpu_custom_call={has_kernel}", flush=True)
            if expect_kernel:
                s.check(has_kernel, f"stats {prec}: the compiled stats "
                        f"program has no Pallas kernel")
        st = s.phase(f"stats_{prec}", stats_fn, H, T)
        if st is not None and ref is not None:
            eg, er = _rel(st.G, ref[0]), _rel(st.R, ref[1])
            print(f"stats {prec}: max_rel_err G={eg!r} R={er!r} "
                  f"tol={STATS_TOL[prec]}", flush=True)
            s.check(eg <= STATS_TOL[prec] and er <= STATS_TOL[prec],
                    f"stats {prec}: G/R off the HIGHEST reference "
                    f"(G {eg:.3e}, R {er:.3e})")
        if prec == "fp32":
            stats_fp32 = st
        for solver in ("sylvester", "pcg"):
            cfg = DMTLELMConfig(r=size.r, iters=size.iters, u_solver=solver,
                                stats_precision=prec, **ADMM)
            out = s.phase(f"fit_{prec}_{solver}", fit, H, T, g, cfg)
            if out is not None:
                _check_trajectory(s, f"{prec}/{solver}", out[1], out[0].U,
                                  out[0].A)

    # precision of the ADMM phase: as coded vs every matmul at HIGHEST,
    # over PRECISION_ITERS iterations of the dense runner on the same
    # statistics (the eigh set-up runs once, outside the context: it is
    # float32-precise inside already)
    if stats_fp32 is not None:
        for solver in ("sylvester", "pcg"):
            cfg = DMTLELMConfig(r=size.r, iters=PRECISION_ITERS,
                                u_solver=solver, **ADMM)
            runner = s.phase(f"admm_{solver}_setup", engine.make_runner,
                             stats_fp32, g, cfg)
            if runner is None:
                continue
            coded = s.phase(f"admm_{solver}", runner.run)

            def highest(runner=runner):
                with jax.default_matmul_precision("highest"):
                    return runner.run()

            hi = s.phase(f"admm_{solver}_highest", highest)
            if coded is not None and hi is not None:
                d = _traj_rel(coded[1]["objective"], hi[1]["objective"])
                print(f"admm {solver}: objective rel diff as-coded vs "
                      f"HIGHEST over {PRECISION_ITERS} iters={d!r} "
                      f"tol={PRECISION_TOL}", flush=True)
                s.check(d <= PRECISION_TOL, f"admm {solver}: matmul "
                        f"precision moves the objective by {d:.3e}")


def run_four_chips(s: Smoke, size: Size, seed: int, devices):
    """The sharded path on four devices and its dense comparison."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    axes = ("agents",)
    mesh = Mesh(np.array(devices[: size.m]), axes)
    sh = NamedSharding(mesh, P(axes))
    kd, kf = jax.random.split(jax.random.PRNGKey(seed))
    data = s.phase("data", make_data, kd, size, sh)
    fmap = make_feature_map(kf, size.d_in, size.L, dist="normal")
    H = s.phase("hidden", jax.jit(_hidden, out_shardings=sh), data[0],
                fmap.W, fmap.b)
    T = data[1]
    del data

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in mesh.devices.flat]

    before = in_use()
    stats = s.phase("stats_sharded", engine.produce_stats, H, T, mesh=mesh,
                    agent_axes=axes)
    if stats is None:
        return
    after = in_use()
    stack = size.m * size.L * size.L * 4
    shards = [(x.device, x.data.shape) for x in stats.G.addressable_shards]
    print(f"stats sharded: G.sharding={stats.G.sharding} shards="
          f"{[tuple(sh_) for _, sh_ in shards]}", flush=True)
    print(f"stats sharded: bytes_in_use before={before} after={after} "
          f"full_stack={stack}", flush=True)
    s.check(stats.G.sharding.is_equivalent_to(sh, 3),
            "stats sharded: G is not laid out one agent per device")
    s.check(len({d for d, _ in shards}) == size.m
            and all(shp == (1, size.L, size.L) for _, shp in shards),
            "stats sharded: some device holds more than its own agent's G")
    s.check(all(a - b < stack for a, b in zip(after, before)),
            "stats sharded: a device grew by the whole stats stack")

    # sylvester: the eigh set-up is hoisted out of the segment programs, so
    # both sharded runners share one compile of it, and both dense runs
    # another
    cfg = DMTLELMConfig(r=size.r, iters=size.iters, u_solver="sylvester",
                        **ADMM)
    stats_one = jax.device_put(stats, mesh.devices.flat[0])
    start_key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    for name, g, executor in (("ring", ring(size.m), "sharded"),
                              ("graph_star", star(size.m), "sharded_graph")):
        # the entry point, from its own all-ones start
        out = s.phase(f"fit_sharded_{name}", fit, H, T, g, cfg,
                      executor="sharded", mesh=mesh, agent_axes=axes)
        if out is not None:
            _check_trajectory(s, f"sharded_{name}", out[2], out[0], out[1],
                              falling=False)
        # the comparison, from a generic start
        runs = {}
        for kind, st, kw in (
                ("sharded", stats, dict(executor=executor, mesh=mesh,
                                        agent_axes=axes)),
                ("dense", stats_one, dict(executor="dense"))):
            runner = engine.make_runner(st, g, cfg, **kw)
            runs[kind] = s.phase(f"cmp_{kind}_{name}", runner.run,
                                 _generic_start(runner, start_key))
        if runs["sharded"] is None or runs["dense"] is None:
            continue
        (sh_state, sh_d), (de_state, de_d) = runs["sharded"], runs["dense"]
        _check_trajectory(s, f"cmp_sharded_{name}", sh_d, sh_state.U,
                          sh_state.A, falling=False)
        per_iter = (np.abs(np.asarray(sh_d["objective"], np.float64)
                           - np.asarray(de_d["objective"], np.float64))
                    / np.abs(np.asarray(de_d["objective"], np.float64)))
        d_w = _rel(jnp.matmul(sh_state.U, sh_state.A, precision=HIGHEST),
                   jnp.matmul(de_state.U, de_state.A, precision=HIGHEST))
        print(f"sharded {name} vs dense over {size.iters} iters from a "
              f"generic start: objective rel diff per iteration "
              f"{per_iter.tolist()!r} (tol {SHARDED_OBJ_TOL}) UA rel diff="
              f"{d_w!r} (tol {SHARDED_UA_TOL})", flush=True)
        s.check(per_iter.max() <= SHARDED_OBJ_TOL and d_w <= SHARDED_UA_TOL,
                f"sharded {name} disagrees with dense (objective "
                f"{per_iter.max():.3e}, UA {d_w:.3e})")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    print(f"peak_bytes_in_use per device={peaks}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU chip, but JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    _compile_cache()
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"versions {_versions()}", flush=True)
    for var in ("LIBTPU_INIT_ARGS", "JAX_COMPILATION_CACHE_DIR"):
        print(f"{var}={os.environ.get(var, '')!r}", flush=True)

    s = Smoke()
    if args.chips == 4:
        run_four_chips(s, FOUR_CHIPS, args.seed, devices)
    else:
        run_one_chip(s, ONE_CHIP, args.seed)
        print(f"peak_bytes_in_use={dev.memory_stats().get('peak_bytes_in_use')}",
              flush=True)
    ok = not s.failures
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
