"""The fit job that the window drives, through the program's own entry
points, the way ``dmtl_elm.fit`` calls them:

1. the frozen hidden layer (``core.elm.ELMFeatureMap``) on the raw features,
   under the JAX default matmul precision that the configuration names
   (``hidden_layer_precision``): the feature map states no precision of its
   own and follows that setting;
2. the statistics (``engine.produce_stats``, or
   ``data.pipeline.stream_sufficient_stats`` over chunks fed from the host);
3. ``engine.make_runner``;
4. ``Runner.run`` from a start state (U, A) drawn from the seed, where fit()
   starts from all-ones (from there rounding decides when the r columns of
   U separate, so no reference comparison holds past a few iterations);
5. U, A and the diagnostics brought to the host.

Spans of the benchmark's own (``stats``, ``h2d``, ``runner_setup``,
``admm``, ``d2h``) surround these calls; with ``block=True`` (the traced
run) each waits for the device before it closes.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time
from typing import NamedTuple

import jax
import numpy as np

from bench import data
from bench.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.core import engine  # noqa: E402
from repro.core.dmtl_elm import DMTLELMConfig  # noqa: E402
from repro.core.elm import ELMFeatureMap  # noqa: E402
from repro.core.graph import ring, star  # noqa: E402
from repro.data.pipeline import stream_sufficient_stats  # noqa: E402

AXES = ("agents",)
ADMM_KEYS = ("mu1", "mu2", "tau", "zeta", "gamma_floor")
GRAPHS = {"ring": ring, "star": star}


def graph(config: dict):
    """The configuration's agent graph (``graph``: a name in ``GRAPHS``)."""
    name = config["graph"]
    if name not in GRAPHS:
        raise ValueError(f"graph {name!r} is not one the benchmark runs; "
                         f"known: {sorted(GRAPHS)}")
    return GRAPHS[name](config["m"])


class Spans:
    """Host-clock spans, in seconds since ``origin``."""

    def __init__(self, block: bool):
        self.block = block
        self.items: list[list] = []
        self.origin = time.time()

    @contextlib.contextmanager
    def span(self, name: str):
        holder = []
        ann = (jax.profiler.TraceAnnotation(f"bench:{name}") if self.block
               else contextlib.nullcontext())
        t0 = time.time()
        with ann:
            yield holder
            if self.block and holder:
                jax.block_until_ready(holder)
        self.items.append([name, t0 - self.origin, time.time() - self.origin])


class FitOut(NamedTuple):
    index: int
    U: np.ndarray        # (m, L, r)
    A: np.ndarray        # (m, r, D)
    objective: np.ndarray
    stats: object        # the program's SufficientStats, on the device


def admm_config(config: dict, traffic: dict) -> DMTLELMConfig:
    return DMTLELMConfig(
        r=config["r"], iters=traffic["iters"], u_solver=traffic["solver"],
        stats_precision=traffic["stats_precision"],
        **{k: config[k] for k in ADMM_KEYS})


def _hidden(X, W, b, precision):
    with jax.default_matmul_precision(precision):
        return ELMFeatureMap(W=W, b=b)(X)


class FitJob:
    """Set-up (data and weights on the device from the seed) and the fit."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 spans: Spans):
        self.config, self.traffic, self.spans = config, traffic, spans
        self.keys = data.keys(seed)
        c = config
        self.m, self.L, self.r, self.D = c["m"], c["L"], c["r"], c["D"]
        self.cfg = admm_config(config, traffic)
        self.g = graph(c)
        self.mesh = self.sharding = None
        self.executor = c["executor"]
        if self.executor == "sharded":
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            self.mesh = Mesh(np.array(devices[: self.m]), AXES)
            self.sharding = NamedSharding(self.mesh, P(AXES))
            replicated = NamedSharding(self.mesh, P())
        else:
            replicated = devices[0]
        self.tasks = jax.jit(functools.partial(
            data.tasks, m=self.m, d_in=c["d_in"],
            n_global_classes=c["n_global_classes"], D=self.D))(
            self.keys.tasks)
        self.W, self.b = jax.device_put(jax.jit(functools.partial(
            data.hidden_weights, d_in=c["d_in"], L=self.L))(self.keys.weights),
            replicated)
        self.hidden = jax.jit(functools.partial(
            _hidden, precision=c["hidden_layer_precision"]),
            out_shardings=self.sharding)
        self.start = jax.jit(functools.partial(
            data.start_state, m=self.m, L=self.L, r=self.r, D=self.D))
        if traffic["feed"] == "resident":
            gen = functools.partial(data.samples, n=c["N"])
            out = None if self.sharding is None else (self.sharding,) * 2
            X, self.T = jax.jit(gen, out_shardings=out)(
                self.keys.samples, self.tasks)
            self.X = data.widen(X)      # resident in float32
            del X
            jax.block_until_ready((self.X, self.T))
        else:
            rows = traffic["chunk_rows"]
            if c["N"] % rows:
                raise ValueError(f"N={c['N']} is not a whole number of "
                                 f"{rows}-row chunks")
            self.n_chunks = c["N"] // rows
            gen = jax.jit(functools.partial(data.samples, n=rows))
            self.host_chunks = [
                jax.device_get(gen(jax.random.fold_in(self.keys.samples, i),
                                   self.tasks))
                for i in range(c["distinct_chunks"])]

    def chunk_of(self, i: int) -> int:
        """Which distinct host chunk the stream's ``i``-th chunk is."""
        return i % len(self.host_chunks)

    def _stream(self):
        for i in range(self.n_chunks):
            with self.spans.span("h2d"):
                X, T = jax.device_put(self.host_chunks[self.chunk_of(i)])
                # a synchronous reader: each chunk is on the device before
                # the next is read
                jax.block_until_ready((X, T))
            yield self.hidden(X, self.W, self.b), T

    def stats(self):
        prec = self.traffic["stats_precision"]
        with self.spans.span("stats") as out:
            if self.traffic["feed"] == "stream":
                st = stream_sufficient_stats(self._stream(), precision=prec)
            else:
                H = self.hidden(self.X, self.W, self.b)
                st = engine.produce_stats(
                    H, self.T, precision=prec, mesh=self.mesh,
                    agent_axes=None if self.mesh is None else AXES)
            out.append(st)
        return st

    def start_state(self, runner, index: int):
        U0, A0 = self.start(self.keys.start, index)
        sh = runner.state_shardings()
        if sh is not None:
            U0, A0 = jax.device_put(U0, sh.U), jax.device_put(A0, sh.A)
        return runner.init_state()._replace(U=U0, A=A0)

    def fit(self, index: int) -> FitOut:
        st = self.stats()
        with self.spans.span("runner_setup") as out:
            runner = engine.make_runner(
                st, self.g, self.cfg, executor=self.executor, mesh=self.mesh,
                agent_axes=None if self.mesh is None else AXES)
            state0 = self.start_state(runner, index)
            out.append(state0)
        with self.spans.span("admm") as out:
            state, diags = runner.run(state0)
            out.append((state, diags))
        with self.spans.span("d2h"):
            U, A, obj = jax.device_get((state.U, state.A, diags["objective"]))
        return FitOut(index, np.asarray(U), np.asarray(A), np.asarray(obj), st)
