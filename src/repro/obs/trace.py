"""Span tracing for the consensus runtime, on the profiler's clock.

One ``Tracer`` records named spans at the layer boundaries of a fit
(``stats`` / ``feed_wait`` / ``runner_build`` / ``sync`` / ``segment`` /
``snapshot`` / ``restore``) as flat dicts: start and duration in µs
since the tracer was made (``time.perf_counter``), ``depth``, the span's
own ``id`` (its opening order) and its ``parent``'s id (``None`` at the
top).  While a tracer is installed each span also enters a
``jax.profiler.TraceAnnotation`` named ``obs:<name>``, so inside a
``jax.profiler`` trace every program span sits in the host plane on the
same clock as the device ops, and an idle gap on the device can be put
down to the span the host was in.

``use(tracer)`` also registers ``jax.monitoring`` listeners for the
compile events (jaxpr trace, lowering to MLIR, backend compile, and a
persistent-cache read); each event adds a count and its seconds to the
innermost open span, keyed by kind and the traced function's
``fun_name`` (``span["compiles"]``).  The listeners go when ``use``
exits.

``export`` writes two artifacts:

  trace.json   Chrome trace event format (``ph: "X"`` complete events,
               microsecond timestamps, ``parent`` and ``compiles`` in
               ``args``) — loadable in Perfetto or chrome://tracing.
  spans.jsonl  one span per line for grepping / pandas.

Activation is a dynamically-scoped global: ``with use(tracer): ...``
installs the tracer, and instrumented call sites do

    with span("segment", iters=n):
        ...

``span(...)`` returns a shared ``contextlib.nullcontext()`` when no
tracer is installed, so the OFF cost at every instrumentation point is a
single function call and a global read — nothing is allocated, no clock
is consulted and no listener is registered.  This is the host-side half
of the zero-overhead guarantee (the device-side half is the
``cfg.telemetry`` gate in ``repro.core.engine``).

Instrumented sites that dispatch device work (the stats pass, every
segment) additionally ``jax.block_until_ready`` their outputs *inside*
the span when a tracer is installed, so span durations cover the device
work rather than its enqueue — at zero cost when tracing is off.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from pathlib import Path

import jax

_CLOCK = time.perf_counter


def timed(fn, *args, repeats: int = 1, **kwargs):
    """Run fn once for compile, then time `repeats` executions."""
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = _CLOCK()
    for _ in range(repeats):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    dt = (_CLOCK() - t0) / repeats
    return out, dt


PREFIX = "obs:"
# jax.monitoring's compile events, by the kind a span's counter keys them
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Tracer:
    """Records spans relative to its construction time (µs)."""

    def __init__(self) -> None:
        self.spans: list[dict] = []
        # compile events that arrived with no span open
        self.compiles: dict[str, dict[str, list]] = {}
        self._t0 = _CLOCK()
        self._open: list[dict] = []
        self._next_id = 0

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Yields the span's ``args``: what the block adds to it lands in
        the record (not in the annotation, whose arguments are fixed when
        it opens)."""
        rec = {
            "name": name,
            "id": self._next_id,
            "parent": self._open[-1]["id"] if self._open else None,
            "depth": len(self._open),
            "args": args,
        }
        self._next_id += 1
        self._open.append(rec)
        t0 = _CLOCK()
        try:
            with jax.profiler.TraceAnnotation(PREFIX + name, **args):
                yield args
        finally:
            t1 = _CLOCK()
            self._open.pop()
            rec["ts"] = (t0 - self._t0) * 1e6
            rec["dur"] = (t1 - t0) * 1e6
            self.spans.append(rec)

    def _count_compile(self, kind: str, fun_name: str, seconds: float):
        """Add one compile event to the innermost open span."""
        if self._open:
            counts = self._open[-1].setdefault("compiles", {})
        else:
            counts = self.compiles
        row = counts.setdefault(kind, {}).setdefault(fun_name, [0, 0.0])
        row[0] += 1
        row[1] += seconds

    def _on_time_span(self, event, start, end, **kwargs):
        kind = COMPILE_EVENTS.get(event)
        if kind is not None and _ACTIVE is self:
            self._count_compile(kind, str(kwargs.get("fun_name", "")),
                                end - start)

    def _on_duration(self, event, duration, **kwargs):
        if event == CACHE_EVENT and _ACTIVE is self:
            self._count_compile("cache_read",
                                str(kwargs.get("fun_name", "")), duration)

    def trace_events(self) -> list[dict]:
        """Chrome trace event format rows (complete ``ph: "X"`` events)."""
        pid = os.getpid()
        return [
            {
                "name": s["name"],
                "ph": "X",
                "ts": s["ts"],
                "dur": s["dur"],
                "pid": pid,
                "tid": 0,
                "args": {**s["args"], "parent": s.get("parent"),
                         **({"compiles": s["compiles"]}
                            if "compiles" in s else {})},
            }
            for s in sorted(self.spans, key=lambda s: (s["ts"], -s["dur"]))
        ]

    def export(self, trace_dir) -> dict:
        """Write trace.json + spans.jsonl under ``trace_dir``; returns
        ``{"trace": path, "spans": path}``."""
        trace_dir = Path(trace_dir)
        trace_dir.mkdir(parents=True, exist_ok=True)
        trace_path = trace_dir / "trace.json"
        spans_path = trace_dir / "spans.jsonl"
        with trace_path.open("w") as f:
            json.dump(
                {"traceEvents": self.trace_events(),
                 "displayTimeUnit": "ms"},
                f,
            )
        with spans_path.open("w") as f:
            for s in self.spans:
                f.write(json.dumps(s) + "\n")
        return {"trace": trace_path, "spans": spans_path}


_ACTIVE: Tracer | None = None
_NULL = contextlib.nullcontext()


def current() -> Tracer | None:
    """The installed tracer, or None when tracing is off."""
    return _ACTIVE


@contextlib.contextmanager
def use(tracer: Tracer | None):
    """Install ``tracer`` for the dynamic extent of the with-block, with
    its compile-event listeners registered for the same extent."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    if tracer is not None:
        jax.monitoring.register_event_time_span_listener(
            tracer._on_time_span)
        jax.monitoring.register_event_duration_secs_listener(
            tracer._on_duration)
    try:
        yield tracer
    finally:
        _ACTIVE = prev
        if tracer is not None:
            jax.monitoring.unregister_event_time_span_listener(
                tracer._on_time_span)
            jax.monitoring.unregister_event_duration_listener(
                tracer._on_duration)


def span(name: str, **args):
    """A span on the installed tracer — or a shared no-op context when
    tracing is off (the zero-overhead path: no allocation, no clock)."""
    t = _ACTIVE
    if t is None:
        return _NULL
    return t.span(name, **args)


def annotate(**args) -> None:
    """Add ``args`` to the record of the innermost open span of the
    installed tracer (nothing when tracing is off or no span is open)."""
    t = _ACTIVE
    if t is not None and t._open:
        t._open[-1]["args"].update(args)


def validate_trace(path) -> int:
    """Check a trace.json loads and its spans nest properly.

    Spans on one (pid, tid) track must form a forest: any two either
    are disjoint in time or one contains the other.  Returns the event
    count; raises ``ValueError`` on malformed traces.
    """
    with Path(path).open() as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("traceEvents missing or not a list")
    tracks: dict[tuple, list[dict]] = {}
    for e in events:
        if e.get("ph") != "X":
            raise ValueError(f"unexpected event phase: {e.get('ph')!r}")
        if not isinstance(e.get("name"), str):
            raise ValueError("event missing name")
        if e.get("dur", -1.0) < 0 or e.get("ts", -1.0) < 0:
            raise ValueError(f"negative ts/dur in {e.get('name')!r}")
        tracks.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    eps = 1e-3  # µs slack for clock rounding at span boundaries
    for track in tracks.values():
        stack: list[float] = []  # open end-times
        for e in sorted(track, key=lambda e: (e["ts"], -e["dur"])):
            start, end = e["ts"], e["ts"] + e["dur"]
            while stack and stack[-1] <= start + eps:
                stack.pop()
            if stack and end > stack[-1] + eps:
                raise ValueError(
                    f"span {e['name']!r} overlaps its parent without nesting"
                )
            stack.append(end)
    return len(events)
