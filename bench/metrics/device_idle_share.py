"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices (profiler trace)."""

from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    lo, hi = ctx.trace["window"]
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / (hi - lo))
