"""Device time per fit of the U-solve's set-up, the eigh of every agent's G:
the programs that run an eigh (named so, or holding the TPU's ``EighTpu``
op), averaged over the devices."""

from bench import trace


def _is_eigh(module: str, ops: set) -> bool:
    return "eigh" in module or any("EighTpu" in op for op in ops)


def read(ctx):
    if ctx.trace is None or not ctx.fits:
        return None
    spent = trace.program_time_s(ctx.trace, _is_eigh)
    return 1e3 * spent / ctx.fits if spent > 0 else None
