"""Device time per ADMM iteration: the runner's programs (the dense
runner's ``scan``; the sharded runner's, which alone exchanges U between
chips by collective-permute), averaged over the devices, over fits times
iterations."""

from bench import trace


def _is_runner(module: str, ops: set) -> bool:
    return "scan" in module or any("collective-permute" in op for op in ops)


def read(ctx):
    if ctx.trace is None or not ctx.fits:
        return None
    spent = trace.program_time_s(ctx.trace, _is_runner)
    return 1e3 * spent / (ctx.fits * ctx.iters) if spent > 0 else None
