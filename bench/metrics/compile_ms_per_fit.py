"""Host time per fit spent tracing, lowering and compiling, or reading a
compiled program back from the persistent cache: the union of
jax.monitoring's compile events inside the window, over the fits."""

from bench import trace


def read(ctx):
    if not ctx.fits:
        return None
    spent = trace.total(trace.union((s, e) for _, s, e in ctx.compiles))
    return 1e3 * spent / ctx.fits
