"""Host time per fit of the hidden layer and the statistics (the benchmark's
``stats`` span, which waits for the device when traced), less the time the
stream spent putting chunks on the device (``h2d`` spans)."""


def read(ctx):
    if not ctx.fits:
        return None
    spent = sum(e - s for n, s, e in ctx.spans if n == "stats")
    spent -= sum(e - s for n, s, e in ctx.spans if n == "h2d")
    return 1e3 * spent / ctx.fits
