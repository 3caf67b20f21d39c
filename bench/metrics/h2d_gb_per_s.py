"""Host-to-device rate of the streamed chunks: the bytes put, over the time
of the benchmark's ``h2d`` spans (each waits until its chunk is on the
device)."""


def read(ctx):
    spent = sum(e - s for n, s, e in ctx.spans if n == "h2d")
    if spent <= 0 or not ctx.work["h2d_bytes"]:
        return None
    return ctx.work["h2d_bytes"] * ctx.fits / spent / 1e9
