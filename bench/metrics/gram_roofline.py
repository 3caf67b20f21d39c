"""The Gram kernel's share of its roofline: the least time the required work
could take (bench/work.py: H and T read once, G and R written once, the
symmetric half of H^T H) over the device time of the kernel's events,
averaged over the devices, each of which does its share of the work.  The
kernel's ops carry the name of the jitted call that launches it
(``%gram_batched.<n>``)."""

from bench import trace, work

KERNEL = "gram"


def read(ctx):
    if ctx.trace is None or not ctx.fits:
        return None
    spent = trace.device_time_s(ctx.trace, lambda name: KERNEL in name)
    if spent <= 0:
        return None
    least, _ = work.roofline_s(ctx.work["gram_flops"], ctx.work["gram_bytes"],
                               ctx.peak)
    return 100.0 * least * ctx.fits / ctx.chips / spent
