"""The whole fit's share of the chips' bf16 peak: the FLOPs a fit requires
(bench/work.py: hidden layer, Gram pass, and G U, U^T G U once per ADMM
iteration) times the fits, over the window, the chips and the peak."""


def read(ctx):
    if not ctx.fits:
        return None
    flops = ctx.work["fit_flops"] * ctx.fits
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
