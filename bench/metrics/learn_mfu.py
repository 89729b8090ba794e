"""The whole update's share of the chip's peak, in roofline form.

Every local step launches the CLG reduction kernel once, so those launches
count the E-step passes the window ran, each over the launch's instances.
The least time of all those passes (``bench.work.estep_pass``: read each
instance once, the E-step arithmetic and the reductions) over the traced
window times the chips.  It bounds what replacing a kernel can gain.
"""

from bench import trace, work


def read(ctx):
    least = 0.0
    for operands, _ in trace.kernel_launches(ctx.trace):
        if work.gram_kind(operands) == "clg":
            n = operands[0][1][-1]
            least += work.least_seconds(*work.estep_pass(ctx.cell.cfg, n),
                                        ctx.peaks)
    chips = max(len(ctx.trace.ops), 1)
    return 100.0 * least / (ctx.window_s * chips) if least else None
