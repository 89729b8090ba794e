"""Roofline share of the suff-stat reduction kernels.

For every launch of the Pallas Gram kernel in the traced window, the least
time its work needs on this chip (``bench.work``: the CLG or discrete-count
reduction over the launch's instances, told apart by its operands), summed,
over the summed device time of those launches.
"""

from bench import trace, work


def read(ctx):
    least = dev = 0.0
    for operands, secs in trace.kernel_launches(ctx.trace):
        n = operands[0][1][-1]
        count = (work.gram_disc if work.gram_kind(operands) == "disc"
                 else work.gram_clg)
        least += work.least_seconds(*count(ctx.cell.cfg, n), ctx.peaks)
        dev += secs
    return 100.0 * least / dev if dev else None
