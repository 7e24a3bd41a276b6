"""topn_lp_roofline: the Pallas `topn_lp` kernel's share of its roofline.
For each call of the kernel in the trace, the least time is the larger of
the operations and the bytes its rows need (`bench.work.topn_lp`) over the
chip's peaks; the share is the summed least time over the kernel's summed
device time. Moves fleet_rounds_per_s."""
from bench import kernels


def read(tr, ctx):
    return kernels.roofline(tr, ctx, "topn_lp")
