"""Operations and bytes the router's kernels need per call.

Counted from what the algorithm needs, not from the padded blocks a kernel
happens to move: rows of K real arms (the kernels pad the arm axis to 128
lanes; that padding is not work). Operations are the elementwise ones of a
stable rank and a masked reduction; against the chip's peak they are
negligible, so the bytes bound these kernels.

  topn_lp  per row: read score and cost (K each) and n, write one cost
           sum; rank K columns against K entries (5 ops each), mask and
           reduce (3 per arm).
  awc_fw   per row: read z, mu and c (K each), G lambdas and n; write the
           gradient (K) and G probe costs; the gradient (6 ops an arm),
           then per probe the scores (3 an arm), the ranks (5 K^2) and the
           masked reduction (3 an arm).
"""
from __future__ import annotations

F32 = 4


def topn_lp(rows: int, k: int):
    """(ops, bytes) of one call over ``rows`` rows of ``k`` arms."""
    ops = rows * (5 * k * k + 3 * k)
    nbytes = F32 * rows * (2 * k + 2)
    return float(ops), float(nbytes)


def awc_fw(rows: int, k: int, g: int):
    """(ops, bytes) of one call over ``rows`` rows, ``k`` arms and ``g``
    lambda probes a row."""
    ops = rows * (6 * k + g * (5 * k * k + 6 * k))
    nbytes = F32 * rows * (4 * k + 2 * g + 1)
    return float(ops), float(nbytes)


def least_seconds(ops: float, nbytes: float, peaks) -> float:
    """The roofline's least time: the larger of the compute and the memory
    bound."""
    return max(ops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
