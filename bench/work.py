"""The algorithmic work of one engine call, from its shapes alone.

These counts are what a call must do whatever implements it: the
stencil's FLOPs per point (the paper's Table 3 FPP) for every cell and
fused step, and one read plus one write of the field. What an
implementation adds on top (halo re-reads, tile round-up, padding
copies, recomputed trapezoid rows) is not work here: it shows as time.
"""
from __future__ import annotations

import math


def stencil_call_flops(shape, fpp: int, steps: int) -> int:
    """FLOPs of ``steps`` fused stencil steps over a field of ``shape``."""
    return fpp * math.prod(shape) * steps


def stencil_call_bytes(shape, itemsize: int) -> int:
    """Bytes of one read and one write of a field of ``shape``."""
    return 2 * itemsize * math.prod(shape)


def least_time_s(flops: int, nbytes: int, peaks: dict) -> float:
    """The least time the chip could take for this work: the larger of
    FLOPs over peak FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
