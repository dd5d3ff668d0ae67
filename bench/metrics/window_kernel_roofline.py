"""window_kernel_roofline: the least time of the engine calls' algorithmic
work over the summed device time of the engine kernel's events, in %.

The least time of a call is the larger of its FLOPs over peak FLOP/s and
its bytes (one read and one write of the field) over peak HBM bytes/s
(``bench/work.py``), per chip; it is summed over the window's calls and
the cell's chips."""
from bench import work


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds("kernel")
    if kernel_s <= 0:
        return None
    least = work.least_time_s(run.work["flops"], run.work["bytes"],
                              run.peaks)
    return 100.0 * run.calls * run.cell.chips * least / kernel_s
