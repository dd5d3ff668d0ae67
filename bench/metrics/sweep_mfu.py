"""sweep_mfu: the window's share of the chips' roofline, in %: the least
time of every engine call's algorithmic work, summed over the calls and
the cell's chips, over the traced window's time times the chips. For
these stencils the byte term binds (``bench/work.py``)."""
from bench import work


def read(run):
    if run.trace is None:
        return None
    least = work.least_time_s(run.work["flops"], run.work["bytes"],
                              run.peaks)
    return 100.0 * run.calls * least / run.trace.window_s()
