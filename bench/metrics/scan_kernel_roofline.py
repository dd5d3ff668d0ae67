"""scan_kernel_roofline: the least time of the WKV scans' algorithmic work
over the summed device time of the scan kernel's events (the
configuration's ``kernel``, ``repro_scan``), in %.

The work is that of every prompt token prefilled in the window, in every
layer: one read of r, k, the decay and v and one write of the output
(bytes), and about 4 K V FLOPs a head (``bench/work.py``). The least
time is the larger of bytes over peak HBM bytes/s and FLOPs over peak
FLOP/s; the bytes bind. Decode steps run no scan kernel. A window that
prefilled nothing, or a trace with no scan kernel, has nothing to read."""
from bench import work


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds("kernel")
    tokens = run.counts["prefill_tokens"]
    if kernel_s <= 0 or tokens <= 0:
        return None
    least = work.least_time_s(tokens * run.work["scan_flops"],
                              tokens * run.work["scan_bytes"], run.peaks)
    return 100.0 * least / kernel_s
