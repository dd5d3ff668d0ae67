"""serve_mfu: the window's share of the chip's peak FLOP/s, in %: the
model FLOPs of every token completed in the window (``bench/work.py``:
two a weight of each matrix product, the WKV term, and the output head
for a generated token) over the traced window's time times the peak."""


def read(run):
    if run.trace is None:
        return None
    flops = (run.counts["prefill_tokens"] * run.work["flops_prefill"]
             + run.counts["decode_tokens"] * run.work["flops_decode"])
    return 100.0 * flops / (run.peaks["flops_per_s"] * run.trace.window_s())
