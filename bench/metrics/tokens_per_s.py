"""tokens_per_s: tokens completed in the window (prompt tokens prefilled
and tokens generated) over the window's whole time (host clock, first
dispatch to the last result read back)."""


def read(run):
    return run.counts["tokens"] / run.window_s
