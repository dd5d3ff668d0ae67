"""serve_idle_share: 1 - (union of the intervals in which an op runs on
the device) / the traced window, averaged over the chips, in %, in a
serving cell: the decode loop reads the sampled tokens back to the host
every step, so the device waits on the host between steps."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s())
