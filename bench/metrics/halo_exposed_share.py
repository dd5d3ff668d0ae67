"""halo_exposed_share: the time in which a collective runs on a chip and
no other op does, over the traced window, averaged over the chips, in %.
A trace with no collective has nothing to read."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    shares = []
    for d in run.trace.devices:
        coll = d.intervals("collective")
        if not coll:
            continue
        rest = tr.merge(iv for c in ("kernel", "other")
                        for iv in d.intervals(c))
        shares.append(tr.total(tr.subtract(coll, rest)) / (hi - lo))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(run.trace.devices)
