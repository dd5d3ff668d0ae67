"""prefill_share: device time of the ops that run inside the prefill
program (the ``jit_prefill`` module, ``DecodeServer.prefill_fn``) over
device busy time, in %. Ops are put under programs by the trace's
``XLA Modules`` line; a trace without it, or with no prefill in the
window, has nothing to read."""
from bench import trace as tr

PROGRAM = "jit_prefill("


def read(run):
    if run.trace is None:
        return None
    busy = picked = 0
    for d in run.trace.devices:
        ops = d.intervals()
        busy += tr.total(ops)
        picked += tr.total(tr.intersect(ops, d.module_intervals(PROGRAM)))
    if busy <= 0 or picked <= 0:
        return None
    return 100.0 * picked / busy
