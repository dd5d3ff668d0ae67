"""engine_wrapper_share: device time of the ops that are neither the
engine kernel nor a collective (the engine's pad and slice, copies, the
halo splice) over device busy time, summed over the chips, in %."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_s() * len(run.trace.devices)
    if busy <= 0:
        return None
    return 100.0 * run.trace.op_seconds("other") / busy
