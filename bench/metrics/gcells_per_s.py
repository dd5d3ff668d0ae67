"""gcells_per_s: grid-cell updates completed in the window over the
window's whole time (host clock, first dispatch to every result ready),
in billions per second."""


def read(run):
    return run.cell_updates / run.window_s * 1e-9
