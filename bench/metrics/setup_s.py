"""setup_s: seconds from process start to the first timed dispatch:
interpreter and JAX start, chip init, field generation, the sweep's
compile (or its load from the compile cache) and warm-up."""


def read(run):
    return run.setup_s
