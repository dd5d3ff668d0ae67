"""engine_pad_share: device time of the ops whose innermost ``engine.*``
scope is ``engine.pad`` or ``engine.crop`` (the engine's origin and
round-up pad of its input, the padding of epilogue operands, and the
crop of its output) over device busy time, summed over the chips, in %.
Ops are put under layers through the compiled program (``bench/
layers.py``); a program that names no layers has nothing to read."""
from bench import layers

WRAPPER = ("engine.pad", "engine.crop")


def read(run):
    return layers.share(
        run, lambda path, cls: layers.layer_of(path, "engine.") in WRAPPER)
