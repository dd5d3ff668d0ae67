"""halo_copy_share: device time of the ops under a ``halo.*`` scope that
are neither a collective nor under an ``engine.*`` scope (the halo-
extended block's concatenates, the frame strips' slices and copies, the
splice's updates) over device busy time, summed over the chips, in %.
Ops are put under layers through the compiled program (``bench/
layers.py``); a program that names no layers has nothing to read."""
from bench import layers


def _copy(path, cls):
    return (cls != "collective"
            and layers.layer_of(path, "halo.") is not None
            and layers.layer_of(path, "engine.") is None)


def read(run):
    return layers.share(run, _copy)
