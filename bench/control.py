#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

Usage (from the root of a checkout, on the cell's chips)::

    python3 bench/control.py --workload jacobi2d-16k.t1 \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 3 --seconds 2

In one process, for each seed, it sets the cell up at its own size, runs
a short window at the cell's own load and reads the gap of the window's
last dispatch to the reference, as a run does (the lower reading). For
the first ``--control-seeds`` seeds it also reads the control: the
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place on the same input. The benchmark's
own runs never run the control. One JSON line per seed goes to standard
output.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

CONTROL_DTYPE = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness
    from repro.config import set_on_failure
    from repro.launch import compile_cache

    compile_cache.enable()
    set_on_failure("raise")
    import jax

    cell = harness.resolve(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("bench/control.py: needs the cell's TPU chips",
              file=sys.stderr)
        return 2
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        d = harness.driver_module(cell).make(
            cell.config, cell.traffic, seed=seed, impl="pallas",
            devices=devices[:cell.chips])
        d.setup()
        window_s = harness.measure(d, args.seconds,
                                   lambda name: contextlib.nullcontext())
        d.release()
        row = {"workload": cell.name, "seed": seed,
               "calls": d.completed()["calls"], "window_s": window_s}
        g = d.gap()
        row.update(program_rel_gap=g.rel_gap, program_nonfinite=g.nonfinite)
        if i < args.control_seeds:
            c = d.gap(got_dtype=CONTROL_DTYPE)
            row.update(control_rel_gap=c.rel_gap,
                       control_nonfinite=c.nonfinite)
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
