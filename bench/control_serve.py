#!/usr/bin/env python3
"""Readings that the limits of a served model's ``correct`` are set from,
on the chip.

Usage (from the root of a checkout, on the cell's chip)::

    python3 bench/control_serve.py --workload rwkv6-1.6b.chat \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 3 --seconds 10

In one process, for each seed, it sets the cell up at its own size, runs
a window at the cell's own load (as long as a run's, so that the check
samples from as many finished requests as a run's does), frees the
program and reads the check's numbers as a run does (the lower
readings). For
the first ``--control-seeds`` seeds it also reads the control: the
reference computed in the precision below the configuration's (its
``control``: float32 with 'high', three-pass, matrix products below
'highest') in the program's place, at the same positions of the same
prompts and served tokens. The benchmark's own runs never run the
control. One JSON line per seed goes to standard output.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _prefixed(readings: dict, prefix: str) -> dict:
    return {f"{prefix}_{k}": v for k, v in readings.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
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
        print("bench/control_serve.py: needs the cell's TPU chips",
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
        row = {"workload": cell.name, "seed": seed, "window_s": window_s,
               "checked": [[len(r.prompt), len(r.out)] for r, _ in d.checked],
               **d.completed()}
        row.update(_prefixed(d.compare(), "program"))
        if i < args.control_seeds:
            row.update(_prefixed(d.compare(**cell.config["control"]),
                                 "control"))
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
