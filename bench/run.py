#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

Usage (from the root of a checkout)::

    python3 bench/run.py --workload jacobi2d-16k.t1 --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration, its traffic mix and its metrics are named in
``BENCHMARK.json``; see ``bench/harness.py``. The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``compared``: each number the check compared, beside its limit). The
same numbers are the last lines of standard error. A run that cannot be
made (no TPU, too few chips, a device kind missing from
``bench/peaks.json``, no program beside the benchmark) exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench.harness import BenchError, resolve, run_cell

    try:
        cell = resolve(args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError(f"no program under {ROOT / 'src'}: the "
                             "benchmark runs the repro package beside it")
        sys.path.insert(0, str(ROOT / "src"))
        os.environ.pop("REPRO_TUNING_CACHE", None)  # untuned defaults
        from repro.config import set_on_failure
        from repro.launch import compile_cache

        compile_cache.enable()
        set_on_failure("raise")
        run_cell(cell, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), t_start=T_START)
    except BenchError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
