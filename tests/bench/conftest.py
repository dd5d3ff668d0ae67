"""Fixtures of the benchmark's own tests: its cells at sizes the CPU
holds, driven through the Pallas interpreter."""
import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


@pytest.fixture
def small_cell():
    """``small_cell(workload, n)``: the cell with its domain cut to n x n."""
    from bench import harness

    def make(workload, n, root=ROOT):
        cell = harness.resolve(workload, root)
        return dataclasses.replace(
            cell, config=dict(cell.config, domain=[n, n]))
    return make


@pytest.fixture
def run_small():
    """Run a small cell on the CPU through the harness, no chip asked for;
    return its result line."""
    from bench import harness

    def run(cell, seed=2**31 + 7, seconds=0.2):
        return harness.run_cell(cell, seed=seed, seconds=seconds,
                                trace=False, t_start=time.perf_counter(),
                                impl="interpret", require_tpu=False)
    return run
