"""The algorithmic FLOPs and bytes of each cell's engine call, from its
shapes, to the exact integer."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

# Per engine call and chip: (cells of the chip's shard, fused steps).
EXPECTED = {
    "jacobi2d-16k.t1": {"flops": 9 * 16384 ** 2,
                        "bytes": 2 * 4 * 16384 ** 2,
                        "cell_updates": 16384 ** 2},
    "jacobi2d-16k.t4": {"flops": 9 * 4 * 16384 ** 2,
                        "bytes": 2 * 4 * 16384 ** 2,
                        "cell_updates": 4 * 16384 ** 2},
    "jacobi2d-48k-x4.t4": {"flops": 9 * 4 * 24576 ** 2,
                           "bytes": 2 * 4 * 24576 ** 2,
                           "cell_updates": 4 * 49152 ** 2},
}


def test_every_cell_has_expected_counts():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert sorted(w["name"] for w in spec["workloads"]) == sorted(EXPECTED)


@pytest.mark.parametrize("workload", sorted(EXPECTED))
def test_call_work_is_exact(workload):
    from bench import harness

    cell = harness.resolve(workload)
    d = harness.driver_module(cell).make(cell.config, cell.traffic, seed=0,
                                         impl="pallas", devices=[None])
    want = EXPECTED[workload]
    work = d.work_per_call()
    assert work == {"flops": want["flops"], "bytes": want["bytes"]}
    assert all(isinstance(v, int) for v in work.values())
    d.dispatches = 3
    assert d.completed() == {"calls": 3,
                             "cell_updates": 3 * want["cell_updates"]}


def test_byte_term_binds_on_v5e():
    from bench import harness, work

    peaks = harness.peaks_for("TPU v5 lite")
    flops, nbytes = 9 * 4 * 16384 ** 2, 2 * 4 * 16384 ** 2
    assert work.least_time_s(flops, nbytes, peaks) == nbytes / 819e9
    assert flops / 197e12 < nbytes / 819e9


def test_unknown_device_kind_is_an_error():
    from bench import harness

    with pytest.raises(harness.BenchError, match="peaks.json"):
        harness.peaks_for("TPU v99")
