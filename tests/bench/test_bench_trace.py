"""The reduction from a profiler trace to the per-layer metrics."""
import dataclasses
import types
from pathlib import Path

import pytest

from bench import harness, trace as tr

ROOT = Path(__file__).resolve().parents[2]
METRICS = ("window_kernel_roofline", "engine_wrapper_share",
           "halo_exposed_share", "device_idle_share", "sweep_mfu")


def test_interval_algebra():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.total([(0, 3), (5, 8)]) == 6


def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


KERNEL = ('%custom-call.2 = f32[8]{0:T(128)} custom-call(f32[8]{0} %pad.1),'
          ' custom_call_target="tpu_custom_call"')
PAD = "%pad.1 = f32[8]{0:T(128)} pad(f32[6]{0} %x, f32[] %c), padding=1_1"
CP_DONE = ("%collective-permute-done.1 = f32[8]{0} collective-permute-done("
           "(f32[8]{0}, f32[8]{0}) %collective-permute-start.1)")
COPY = "%copy.3 = f32[8]{0} copy(f32[8]{0} %collective-permute-done.1)"


def _profile():
    """Two chips. Times in ns; the window is [1000, 2000)."""
    host = _plane("/host:CPU", [("python", [
        _event("bench.window", 1000, 1000),
        _event("bench.dispatch", 1000, 50),
        _event("bench.throttle", 1050, 900),
        _event("bench.drain", 1950, 50),
        _event("unrelated", 1000, 10)])])
    dev0 = _plane("/device:TPU:0", [
        ("XLA Ops", [
            _event(KERNEL, 500, 100),        # before the window
            _event(PAD, 1000, 100),
            _event(KERNEL, 1100, 600),
            _event(CP_DONE, 1700, 100),
            _event(COPY, 1750, 100)]),
        ("XLA Modules", [_event("jit_program", 1000, 900)])])
    dev1 = _plane("/device:TPU:1", [("XLA Ops", [_event(KERNEL, 1000, 800)])])
    return types.SimpleNamespace(planes=[host, dev1, dev0])


def test_reduce_profile_classifies_and_clips():
    r = tr.reduce_profile(_profile(), kernel="tpu_custom_call")
    assert r.window == (1000, 2000)
    assert [d.name for d in r.devices] == ["/device:TPU:0", "/device:TPU:1"]
    d0 = r.devices[0]
    assert [(n, c) for _, _, n, c in d0.ops] == [
        (PAD, "other"), (KERNEL, "kernel"), (CP_DONE, "collective"),
        (COPY, "other")]
    assert r.window_s() == pytest.approx(1e-6)
    # chip 0 busy [1000, 1850), chip 1 busy [1000, 1800)
    assert r.busy_s() == pytest.approx((850 + 800) / 2 * 1e-9)
    assert r.op_seconds("kernel") == pytest.approx(1400e-9)
    assert r.op_seconds("other") == pytest.approx(200e-9)
    bd = r.breakdown()
    assert bd["device_ops"][0] == [
        "%custom-call.2 = f32[8]{0:T(128)} custom-call", pytest.approx(700e-9)]
    assert bd["idle_gaps"][0] == ["bench.throttle (/device:TPU:1)",
                                  pytest.approx(200e-9)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def _run(reduced, calls=1, chips=2):
    cell = harness.resolve("jacobi2d-48k-x4.t4")
    cell = dataclasses.replace(cell, chips=chips)
    return harness.Run(cell=cell, peaks={"flops_per_s": 1e12,
                                         "hbm_bytes_per_s": 1e12},
                       setup_s=1.0, window_s=1e-6,
                       work={"flops": 10, "bytes": 100}, trace=reduced,
                       counts={"calls": calls, "cell_updates": 0})


def test_metric_readers_on_a_reduced_trace():
    r = tr.reduce_profile(_profile(), kernel="tpu_custom_call")
    read = {m: harness.metric_reader(harness.resolve("jacobi2d-48k-x4.t4"),
                                     m).read for m in METRICS}
    run = _run(r, calls=3)
    least = 100e-12                       # 100 bytes at 1e12 bytes/s
    assert read["window_kernel_roofline"](run) == pytest.approx(
        100 * 3 * 2 * least / 1400e-9)
    assert read["sweep_mfu"](run) == pytest.approx(100 * 3 * least / 1e-6)
    assert read["engine_wrapper_share"](run) == pytest.approx(
        100 * 200 / (850 + 800))
    assert read["device_idle_share"](run) == pytest.approx(
        100 * (1 - (850 + 800) / 2 / 1000))
    # chip 0: the collective [1700, 1800) runs alone until the copy
    # starts at 1750; chip 1 has none; mean over the 2 chips
    assert read["halo_exposed_share"](run) == pytest.approx(
        100 * 50 / 1000 / 2)


def test_exposed_collective_time():
    prof = _profile()
    ops = prof.planes[2].lines[0].events
    ops[-1] = _event(COPY, 1850, 50)
    r = tr.reduce_profile(prof, kernel="tpu_custom_call")
    read = harness.metric_reader(harness.resolve("jacobi2d-48k-x4.t4"),
                                 "halo_exposed_share").read
    # chip 0: all of [1700, 1800) exposed once the copy moves after it
    assert read(_run(r)) == pytest.approx(100 * 100 / 1000 / 2)


def test_readers_return_nothing_without_a_trace():
    cell = harness.resolve("jacobi2d-16k.t1")
    run = _run(None)
    for m in METRICS:
        assert harness.metric_reader(cell, m).read(run) is None


def test_a_trace_without_collectives_has_no_halo_share():
    prof = _profile()
    prof.planes[2].lines[0].events.pop(3)
    r = tr.reduce_profile(prof, kernel="tpu_custom_call")
    read = harness.metric_reader(harness.resolve("jacobi2d-48k-x4.t4"),
                                 "halo_exposed_share").read
    assert read(_run(r)) is None


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip: 61 dispatches of a 2048x2048
    ``2d5pt`` sweep, four fused steps a call, through the harness's window
    (bench.window, bench.dispatch, bench.throttle, bench.drain)."""
    r = tr.reduce_file(ROOT / "tests" / "bench" / "data" / "t4_2048.xplane.pb",
                       kernel="tpu_custom_call")
    assert [d.name for d in r.devices] == ["/device:TPU:0"]
    ops = r.devices[0].ops
    assert sum(c == "kernel" for *_, c in ops) == 61
    assert sum(c == "other" for *_, c in ops) == 61
    assert r.window == (36669529, 247852856)
    assert r.op_seconds("kernel") == pytest.approx(0.208731604, rel=1e-9)
    assert r.op_seconds("other") == pytest.approx(0.001592484, rel=1e-9)
    assert r.busy_s() == pytest.approx(0.210324088, rel=1e-9)
    assert [h[2] for h in r.host].count("bench.dispatch") == 61
    bd = r.breakdown()
    assert [n for n, _ in bd["device_ops"]] == [
        "%_run_window_plan_tpu.1 = f32[2048,2048]{1,0:T(8,128)} custom-call",
        "%pad.2 = f32[2056,2176]{1,0:T(8,128)S(1)} pad"]
    assert bd["idle_gaps"][0] == ["bench.drain (/device:TPU:0)",
                                  pytest.approx(0.000497207, rel=1e-6)]
    read = harness.metric_reader(harness.resolve("jacobi2d-16k.t4"),
                                 "device_idle_share").read
    assert read(_run(r, chips=1)) == pytest.approx(
        100 * (1 - 0.210324088 / 0.211183327), rel=1e-6)
