"""``correct`` holds for the program and fails for the control and for
each fault a sweep cell can have, with the rest of a run driven as on
the chip (the look for a chip skipped, small sizes, the interpreter)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def patched_stencil(monkeypatch):
    """``patched_stencil(make)`` puts ``make(real_stencil)`` in the
    program's place, underneath the driver."""
    from repro.kernels import ops

    def patch(make):
        monkeypatch.setattr(ops, "stencil", make(ops.stencil))
    return patch


def _full_domain_reference(cfg, dtype):
    """The plain reference over the whole field in ``dtype``: the control
    when that is below the configuration's precision."""
    from bench import reference

    offsets = tuple(tuple(o) for o in cfg["offsets"])
    coeffs = tuple(cfg["coeffs"])
    lead, trail = reference.extents(offsets)

    def stencil(x, name, *, time_steps, **_):
        t = time_steps
        pads = (((t * lead[0], t * trail[0]), (t * lead[1], t * trail[1])),)
        return reference.sweep_region(x, offsets=offsets, coeffs=coeffs,
                                      steps=t, pads=pads, dtype=dtype)
    return stencil


@pytest.mark.parametrize("workload", ["jacobi2d-16k.t1", "jacobi2d-16k.t4"])
def test_program_is_correct(workload, small_cell, run_small):
    line = run_small(small_cell(workload, 256))
    assert line["correct"] is True
    assert list(line)[-1] == "compared"
    gap = line["compared"]["rel_gap"]
    assert gap["value"] < gap["limit"] / 10


@pytest.mark.parametrize("workload", ["jacobi2d-16k.t1", "jacobi2d-16k.t4"])
def test_reference_in_float32_passes_and_control_fails(
        workload, small_cell, run_small, patched_stencil):
    cell = small_cell(workload, 256)
    patched_stencil(lambda real: _full_domain_reference(cell.config,
                                                        "float32"))
    assert run_small(cell)["correct"] is True
    patched_stencil(lambda real: _full_domain_reference(cell.config,
                                                        "bfloat16"))
    line = run_small(cell)
    assert line["correct"] is False
    gap = line["compared"]["rel_gap"]
    assert gap["value"] > 3 * gap["limit"]


def _unchanged(real):
    return lambda x, *a, **k: x


def _half_domain(real):
    def stencil(x, *a, **k):
        y = real(x, *a, **k)
        return y.at[x.shape[0] // 2:].set(x[x.shape[0] // 2:])
    return stencil


def _altered_answer(real):
    def stencil(x, *a, **k):
        y = real(x, *a, **k)
        return y.at[x.shape[0] // 3, x.shape[1] // 5].add(1.0)
    return stencil


@pytest.mark.parametrize("fault", [_unchanged, _half_domain,
                                   _altered_answer],
                         ids=["state_unchanged", "half_domain_left_out",
                              "answer_altered"])
@pytest.mark.parametrize("workload", ["jacobi2d-16k.t1", "jacobi2d-16k.t4"])
def test_fault_fails(workload, fault, small_cell, run_small,
                     patched_stencil):
    patched_stencil(fault)
    line = run_small(small_cell(workload, 256))
    assert line["correct"] is False


SHARDED = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path.insert(0, {root!r}); sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from bench import harness
    cell = harness.resolve("jacobi2d-48k-x4.t4")
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 domain=[512, 512]))
    def run():
        return harness.run_cell(cell, seed=2**31 + 11, seconds=0.2,
                                trace=False, t_start=time.perf_counter(),
                                impl="interpret", require_tpu=False)
    sound = run()
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    broken = run()
    print(json.dumps([sound["correct"], broken["correct"],
                      broken["compared"]["rel_gap"]["value"]]))
""")


def test_sharded_program_is_correct_and_exchange_left_out_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c", SHARDED.format(root=str(ROOT),
                                              src=str(ROOT / "src"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    sound, broken, gap = json.loads(r.stdout.strip().splitlines()[-1])
    assert sound is True
    assert broken is False and gap > 1e-3


@pytest.mark.parametrize("ahead_s, least", [(0.0, 2), (1e6, 4096)])
def test_queue_is_sized_from_ahead_s_and_holds_only_probes(
        ahead_s, least, small_cell):
    import jax

    from bench import harness

    cell = small_cell("jacobi2d-16k.t4", 128)
    driver = harness.driver_module(cell).make(
        cell.config, dict(cell.traffic, ahead_s=ahead_s), seed=3,
        impl="interpret", devices=jax.devices()[:1])
    driver.setup()
    assert driver.in_flight == least
    for _ in range(6):
        driver.dispatch()
        driver.throttle()
        assert len(driver.pending) <= driver.in_flight
    assert all(p.size == 1 for p in driver.pending)
    driver.drain()
    assert driver.completed()["calls"] == 6
