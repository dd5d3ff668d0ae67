"""The readers that put device ops under the program's layers."""
import dataclasses
import types

import jax
import pytest

from bench import harness, layers, trace as tr

WINDOW = [1000, 2000]


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start, stats=[])


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=evs) for n, evs in lines])


def _op(name, opcode, extra=""):
    return f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %x){extra}"


KERNEL = ', custom_call_target="tpu_custom_call"'
INTERIOR = "jit(_program)/shard_map/halo.interior/jit(_run_window_plan_tpu)"
FRAME = "jit(_program)/shard_map/halo.frame/jit(_run_window_plan_tpu)"
EXCHANGE = "jit(_program)/shard_map/halo.exchange"
LAYERS = {
    "pad.1": f"{INTERIOR}/engine.pad/jit(_pad)/pad",
    "repro_window.2": f"{INTERIOR}/engine.kernel/repro_window/pallas_call",
    "concatenate.3": f"{EXCHANGE}/concatenate",
    "collective-permute-done.4": f"{EXCHANGE}/ppermute",
    "pad.5": f"{FRAME}/engine.pad/jit(_pad)/pad",
    "repro_window.6": f"{FRAME}/engine.kernel/repro_window/pallas_call",
    "slice.7": f"{FRAME}/engine.crop/slice",
    "dynamic-update-slice.8": "jit(_program)/shard_map/halo.splice/scatter",
    "copy-start.9": f"{EXCHANGE}/concatenate",
}


def _profile(extra_chip1=()):
    """Two chips of a sharded sweep; times in ns, window [1000, 2000)."""
    host = _plane("/host:CPU", [("python", [_event("bench.window", *WINDOW)])])
    dev0 = _plane("/device:TPU:0", [
        ("XLA Ops", [
            _event(_op("pad.1", "pad"), 1000, 1100),
            _event(_op("repro_window.2", "custom-call", KERNEL), 1100, 1600),
            _event(_op("concatenate.3", "concatenate"), 1600, 1650),
            _event(_op("collective-permute-done.4",
                       "collective-permute-done"), 1650, 1700),
            _event(_op("pad.5", "pad"), 1700, 1720),
            _event(_op("repro_window.6", "custom-call", KERNEL), 1720, 1760),
            _event(_op("slice.7", "slice"), 1760, 1770),
            _event(_op("dynamic-update-slice.8", "dynamic-update-slice"),
                   1770, 1800)]),
        ("Async XLA Ops", [
            _event(_op("copy-start.9", "copy-start"), 1600, 1700)])])
    dev1 = _plane("/device:TPU:1", [("XLA Ops", [
        _event(_op("pad.1", "pad"), 1000, 1050),
        _event(_op("repro_window.2", "custom-call", KERNEL), 1050, 1900),
        *extra_chip1])])
    return types.SimpleNamespace(planes=[host, dev0, dev1])


def _run(reduced):
    cell = harness.resolve("jacobi2d-48k-x4.t4")
    return harness.Run(cell=dataclasses.replace(cell, chips=2), peaks={},
                       setup_s=1.0, window_s=1e-6, calls=1, cell_updates=0,
                       work={"flops": 10, "bytes": 100}, trace=reduced)


def _readers():
    cell = harness.resolve("jacobi2d-48k-x4.t4")
    return {m: harness.metric_reader(cell, m).read
            for m in ("engine_pad_share", "halo_copy_share",
                      "engine_wrapper_share")}


@pytest.fixture
def layer_names(monkeypatch):
    """The readers see ``LAYERS`` as the compiled program's layer map."""
    monkeypatch.setattr(layers, "layer_map", lambda cell, *a, **k: LAYERS)


def test_layer_readers_on_a_reduced_trace(layer_names):
    read = _readers()
    run = _run(tr.reduce_profile(_profile(), kernel="tpu_custom_call"))
    # busy: chip 0 [1000, 1800), chip 1 [1000, 1900)
    busy = 800 + 900
    # chip 0: the interior pad, the frame pad and the crop; chip 1: its pad
    assert read["engine_pad_share"](run) == pytest.approx(
        100 * (100 + 20 + 10 + 50) / busy)
    # chip 0: the concatenate under the async copy [1600, 1700), the
    # splice; not the collective, not the frame kernel's pad or crop
    assert read["halo_copy_share"](run) == pytest.approx(
        100 * (100 + 30) / busy)
    # every op that is neither kernel nor collective is in one of the two
    assert (read["engine_pad_share"](run) + read["halo_copy_share"](run)
            == pytest.approx(read["engine_wrapper_share"](run)))


def test_an_op_missing_from_the_map_is_in_no_layer(layer_names):
    read = _readers()
    run = _run(tr.reduce_profile(
        _profile([_event(_op("fusion.99", "fusion"), 1900, 1950)]),
        kernel="tpu_custom_call"))
    busy = 800 + 950
    assert read["engine_pad_share"](run) == pytest.approx(
        100 * 180 / busy)
    assert read["halo_copy_share"](run) == pytest.approx(100 * 130 / busy)
    assert read["engine_wrapper_share"](run) == pytest.approx(
        100 * (260 + 100) / busy)


def test_layer_readers_report_nothing_without_layer_names(monkeypatch):
    """A program that names no layers (one older than its scopes): an
    empty map, or no ``repro.obs.scopes`` at all; and a run untraced."""
    run = _run(tr.reduce_profile(_profile(), kernel="tpu_custom_call"))
    read = _readers()
    real = layers.layer_map
    monkeypatch.setattr(layers, "layer_map", lambda cell, *a, **k: {})
    assert read["engine_pad_share"](run) is None
    assert read["halo_copy_share"](run) is None
    monkeypatch.setattr(layers, "layer_map", real)
    monkeypatch.setattr(layers, "_scopes", lambda: None)
    monkeypatch.setattr(layers, "_MAPS", {})
    assert read["engine_pad_share"](run) is None
    assert read["halo_copy_share"](run) is None
    assert layers._MAPS == {run.cell.name: {}}      # nothing compiled
    assert read["engine_pad_share"](_run(None)) is None


def test_instruction_name_of_a_trace_op():
    assert layers.instruction(
        "%pad.2 = f32[16392,16512]{1,0:T(8,128)} pad(f32[16384,16384]{1,0} "
        "%x.1, f32[] %constant.2), padding=1_7x1_127") == "pad.2"
    assert layers.instruction("repro_window.1") == "repro_window.1"


def test_layer_map_of_a_small_cell(small_cell, monkeypatch):
    """The timed program of a cell, compiled through the Pallas
    interpreter on the CPU: its pad and its kernel under their layers."""
    monkeypatch.setattr(layers, "_MAPS", {})
    paths = layers.layer_map(small_cell("jacobi2d-16k.t1", 256),
                             devices=jax.devices()[:1], impl="interpret")
    found = {layers.layer_of(p, "engine.") for p in paths.values()}
    assert {"engine.pad", "engine.kernel"} <= found
