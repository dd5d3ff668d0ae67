"""Main-path engine kernels compiled for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached. What it refuses
here — unaligned blocks, unsupported vector shapes, too much VMEM — is
what the chip would refuse, so these tests guard every lowering change
at chip-filling widths. Each test asserts that the compiled program
holds the Pallas kernel (``tpu_custom_call``). The layer tests compile
small one-chip and 2x2 programs and check that every instruction a
device trace would show lies under the layer it belongs to
(``repro.obs.scopes``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a
time, so under several test workers only the worker running this file
may load it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import ops
from repro.obs import scopes


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile is written to a persistent cache but cannot be
    # read back without a chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh2x2(topo):
    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _kernels(one_chip, fn, *shapes) -> int:
    """Compile ``fn`` for the described chip; the number of Pallas
    kernels in the compiled program."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _nchw_grads(x, w):
    return jax.grad(lambda x, w: ops.conv2d(
        x, w, mode="same", impl="pallas").sum(), argnums=(0, 1))(x, w)


FIELD = (8192, 8192)
CASES = {
    "2d5pt_block8x128": (
        lambda x: ops.stencil(x, "2d5pt", impl="pallas"), [FIELD], 1),
    "2d5pt_block64x512": (
        lambda x: ops.stencil(x, "2d5pt", impl="pallas",
                              block_h=64, block_w=512), [FIELD], 1),
    "2d121pt_t2": (
        lambda x: ops.stencil(x, "2d121pt", time_steps=2, impl="pallas"),
        [FIELD], 1),
    "3d27pt": (
        lambda x: ops.stencil(x, "3d27pt", impl="pallas"),
        [(512, 512, 512)], 1),
    "conv2d_same_5x5": (
        lambda x, w: ops.conv2d(x, w, mode="same", impl="pallas"),
        [FIELD, (5, 5)], 1),
    "conv2d_same_5x5_mxu": (
        lambda x, w: ops.conv2d(x, w, mode="same", strategy="mxu",
                                impl="pallas"),
        [FIELD, (5, 5)], 1),
    "2d25pt_mxu": (
        lambda x: ops.stencil(x, "2d25pt", strategy="mxu", impl="pallas"),
        [FIELD], 1),
    "nchw_forward": (
        lambda x, w: ops.conv2d(x, w, mode="same", impl="pallas"),
        [(8, 64, 256, 256), (64, 64, 3, 3)], 1),
    # backward-input (adjoint plan) + backward-weight (correlation) kernels
    "nchw_grad_x_w": (_nchw_grads, [(8, 64, 256, 256), (64, 64, 3, 3)], 2),
    "linear_recurrence_carry": (
        lambda a, b, h0: ops.linear_recurrence_carry(a, b, h0,
                                                     impl="pallas"),
        [(2048, 16384), (2048, 16384), (2048,)], 1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_to_pallas_kernel(one_chip, case):
    fn, shapes, kernels = CASES[case]
    assert _kernels(one_chip, fn, *shapes) == kernels


# Instructions that hold data or rename it, and run no work of their own.
NO_WORK = ("parameter", "constant", "tuple", "bitcast")


def _entry_layers(fn, x):
    """``[(name, opcode, op_name path)]`` of the compiled ENTRY
    computation of ``fn(x)``: the ops a device trace shows."""
    text = jax.jit(fn).lower(x).compile().as_text()
    paths = scopes.instruction_layers(text)
    entry = text[text.index("\nENTRY"):]
    lines = entry[:entry.index("\n}")].splitlines()[2:]
    # scopes._parse: (name, opcode, operands, own op_name) of a line
    return [(n, op, paths.get(n)) for n, op, _, _ in
            map(scopes._parse, lines)]


def _assert_every_op_scoped(instrs):
    loose = [(n, op, p) for n, op, p in instrs if op not in NO_WORK
             and not (scopes.layer_of(p, "engine.")
                      or scopes.layer_of(p, "halo."))]
    assert not loose, loose


def test_layer_names_one_chip(one_chip):
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=one_chip)
    instrs = _entry_layers(
        lambda x: ops.stencil(x, "2d5pt", time_steps=4, impl="pallas"), x)
    kernels = [(n, p) for n, op, p in instrs if op == "custom-call"]
    assert len(kernels) == 1
    ((name, path),) = kernels
    assert name.startswith(scopes.WINDOW_KERNEL)
    assert scopes.layer_of(path, "engine.") == scopes.ENGINE_KERNEL
    pads = [p for _, op, p in instrs if op == "pad"]
    assert pads and all(scopes.layer_of(p, "engine.") == scopes.ENGINE_PAD
                        for p in pads)
    _assert_every_op_scoped(instrs)


def test_layer_names_2x2(mesh2x2):
    x = jax.ShapeDtypeStruct((512, 512), jnp.float32,
                             sharding=NamedSharding(mesh2x2,
                                                    P("data", "model")))
    instrs = _entry_layers(
        lambda x: ops.stencil(x, "2d5pt", time_steps=4, impl="pallas",
                              mesh=mesh2x2), x)
    halo = {n: scopes.layer_of(p, "halo.") for n, _, p in instrs}
    kernels = [(n, p) for n, op, p in instrs if op == "custom-call"
               and scopes.layer_of(p, "engine.") == scopes.ENGINE_KERNEL]
    assert all(n.startswith(scopes.WINDOW_KERNEL) for n, _ in kernels)
    assert sorted(halo[n] for n, _ in kernels) == (
        [scopes.HALO_FRAME] * 4 + [scopes.HALO_INTERIOR])
    # the extended block: the ppermutes and the whole-shard concatenates
    concats = [n for n, op, p in instrs
               if op != "get-tuple-element"
               and (p or "").endswith("/concatenate")]
    permutes = [n for n, op, _ in instrs if op.startswith("collective-")]
    assert concats and permutes
    assert {halo[n] for n in concats + permutes} == {scopes.HALO_EXCHANGE}
    updates = [n for n, op, _ in instrs if op == "dynamic-update-slice"]
    assert len(updates) == 4
    assert {halo[n] for n in updates} == {scopes.HALO_SPLICE}
    _assert_every_op_scoped(instrs)
