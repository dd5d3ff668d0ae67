"""Main-path engine kernels compiled for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached. What it refuses
here — unaligned blocks, unsupported vector shapes, too much VMEM — is
what the chip would refuse, so these tests guard every lowering change
at chip-filling widths. Each test asserts that the compiled program
holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a
time, so under several test workers only the worker running this file
may load it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


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
