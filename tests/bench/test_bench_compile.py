"""The served cell's programs compiled for a described TPU v5e, at the
published widths and the configuration's matrix-product precision: the
B=1 prefill of the cycle's longest prompt, through the engine WKV scan
(its Pallas kernel, ``tpu_custom_call``, in the compiled program), and
the decode step of all the slots. No chip is needed; what the compiler
refuses here the chip would refuse. Run by a single test worker, as the
engine's compile tests are (``tests/test_tpu_compile.py``)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness
from bench.drivers import lm_serve

CELL = "rwkv6-1.6b.chat"
HBM_BYTES = 16 * 2**30          # one v5e chip


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
    # a described compile cannot be read back from a persistent cache
    # without a chip; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _abstract(specs, sharding):
    from repro.nn.spec import abstract_params
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        abstract_params(specs))


@pytest.mark.parametrize("program", ["prefill_longest", "decode_step"])
def test_served_program_compiles_for_v5e(program, one_chip, monkeypatch):
    from repro.kernels import ops
    from repro.models import build_model

    # the engine picks its path from the backend, which here is the CPU
    monkeypatch.setattr(ops, "default_engine_impl", lambda: "pallas")
    cell = harness.resolve(CELL)
    d = lm_serve.make(cell.config, cell.traffic, seed=0, impl="pallas",
                      devices=None)
    model = build_model(d._arch())
    params = _abstract(model.specs(), one_chip)
    if program == "prefill_longest":
        longest = max(length for length, _ in d.cycle)
        args = (params, jax.ShapeDtypeStruct((1, longest - 1), jnp.int32,
                                             sharding=one_chip))
        fn = model.prefill
    else:
        state = _abstract(model.decode_state_specs(
            d.slots, int(cell.traffic["cache_len"])), one_chip)
        args = (params, state,
                jax.ShapeDtypeStruct((d.slots, 1), jnp.int32,
                                     sharding=one_chip),
                jax.ShapeDtypeStruct((d.slots,), jnp.int32,
                                     sharding=one_chip))
        fn = model.serve_step
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        compiled = jax.jit(fn).lower(*args).compile()
    if program == "prefill_longest":
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
