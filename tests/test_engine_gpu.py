"""GPU-backend equivalence suite (DESIGN.md §14).

The GPU lowering (``core/engine_gpu.py``) maps the unchanged plan IR
onto warp-shuffle psum shifts, SMEM skirt staging and per-thread
register accumulators. Interpret mode runs that lowering on any host,
so CI proves here that for every plan family

1. ``warp_shift`` — the shuffle + warp-boundary hand-off decomposition —
   is *bitwise* ``jnp.roll`` (the emulation contract the module
   docstring documents),
2. the GPU lowering matches the TPU lowering and the pure-jnp oracles
   in ``ref.py`` across the full Table-3 zoo × schedule variants ×
   ``time_steps ∈ {1, 2}``, convs (all ranks), scans and recurrences,
3. the ops layer's ``backend=`` / ``repro.config`` session default
   actually select it.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import config
from repro.core import (conv2d_nchw_plan, conv2d_plan, conv2d_same_plan,
                        linear_recurrence_plan, run_scan_plan,
                        run_window_plan, scan_plan, stencil2d_plan,
                        stencil3d_plan)
from repro.core import engine_gpu
from repro.core.engine_gpu import run_scan_plan_gpu, run_window_plan_gpu, \
    warp_shift
from repro.core.plan import GPU_WARP_LANES
from repro.kernels import ref
from repro.kernels.stencils import BENCHMARKS

VARIANTS = ("shift_psum", "shift_data")


def assert_close(a, b, tol=3e-5):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=tol, atol=tol)


def assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# warp_shift: the shuffle decomposition is exactly a lane roll
# ---------------------------------------------------------------------------

class TestWarpShift:
    @pytest.mark.parametrize("shift", [0, 1, 5, 31, 32, 33, 64, 95, 127])
    @pytest.mark.parametrize("lanes", [32, 64, 128, 256])
    def test_bitwise_roll_warp_aligned(self, rng, lanes, shift):
        """shift = q·warp + r decomposition composes to the exact roll."""
        v = jnp.array(rng.standard_normal((6, lanes)), jnp.float32)
        assert_bitwise(warp_shift(v, shift), jnp.roll(v, shift, axis=-1))

    @pytest.mark.parametrize("shift", [1, 17, 32, 40])
    def test_negative_shift_shfl_down(self, rng, shift):
        v = jnp.array(rng.standard_normal((4, 128)), jnp.float32)
        assert_bitwise(warp_shift(v, -shift), jnp.roll(v, -shift, axis=-1))

    @pytest.mark.parametrize("lanes", [8, 48, 100])
    def test_fractional_warp_falls_back(self, rng, lanes):
        """Lane extents that are not whole warps use the documented
        plain-roll fallback — same values either way."""
        v = jnp.array(rng.standard_normal((3, lanes)), jnp.float32)
        assert_bitwise(warp_shift(v, 3), jnp.roll(v, 3, axis=-1))

    def test_nd_leading_axes(self, rng):
        v = jnp.array(rng.standard_normal((2, 3, 4, 64)), jnp.float32)
        assert_bitwise(warp_shift(v, 33), jnp.roll(v, 33, axis=-1))

    def test_custom_warp_width(self, rng):
        v = jnp.array(rng.standard_normal((2, 64)), jnp.float32)
        assert_bitwise(warp_shift(v, 10, warp=16),
                       jnp.roll(v, 10, axis=-1))


# ---------------------------------------------------------------------------
# Table-3 zoo: GPU lowering vs TPU lowering vs the jnp oracle
# ---------------------------------------------------------------------------

class TestStencilZooGpu:
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("t", [1, 2])
    @pytest.mark.parametrize("name", sorted(BENCHMARKS))
    def test_zoo_matrix(self, rng, name, t, variant):
        sdef = BENCHMARKS[name]
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((22, 64)), jnp.float32)
            plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
            block = (8, 32)
        else:
            x = jnp.array(rng.standard_normal((8, 10, 32)), jnp.float32)
            plan = stencil3d_plan(sdef.offsets, coeffs=sdef.coeffs)
            block = (4, 4, 32)
        gpu = run_window_plan_gpu(x, plan=plan, block=block, time_steps=t,
                                  variant=variant)
        tpu = run_window_plan(x, plan=plan, block=block, time_steps=t,
                              variant=variant, backend="tpu")
        assert_close(gpu, ref.stencil_iterate(x, sdef, t), 2e-4)
        # same tap walk, same accumulation order → bitwise across backends
        assert_bitwise(gpu, tpu)

    @pytest.mark.parametrize("name", ["2d25pt", "2d121pt", "3d27pt"])
    def test_mxu_strategy_on_gpu(self, rng, name):
        """strategy='mxu' (tensor-core im2row) through the GPU lowering
        matches the lanes schedule to fp32 tolerance."""
        sdef = BENCHMARKS[name]
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((24, 64)), jnp.float32)
            plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
            block = (8, 32)
        else:
            x = jnp.array(rng.standard_normal((8, 10, 32)), jnp.float32)
            plan = stencil3d_plan(sdef.offsets, coeffs=sdef.coeffs)
            block = (4, 4, 32)
        mxu = run_window_plan_gpu(x, plan=plan, block=block, strategy="mxu")
        assert_close(mxu, ref.stencil_iterate(x, sdef, 1), 2e-5)


# ---------------------------------------------------------------------------
# conv family through the GPU lowering
# ---------------------------------------------------------------------------

class TestConvGpu:
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("fs", [2, 3, 5, 7])
    def test_conv2d_valid(self, rng, fs, variant):
        x = jnp.array(rng.standard_normal((24, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((fs, fs)), jnp.float32)
        gpu = run_window_plan_gpu(x, w, plan=conv2d_plan(fs, fs),
                                  block=(8, 32), variant=variant)
        tpu = run_window_plan(x, w, plan=conv2d_plan(fs, fs), block=(8, 32),
                              variant=variant, backend="tpu")
        assert_close(gpu, ref.conv2d_valid(x, w))
        assert_bitwise(gpu, tpu)

    def test_conv2d_same(self, rng):
        x = jnp.array(rng.standard_normal((20, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 5)), jnp.float32)
        gpu = run_window_plan_gpu(x, w, plan=conv2d_same_plan(5, 3),
                                  block=(8, 32))
        assert_close(gpu, ref.conv2d_same(x, w))

    def test_conv2d_nchw_register_accumulator(self, rng):
        """The reduce sweep (NCHW C_in accumulation) through the GPU
        kernel's register-accumulator discipline."""
        B, Ci, Co, H, W = 2, 3, 4, 12, 32
        x = jnp.array(rng.standard_normal((B, Ci, H, W)), jnp.float32)
        w = jnp.array(rng.standard_normal((Co, Ci, 3, 3)), jnp.float32)
        plan = conv2d_nchw_plan(B, Ci, Co, 3, 3)
        gpu = run_window_plan_gpu(x, w, plan=plan, block=(8, 16))
        tpu = run_window_plan(x, w, plan=plan, block=(8, 16), backend="tpu")
        assert_close(gpu, ref.conv2d_nchw(x, w, "valid"), 1e-4)
        assert_close(gpu, tpu, 1e-6)

    def test_ops_conv1d_causal_gpu(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((4, 50, 8)), jnp.float32)
        w = jnp.array(rng.standard_normal((4, 8)), jnp.float32)
        gpu = ops.conv1d_causal(x, w, impl="interpret", backend="gpu")
        assert_close(gpu, ref.conv1d_causal(x, w))

    def test_epilogue_fusion_gpu(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((20, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 3)), jnp.float32)
        b = jnp.float32(0.7)
        gpu = ops.conv2d(x, w, impl="interpret", backend="gpu",
                         epilogue=("bias", "gelu"),
                         epilogue_args=(b,))
        want = ops.conv2d(x, w, impl="xla", epilogue=("bias", "gelu"),
                          epilogue_args=(b,))
        assert_close(gpu, want, 1e-4)

    def test_strided_grid_gpu(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((20, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 3)), jnp.float32)
        gpu = ops.conv2d(x, w, impl="interpret", backend="gpu", stride=2)
        want = ops.conv2d(x, w, impl="xla", stride=2)
        assert_close(gpu, want, 1e-4)


# ---------------------------------------------------------------------------
# scans and recurrences
# ---------------------------------------------------------------------------

class TestScanGpu:
    def test_cumsum_bitwise_vs_tpu(self, rng):
        x = jnp.array(rng.standard_normal((8, 256)), jnp.float32)
        plan = scan_plan(128)
        gpu = run_scan_plan_gpu(x, plan=plan, block_r=4)
        tpu = run_scan_plan(x, plan=plan, block_r=4, backend="tpu")
        assert_close(gpu, jnp.cumsum(x, axis=-1), 1e-4)
        assert_bitwise(gpu, tpu)

    def test_linrec_one_ulp_vs_tpu(self, rng):
        """linrec's per-step A·Bs + B may contract to FMA differently
        between the kernel bodies — allow ≤1 ulp, nothing more."""
        a = jnp.array(rng.uniform(0.5, 1.0, (4, 128)), jnp.float32)
        b = jnp.array(rng.standard_normal((4, 128)), jnp.float32)
        plan = linear_recurrence_plan(128)
        gpu = run_scan_plan_gpu(a, b, plan=plan, block_r=4)
        tpu = run_scan_plan(a, b, plan=plan, block_r=4, backend="tpu")
        g, t = np.asarray(gpu), np.asarray(tpu)
        ulp = np.spacing(np.maximum(np.abs(g), np.abs(t)))
        assert np.all(np.abs(g - t) <= ulp)
        want = ref.linear_recurrence(a, b)
        assert_close(gpu, want, 1e-4)

    def test_carry_round_trip(self, rng):
        x = jnp.array(rng.standard_normal((4, 128)), jnp.float32)
        plan = scan_plan(64)
        y1, c1 = run_scan_plan_gpu(x[:, :64], plan=plan, block_r=4,
                                   return_carry=True)
        y2 = run_scan_plan_gpu(x[:, 64:], plan=plan, block_r=4, carry=c1)
        whole = run_scan_plan_gpu(x, plan=plan, block_r=4)
        assert_close(jnp.concatenate([y1, y2], axis=-1), whole, 1e-5)

    def test_chunked_linear_recurrence_gpu(self, rng):
        from repro.kernels import ops
        a = jnp.array(rng.uniform(0.5, 1.0, (2, 3, 70)), jnp.float32)
        b = jnp.array(rng.standard_normal((2, 3, 70)), jnp.float32)
        got = ops.chunked_linear_recurrence(a, b, chunk=32, impl="engine",
                                            backend="gpu")
        want = ops.chunked_linear_recurrence(a, b)
        assert_close(got, want, 1e-4)


# ---------------------------------------------------------------------------
# dispatch: ops backend=, config default, and gradients
# ---------------------------------------------------------------------------

class TestBackendDispatch:
    def test_ops_stencil_backend_kwarg(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((24, 96)), jnp.float32)
        g = ops.stencil(x, "2d9pt", impl="interpret", backend="gpu",
                        time_steps=2)
        t = ops.stencil(x, "2d9pt", impl="interpret", backend="tpu",
                        time_steps=2)
        assert_bitwise(g, t)

    def test_unknown_backend_named_error(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((8, 32)), jnp.float32)
        with pytest.raises(ValueError, match="ops.stencil.*cuda"):
            ops.stencil(x, "2d5pt", impl="interpret", backend="cuda")

    def test_config_session_default(self, rng):
        """set_engine_backend('gpu') routes backend=None calls to the
        GPU lowering; None restores auto (tpu on this host)."""
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((16, 64)), jnp.float32)
        want = ops.stencil(x, "2d5pt", impl="interpret")
        try:
            config.set_engine_backend("gpu")
            assert config.engine_backend() == "gpu"
            got = ops.stencil(x, "2d5pt", impl="interpret")
        finally:
            config.set_engine_backend(None)
        assert config.engine_backend() in ("tpu", "gpu")
        assert_close(got, want, 1e-6)

    def test_env_default(self, monkeypatch):
        monkeypatch.setenv(config.ENGINE_BACKEND_ENV, "gpu")
        assert config.engine_backend() == "gpu"
        monkeypatch.setenv(config.ENGINE_BACKEND_ENV, "bogus")
        with pytest.raises(ValueError, match="bogus"):
            config.engine_backend()

    def test_grad_through_gpu_backend(self, rng):
        """jax.grad of an ops call pinned to the GPU lowering runs the
        adjoint plan through the same backend and matches the oracle."""
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((16, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 3)), jnp.float32)
        gx, gw = jax.grad(lambda a, b: jnp.sum(ops.conv2d(
            a, b, impl="interpret", backend="gpu") ** 2), (0, 1))(x, w)
        wx, ww = jax.grad(lambda a, b: jnp.sum(ops.conv2d(
            a, b, impl="xla") ** 2), (0, 1))(x, w)
        assert_close(gx, wx, 1e-3)
        assert_close(gw, ww, 1e-3)

    def test_machine_model_registry(self):
        from repro.core import perfmodel, tuning
        gpu = perfmodel.machine_for("gpu")
        tpu = perfmodel.machine_for("tpu")
        assert gpu.backend == "gpu" and gpu.warp == GPU_WARP_LANES
        assert tpu.backend == "tpu" and tpu.lanes == 128
        with pytest.raises(ValueError, match="machine"):
            perfmodel.machine_for("npu")
        # the §5 model prices against the chosen machine's latencies
        sdef = BENCHMARKS["2d9pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        cfg = tuning.KernelConfig((8, 128), "shift_psum")
        ct = tuning.model_cost(plan, cfg, backend="tpu")
        cg = tuning.model_cost(plan, cfg, backend="gpu")
        assert ct > 0 and cg > 0 and ct != cg

    def test_gpu_candidates_warp_shaped(self):
        from repro.core import tuning
        sdef = BENCHMARKS["2d9pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        cands = tuning.candidate_configs(plan, (64, 256), backend="gpu")
        assert cands
        lanes = {c.block[-1] for c in cands}
        assert lanes <= {32, 64, 128, 256}, lanes

    def test_fused_pipeline_gpu(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((24, 96)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 3)), jnp.float32)
        g = ops.pipeline(x, ["2d5pt", (w, "gelu")], impl="interpret",
                         fuse=True, backend="gpu")
        t = ops.pipeline(x, ["2d5pt", (w, "gelu")], impl="interpret",
                         fuse=True, backend="tpu")
        assert_close(g, t, 1e-6)
        assert_close(g, ops.pipeline(x, ["2d5pt", (w, "gelu")], impl="xla"),
                     2e-4)

    def test_smem_staging_requested(self, monkeypatch):
        """The GPU lowering requests an SMEM staging buffer — the §14
        skirt-through-shared-memory discipline."""
        scratch = []
        sdef = BENCHMARKS["2d5pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        orig = engine_gpu.plgpu.SMEM

        def spy(shape, dtype):
            scratch.append(shape)
            return orig(shape, dtype)

        monkeypatch.setattr(engine_gpu.plgpu, "SMEM", spy)
        x = jnp.zeros((16, 64), jnp.float32)
        run_window_plan_gpu(x, plan=plan, block=(8, 32))
        assert scratch and scratch[0] == plan.block_in_shape((8, 32), 1)
