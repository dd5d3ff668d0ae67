"""Engine equivalence suite: every plan family through the single
plan→Pallas lowering, validated three ways —

1. engine (Pallas interpret)  vs  the pure-jnp oracles in ``ref.py``,
2. engine                     vs  the plan executor (``executor.py``),
3. ``shift_psum``             vs  ``shift_data`` schedule variants,

across the full ``BENCHMARKS`` stencil table, conv filter shapes
2×2…9×9, ``time_steps ∈ {1, 2, 3}``, plus the perf-model autotuner.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (conv2d_batched_plan, conv2d_nchw_plan, conv2d_plan,
                        conv2d_same_plan, depthwise_conv1d_plan,
                        execute_conv_global, linear_recurrence_plan,
                        run_scan_plan, run_window_plan, run_window_plan_mxu,
                        scan_plan, stencil2d_plan, stencil3d_plan)
from repro.core import tuning
from repro.kernels import ref
from repro.kernels.stencils import BENCHMARKS

VARIANTS = ("shift_psum", "shift_data")


def assert_close(a, b, tol=3e-5):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# conv2d: filter sweep 2×2 … 9×9, engine vs oracle vs executor
# ---------------------------------------------------------------------------

class TestConvThroughEngine:
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("fs", [2, 3, 5, 7, 9])
    def test_square_filter_sweep(self, rng, fs, variant):
        x = jnp.array(rng.standard_normal((24, 56)), jnp.float32)
        w = jnp.array(rng.standard_normal((fs, fs)), jnp.float32)
        out = run_window_plan(x, w, plan=conv2d_plan(fs, fs),
                              block=(8, 32), variant=variant)
        assert_close(out, ref.conv2d_valid(x, w))

    @pytest.mark.parametrize("fshape", [(2, 5), (5, 2), (1, 4), (4, 1)])
    def test_rectangular_filters(self, rng, fshape):
        N, M = fshape
        x = jnp.array(rng.standard_normal((20, 48)), jnp.float32)
        w = jnp.array(rng.standard_normal((N, M)), jnp.float32)
        out = run_window_plan(x, w, plan=conv2d_plan(M, N), block=(4, 16))
        assert_close(out, ref.conv2d_valid(x, w))

    def test_engine_matches_executor(self, rng):
        """Same plan, two backends: the jnp.roll interpreter and the
        Pallas lowering agree — the schedule *is* the semantics."""
        x = jnp.array(rng.standard_normal((14, 60)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 5)), jnp.float32)
        a = execute_conv_global(conv2d_plan(5, 3, S=60, P=1), x, w)
        b = run_window_plan(x, w, plan=conv2d_plan(5, 3), block=(4, 16))
        assert_close(a, b, 1e-4)

    def test_variants_agree_to_ulp(self, rng):
        """Both variants add the same products in the same per-lane order;
        any residue is XLA FMA-contraction noise (≤ a few ulp)."""
        x = jnp.array(rng.standard_normal((24, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((4, 6)), jnp.float32)
        plan = conv2d_plan(6, 4)
        outs = [np.asarray(run_window_plan(x, w, plan=plan, block=(8, 32),
                                           variant=v)) for v in VARIANTS]
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Reduction axes: batched / NCHW conv2d through the engine
# ---------------------------------------------------------------------------

class TestBatchedConvThroughEngine:
    """The reduce-axes IR: grid over batch × C_out × spatial × C_in with
    an fp32 accumulator across the channel reduction — validated against
    ``jax.lax.conv_general_dilated`` (no Python loop anywhere)."""

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("mode", ["valid", "same"])
    @pytest.mark.parametrize("bcc", [(1, 1, 1), (2, 3, 4), (3, 4, 2)])
    def test_nchw_vs_lax(self, rng, bcc, mode, variant):
        B, C_in, C_out = bcc
        x = jnp.array(rng.standard_normal((B, C_in, 12, 40)), jnp.float32)
        w = jnp.array(rng.standard_normal((C_out, C_in, 3, 5)), jnp.float32)
        plan = conv2d_nchw_plan(B, C_in, C_out, 5, 3, mode=mode)
        out = run_window_plan(x, w, plan=plan, block=(8, 32), variant=variant)
        assert_close(out, ref.conv2d_nchw(x, w, mode), 1e-4)

    @pytest.mark.parametrize("fshape", [(2, 2), (5, 3), (1, 7), (4, 1)])
    def test_nchw_filter_sweep(self, rng, fshape):
        N, M = fshape
        x = jnp.array(rng.standard_normal((2, 3, 14, 36)), jnp.float32)
        w = jnp.array(rng.standard_normal((2, 3, N, M)), jnp.float32)
        plan = conv2d_nchw_plan(2, 3, 2, M, N)
        out = run_window_plan(x, w, plan=plan, block=(4, 16))
        assert_close(out, ref.conv2d_nchw(x, w, "valid"), 1e-4)

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("t", [1, 2])
    def test_batched_single_channel(self, rng, t, variant):
        """(B, H, W) stacks: the batch grid axis must reproduce a Python
        loop of per-image engine calls exactly, including under temporal
        blocking (reduce-free batched plans keep full t support)."""
        x = jnp.array(rng.standard_normal((3, 18, 40)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 5)), jnp.float32)
        bplan = conv2d_batched_plan(5, 3, mode="same")
        out = run_window_plan(x, w, plan=bplan, block=(8, 16), time_steps=t,
                              variant=variant)
        splan = conv2d_same_plan(5, 3)
        per_image = jnp.stack([
            run_window_plan(x[i], w, plan=splan, block=(8, 16), time_steps=t,
                            variant=variant)
            for i in range(x.shape[0])])
        assert_close(out, per_image, 1e-5)
        if t == 1:
            assert_close(out, ref.conv2d_batched(x, w, "same"), 1e-4)

    def test_ops_nchw_acceptance(self, rng):
        """Acceptance: ``ops.conv2d`` on an NCHW minibatch matches
        ``jax.lax.conv_general_dilated`` to fp32 tolerance."""
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((2, 3, 16, 48)), jnp.float32)
        w = jnp.array(rng.standard_normal((4, 3, 3, 3)), jnp.float32)
        for mode in ("same", "valid"):
            want = jax.lax.conv_general_dilated(
                x, w, (1, 1),
                [(1, 1), (1, 1)] if mode == "same" else "VALID",
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            assert_close(ops.conv2d(x, w, mode=mode, impl="interpret"),
                         want, 1e-4)
            assert_close(ops.conv2d(x, w, mode=mode, impl="xla"), want, 1e-4)

    def test_nchw_rejects_temporal_blocking(self, rng):
        x = jnp.zeros((1, 2, 8, 16), jnp.float32)
        w = jnp.zeros((2, 2, 3, 3), jnp.float32)
        plan = conv2d_nchw_plan(1, 2, 2, 3, 3, mode="same")
        with pytest.raises(AssertionError, match="temporal blocking"):
            run_window_plan(x, w, plan=plan, block=(8, 16), time_steps=2)

    def test_nchw_channel_mismatch(self):
        from repro.kernels import ops
        x = jnp.zeros((1, 3, 8, 16), jnp.float32)
        w = jnp.zeros((2, 4, 3, 3), jnp.float32)
        with pytest.raises(ValueError, match="C_in"):
            ops.conv2d(x, w, impl="interpret")

    def test_nchw_autotune(self, rng):
        """Tuned NCHW keys on the 4-D shape + nchw context — no
        collision with single-image winners."""
        from repro.kernels import ops
        tuning.clear_cache()
        x = jnp.array(rng.standard_normal((2, 2, 16, 64)), jnp.float32)
        w = jnp.array(rng.standard_normal((2, 2, 3, 3)), jnp.float32)
        out = ops.conv2d(x, w, impl="interpret", autotune=True)
        assert_close(out, ref.conv2d_nchw(x, w, "same"), 1e-4)
        keys = list(tuning._CACHE)
        assert any(k[1] == (2, 2, 16, 64) and "conv2d_nchw" in k[4]
                   for k in keys), keys


# ---------------------------------------------------------------------------
# Full BENCHMARKS table × variants × time_steps through the engine
# ---------------------------------------------------------------------------

class TestBenchmarkTableThroughEngine:
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("name",
                             [n for n, d in BENCHMARKS.items() if d.ndim == 2])
    def test_2d_table(self, rng, name, variant):
        sdef = BENCHMARKS[name]
        x = jnp.array(rng.standard_normal((26, 70)), jnp.float32)
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        out = run_window_plan(x, plan=plan, block=(8, 32), variant=variant)
        assert_close(out, ref.stencil_iterate(x, sdef, 1), 1e-4)

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("name",
                             [n for n, d in BENCHMARKS.items() if d.ndim == 3])
    def test_3d_table(self, rng, name, variant):
        sdef = BENCHMARKS[name]
        x = jnp.array(rng.standard_normal((10, 12, 40)), jnp.float32)
        plan = stencil3d_plan(sdef.offsets, coeffs=sdef.coeffs)
        out = run_window_plan(x, plan=plan, block=(4, 8, 16), variant=variant)
        assert_close(out, ref.stencil_iterate(x, sdef, 1), 1e-4)

    @pytest.mark.parametrize("t", [1, 2, 3])
    @pytest.mark.parametrize("name", ["2d5pt", "2d9pt", "2d25pt"])
    def test_temporal_blocking_2d(self, rng, name, t):
        sdef = BENCHMARKS[name]
        x = jnp.array(rng.standard_normal((24, 48)), jnp.float32)
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        out = run_window_plan(x, plan=plan, block=(8, 16), time_steps=t)
        assert_close(out, ref.stencil_iterate(x, sdef, t), 1e-4)

    @pytest.mark.parametrize("t", [1, 2, 3])
    def test_temporal_blocking_3d(self, rng, t):
        sdef = BENCHMARKS["3d7pt"]
        x = jnp.array(rng.standard_normal((8, 10, 24)), jnp.float32)
        plan = stencil3d_plan(sdef.offsets, coeffs=sdef.coeffs)
        out = run_window_plan(x, plan=plan, block=(4, 4, 8), time_steps=t)
        assert_close(out, ref.stencil_iterate(x, sdef, t), 1e-4)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_temporal_variants_agree(self, rng, variant):
        sdef = BENCHMARKS["2d9pt"]
        x = jnp.array(rng.standard_normal((20, 40)), jnp.float32)
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        out = run_window_plan(x, plan=plan, block=(8, 16), time_steps=2,
                              variant=variant)
        assert_close(out, ref.stencil_iterate(x, sdef, 2), 1e-4)


# ---------------------------------------------------------------------------
# conv1d + scan families through the same engine
# ---------------------------------------------------------------------------

class TestScanFamiliesThroughEngine:
    @pytest.mark.parametrize("K", [1, 2, 4, 8])
    def test_depthwise_conv1d(self, rng, K):
        x = jnp.array(rng.standard_normal((2, 37, 24)), jnp.float32)
        w = jnp.array(rng.standard_normal((K, 24)), jnp.float32)
        out = run_window_plan(x, w, plan=depthwise_conv1d_plan(K),
                              block=(16, 8))
        assert_close(out, ref.conv1d_causal(x, w), 1e-4)

    @pytest.mark.parametrize("T", [32, 100, 256])
    def test_cumsum(self, rng, T):
        x = jnp.array(rng.standard_normal((5, T)), jnp.float32)
        out = run_scan_plan(x, plan=scan_plan(32), block_r=4)
        assert_close(out, ref.cumsum(x), 1e-4)

    @pytest.mark.parametrize("T", [32, 100, 256])
    def test_linear_recurrence(self, rng, T):
        a = jnp.array(rng.uniform(0.5, 1.0, (5, T)), jnp.float32)
        b = jnp.array(rng.standard_normal((5, T)), jnp.float32)
        out = run_scan_plan(a, b, plan=linear_recurrence_plan(32), block_r=4)
        assert_close(out, ref.linear_recurrence(a, b), 1e-3)


# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------

class TestAutotuner:
    def setup_method(self):
        tuning.clear_cache()

    def test_candidates_respect_shape_and_vmem(self):
        sdef = BENCHMARKS["2d5pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        cands = tuning.candidate_configs(plan, (64, 96), time_steps=2)
        assert cands
        for c in cands:
            assert c.block[0] <= 64 and c.block[1] <= 96
            loaded = 1
            for b, h in zip(c.block, plan.halo(2)):
                loaded *= b + h
            assert loaded <= tuning.VMEM_BUDGET_ELEMS

    def test_model_prefers_low_halo_blocks(self):
        """§5.3: larger lane tiles amortize the halo — the model must
        rank a (8, 512) block above (8, 128) for a wide stencil."""
        sdef = BENCHMARKS["2d21pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        small = tuning.model_cost(plan, tuning.KernelConfig((8, 128)))
        big = tuning.model_cost(plan, tuning.KernelConfig((8, 512)))
        assert big < small

    def test_autotuner_changes_default_config(self):
        """The tuner must demonstrably improve on the seed default
        (8, 128, shift_psum) for the Table 3 suite at model level."""
        sdef = BENCHMARKS["2d5pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        default = tuning.KernelConfig((8, 128))
        res = tuning.autotune(plan, (384, 384), default=default)
        assert res.config != default
        assert res.model_cost <= tuning.model_cost(plan, default)

    def test_measured_winner_never_loses_default(self, rng):
        from repro.kernels import ops
        tuning.clear_cache()
        x = jnp.array(rng.standard_normal((64, 128)), jnp.float32)
        default_us = tuning.measure_us(
            lambda: ops.stencil(x, "2d5pt", impl="interpret"))
        out = ops.stencil(x, "2d5pt", impl="interpret", autotune=True)
        assert_close(out, ref.stencil_iterate(x, BENCHMARKS["2d5pt"], 1), 1e-4)
        res = next(iter(tuning._CACHE.values()))
        assert res.source == "measured"
        # generous 2x guard: interpret-mode timings are noisy, but the
        # tuner measured the default too, so it cannot have picked a
        # config that is materially slower.
        assert res.measured_us <= 2.0 * max(default_us, 1.0)

    def test_cache_hit(self):
        sdef = BENCHMARKS["2d9pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        r1 = tuning.autotune(plan, (256, 256))
        r2 = tuning.autotune(plan, (256, 256))
        assert r1.config == r2.config
        assert r2.source == "cache"

    def test_scan_candidates(self):
        plan = scan_plan(128)
        cands = tuning.candidate_configs(plan, (64, 8192))
        assert cands
        assert all((c.block[1] & (c.block[1] - 1)) == 0 for c in cands)

    def test_nchw_candidates_use_spatial_shape(self):
        """Reduce/batch axes are block-1 grid axes — candidates tile the
        spatial extents only and stay within the VMEM budget."""
        plan = conv2d_nchw_plan(4, 3, 8, 5, 5)
        cands = tuning.candidate_configs(plan, (4, 3, 64, 96))
        assert cands
        for c in cands:
            assert len(c.block) == 2
            assert c.block[0] <= 60 and c.block[1] <= 92  # valid-mode out

    def test_sidecar_schema_staleness(self, tmp_path):
        """Entries stamped with an old engine schema are ignored on load
        and dropped by the next write-through (the ROADMAP age-out)."""
        import json
        path = tmp_path / "tuning.json"
        stale = {"block": [8, 128], "variant": "shift_psum",
                 "model_cost": 1.0, "measured_us": 5.0,
                 "schema": tuning.ENGINE_SCHEMA_VERSION - 1}
        fresh = dict(stale, schema=tuning.ENGINE_SCHEMA_VERSION)
        path.write_text(json.dumps(
            {"version": 1, "entries": {"stale-key": stale,
                                       "fresh-key": fresh}}))
        tuning.clear_sidecar()
        try:
            assert tuning.load_sidecar(str(path)) == 1   # stale one skipped
            assert "fresh-key" in tuning._SIDECAR
            tuning.save_sidecar(str(path))               # rewrite ages it out
            doc = json.loads(path.read_text())
            assert set(doc["entries"]) == {"fresh-key"}
            assert doc["entries"]["fresh-key"]["schema"] == \
                tuning.ENGINE_SCHEMA_VERSION
        finally:
            tuning.clear_sidecar()


# ---------------------------------------------------------------------------
# Engine-lowered recurrences: the production LM paths through run_scan_plan
# ---------------------------------------------------------------------------

class TestEngineLoweredRecurrences:
    """Acceptance: selective_scan / wkv6 / chunked_linear_recurrence give
    identical outputs through ``impl='engine'`` (run_scan_plan Kogge–
    Stone blocks) as through the chunked production schedules."""

    def test_chunked_linear_recurrence_engine(self, rng):
        from repro.kernels import ops
        a = jnp.array(rng.uniform(0.5, 1.0, (2, 3, 70)), jnp.float32)
        b = jnp.array(rng.standard_normal((2, 3, 70)), jnp.float32)
        want = ops.chunked_linear_recurrence(a, b)
        got = ops.chunked_linear_recurrence(a, b, chunk=32, impl="engine")
        assert_close(got, want, 1e-4)
        with pytest.raises(ValueError):
            ops.chunked_linear_recurrence(a, b, impl="nope")

    def test_selective_scan_engine(self, rng):
        from repro.nn import ssm
        B, T, Di, N = 2, 37, 6, 4
        delta = jnp.array(rng.uniform(0.1, 0.5, (B, T, Di)), jnp.float32)
        A_log = jnp.array(rng.uniform(-1, 0.5, (Di, N)), jnp.float32)
        Bm = jnp.array(rng.standard_normal((B, T, N)), jnp.float32)
        Cm = jnp.array(rng.standard_normal((B, T, N)), jnp.float32)
        x = jnp.array(rng.standard_normal((B, T, Di)), jnp.float32)
        y1, h1 = ssm.selective_scan(delta, A_log, Bm, Cm, x, chunk=16)
        y2, h2 = ssm.selective_scan(delta, A_log, Bm, Cm, x, impl="engine")
        assert_close(y2, y1, 2e-4)
        assert_close(h2, h1, 2e-4)

    def test_wkv6_engine(self, rng):
        from repro.nn import ssm
        B, T, H, K, V = 2, 33, 2, 4, 5
        r = jnp.array(rng.standard_normal((B, T, H, K)), jnp.float32)
        k = jnp.array(rng.standard_normal((B, T, H, K)), jnp.float32)
        v = jnp.array(rng.standard_normal((B, T, H, V)), jnp.float32)
        logw = jnp.array(-np.exp(rng.standard_normal((B, T, H, K))),
                         jnp.float32)
        u = jnp.array(rng.standard_normal((H, K)), jnp.float32)
        y1, S1 = ssm.wkv6_chunked(r, k, v, logw, u, chunk=16)
        y2, S2 = ssm.wkv6_chunked(r, k, v, logw, u, impl="engine")
        y3, _ = ssm.wkv6_sequential(r, k, v, logw, u)
        assert_close(y2, y1, 2e-4)
        assert_close(S2, S1, 2e-4)
        assert_close(y2, y3, 2e-4)      # and both match the gold oracle

    def test_mamba_block_engine_path(self, rng):
        from repro.nn import ssm
        specs = ssm.mamba_specs(16, d_inner=32, ssm_state=4)
        p = {k: jnp.array(rng.standard_normal(s.shape), jnp.float32) * 0.1
             for k, s in specs.items()}
        x = jnp.array(rng.standard_normal((2, 24, 16)), jnp.float32)
        o1, _ = ssm.mamba_apply(p, x, ssm_state=4)
        o2, _ = ssm.mamba_apply(p, x, ssm_state=4, conv_impl="interpret",
                                scan_impl="engine")
        assert_close(o2, o1, 2e-4)


# ---------------------------------------------------------------------------
# MXU lowering strategy (DESIGN.md §13): im2row matmul vs VPU shift-fma
# ---------------------------------------------------------------------------

class TestMxuStrategy:
    """Strategy equivalence matrix: for every windowed plan the MXU
    (im2row-over-the-tap-set matmul) lowering must match the lanes
    (shift-fma) lowering to fp32 tolerance, forward and under temporal
    blocking — so the §5 tuner may choose between them on cost alone."""

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("t", [1, 2])
    @pytest.mark.parametrize("name", sorted(BENCHMARKS))
    def test_table_matrix(self, rng, name, t, variant):
        sdef = BENCHMARKS[name]
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((22, 48)), jnp.float32)
            plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
            block = (8, 16)
        else:
            x = jnp.array(rng.standard_normal((8, 10, 24)), jnp.float32)
            plan = stencil3d_plan(sdef.offsets, coeffs=sdef.coeffs)
            block = (4, 4, 8)
        lanes = run_window_plan(x, plan=plan, block=block, time_steps=t,
                                variant=variant, strategy="lanes")
        mxu = run_window_plan(x, plan=plan, block=block, time_steps=t,
                              variant=variant, strategy="mxu")
        assert_close(mxu, lanes, 1e-4)
        if t == 1:
            assert_close(mxu, ref.stencil_iterate(x, sdef, 1), 1e-4)

    def test_run_window_plan_mxu_wrapper(self, rng):
        x = jnp.array(rng.standard_normal((20, 48)), jnp.float32)
        w = jnp.array(rng.standard_normal((3, 5)), jnp.float32)
        plan = conv2d_plan(5, 3)
        a = run_window_plan_mxu(x, w, plan=plan, block=(8, 16))
        b = run_window_plan(x, w, plan=plan, block=(8, 16), strategy="mxu")
        assert_close(a, b, 1e-6)
        assert_close(a, ref.conv2d_valid(x, w), 1e-4)

    @pytest.mark.parametrize("bcc", [(1, 1, 1), (2, 3, 4), (3, 4, 2)])
    @pytest.mark.parametrize("fshape", [(3, 3), (1, 7), (5, 2)])
    def test_nchw_matrix(self, rng, bcc, fshape):
        """NCHW reduce plans fold C_in·taps into one contraction — the
        MXU path must agree with lanes and lax across B/C/filters."""
        from repro.kernels import ops
        B, C_in, C_out = bcc
        N, M = fshape
        x = jnp.array(rng.standard_normal((B, C_in, 12, 40)), jnp.float32)
        w = jnp.array(rng.standard_normal((C_out, C_in, N, M)), jnp.float32)
        lanes = ops.conv2d(x, w, mode="same", impl="interpret",
                           strategy="lanes")
        mxu = ops.conv2d(x, w, mode="same", impl="interpret", strategy="mxu")
        assert_close(mxu, lanes, 1e-4)
        assert_close(mxu, ref.conv2d_nchw(x, w, "same"), 1e-4)

    def test_strided_conv_mxu(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((1, 3, 12, 40)), jnp.float32)
        w = jnp.array(rng.standard_normal((2, 3, 3, 3)), jnp.float32)
        want = ops.conv2d(x, w, mode="same", impl="xla", stride=(1, 2))
        for s in ("lanes", "mxu"):
            got = ops.conv2d(x, w, mode="same", impl="interpret",
                             stride=(1, 2), strategy=s)
            assert_close(got, want, 1e-4)

    def test_conv1d_causal_strategies_agree(self, rng):
        """Per-lane (depthwise) coefficients lower on the MXU as a
        lane-batched contraction — same output as the shift-fma path."""
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((2, 37, 24)), jnp.float32)
        w = jnp.array(rng.standard_normal((4, 24)), jnp.float32)
        lanes = ops.conv1d_causal(x, w, impl="interpret", strategy="lanes")
        mxu = ops.conv1d_causal(x, w, impl="interpret", strategy="mxu")
        assert_close(mxu, lanes, 1e-4)
        assert_close(mxu, ref.conv1d_causal(x, w), 1e-4)

    def test_fused_pipeline_strategies_agree(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((40, 72)), jnp.float32)
        chain = ["2d5pt", ("2d9pt", "gelu"), "2d5pt"]
        lanes = ops.pipeline(x, chain, impl="interpret", fuse=True,
                             strategy="lanes")
        mxu = ops.pipeline(x, chain, impl="interpret", fuse=True,
                           strategy="mxu")
        assert_close(mxu, lanes, 1e-4)
        assert_close(mxu, ops.pipeline(x, chain, impl="xla"), 1e-4)

    @pytest.mark.parametrize("strategy", ["lanes", "mxu"])
    def test_grouped_conv_vs_lax(self, rng, strategy):
        """groups= slices the reduce axis per group: validated against
        lax.conv_general_dilated's feature_group_count."""
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((2, 6, 10, 32)), jnp.float32)
        w = jnp.array(rng.standard_normal((4, 3, 3, 3)), jnp.float32)
        want = jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=2)
        got = ops.conv2d(x, w, mode="same", impl="interpret", groups=2,
                         strategy=strategy)
        assert_close(got, want, 1e-4)
        assert_close(ops.conv2d(x, w, mode="same", impl="xla", groups=2),
                     want, 1e-4)

    def test_depthwise_conv2d_groups(self, rng):
        """groups == C_in == C_out/1-per-group: the depthwise-2d case."""
        from repro.kernels import ops
        C = 6
        x = jnp.array(rng.standard_normal((2, C, 8, 24)), jnp.float32)
        w = jnp.array(rng.standard_normal((C, 1, 3, 3)), jnp.float32)
        want = jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=C)
        got = ops.conv2d(x, w, mode="same", impl="interpret", groups=C,
                         strategy="mxu")
        assert_close(got, want, 1e-4)

    def test_groups_validation_errors(self):
        from repro.kernels import ops
        x = jnp.zeros((1, 6, 8, 16), jnp.float32)
        with pytest.raises(ValueError, match="group"):
            ops.conv2d(x, jnp.zeros((4, 2, 3, 3), jnp.float32),
                       impl="interpret", groups=4)   # 2*4 != 6
        with pytest.raises(ValueError, match="group"):
            ops.conv2d(x, jnp.zeros((3, 3, 3, 3), jnp.float32),
                       impl="interpret", groups=2)   # C_out 3 % 2 != 0

    def test_invalid_strategy_named_error(self):
        from repro.kernels import ops
        x = jnp.zeros((16, 32), jnp.float32)
        with pytest.raises(ValueError, match="ops.stencil"):
            ops.stencil(x, "2d5pt", impl="interpret", strategy="tensor")

    def test_scan_plans_reject_strategy(self, rng):
        from repro.kernels import ops
        x = jnp.array(rng.standard_normal((4, 64)), jnp.float32)
        with pytest.raises(ValueError, match="strategy"):
            ops.cumsum(x, impl="interpret", strategy="mxu")

    def test_fuse_rejects_conflicting_pins(self):
        import dataclasses
        from repro.core.fuse import fuse_plans
        sdef = BENCHMARKS["2d5pt"]
        mk = lambda s: dataclasses.replace(
            stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs), strategy=s)
        with pytest.raises(ValueError, match="conflicting lowering"):
            fuse_plans(mk("lanes"), mk("mxu"))
        fused = fuse_plans(mk("mxu"), mk(None))   # one pin pins the chain
        assert fused.strategy == "mxu"
        assert fuse_plans(mk(None), mk(None)).strategy is None

    # ---- tuner integration (schema v5 strategy / v6 backend keys) ---------

    def test_candidates_enumerate_strategy(self):
        import dataclasses
        sdef = BENCHMARKS["2d25pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        cands = tuning.candidate_configs(plan, (64, 96))
        assert {"lanes", "mxu"} <= {c.strategy for c in cands}
        pinned = dataclasses.replace(plan, strategy="mxu")
        pcands = tuning.candidate_configs(pinned, (64, 96))
        assert pcands and all(c.strategy == "mxu" for c in pcands)

    def test_model_crossover_by_tap_count(self):
        """§5 + MXU terms: narrow stencils stay on the VPU lanes, wide
        tap sets flip to the matmul path — the shape-dependent choice
        the strategy dimension exists to expose."""
        def best(name):
            sdef = BENCHMARKS[name]
            plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs) \
                if sdef.ndim == 2 else \
                stencil3d_plan(sdef.offsets, coeffs=sdef.coeffs)
            cands = tuning.candidate_configs(plan, (512, 512) if
                                             sdef.ndim == 2 else (64, 64, 64))
            return min(cands, key=lambda c: tuning.model_cost(plan, c))
        assert best("2d5pt").strategy == "lanes"
        assert best("2d9pt").strategy == "lanes"
        assert best("2d25pt").strategy == "mxu"
        assert best("2d121pt").strategy == "mxu"
        assert best("3d27pt").strategy == "mxu"

    def test_autotune_records_strategy_v6(self, rng, tmp_path, monkeypatch):
        """Measured winners land in the sidecar with the strategy field
        and the 7-component (strategy- and backend-keyed) v6 key."""
        import json
        from repro.kernels import ops
        tuning.clear_cache()
        tuning.clear_sidecar()
        monkeypatch.setenv(tuning.SIDECAR_ENV, str(tmp_path / "side.json"))
        try:
            x = jnp.array(rng.standard_normal((48, 96)), jnp.float32)
            out = ops.stencil(x, "2d25pt", impl="interpret", autotune=True,
                              strategy="mxu")
            assert_close(out, ref.stencil_iterate(x, BENCHMARKS["2d25pt"], 1),
                         1e-4)
            assert tuning._SIDECAR
            key, (cfg, _, _) = next(iter(tuning._SIDECAR.items()))
            parts = json.loads(key)
            assert len(parts) == 7 and parts[-2] == "mxu"
            assert parts[-1] in ("tpu", "gpu")
            assert cfg.strategy == "mxu"
            entries = tuning.sidecar_entries()
            assert all(v["schema"] == tuning.ENGINE_SCHEMA_VERSION
                       and v["strategy"] == "mxu" for v in entries.values())
        finally:
            tuning.clear_sidecar()
            tuning.clear_cache()

    def test_autotune_gpu_backend_v6_entries(self, rng, tmp_path,
                                             monkeypatch):
        """``autotune(backend='gpu')`` lands warp-shaped winners under a
        key whose seventh component says 'gpu' — and the same op tuned
        on the TPU lowering gets its own separate entry."""
        import json
        from repro.kernels import ops
        tuning.clear_cache()
        tuning.clear_sidecar()
        monkeypatch.setenv(tuning.SIDECAR_ENV, str(tmp_path / "side.json"))
        try:
            x = jnp.array(rng.standard_normal((48, 96)), jnp.float32)
            g = ops.stencil(x, "2d5pt", impl="interpret", autotune=True,
                            backend="gpu")
            t = ops.stencil(x, "2d5pt", impl="interpret", autotune=True,
                            backend="tpu")
            assert_close(g, ref.stencil_iterate(x, BENCHMARKS["2d5pt"], 1),
                         1e-4)
            assert_close(t, ref.stencil_iterate(x, BENCHMARKS["2d5pt"], 1),
                         1e-4)
            backends = {json.loads(k)[-1] for k in tuning._SIDECAR}
            assert {"gpu", "tpu"} <= backends
            # GPU winners come from the warp-multiple grid
            for k, (cfg, _, _) in tuning._SIDECAR.items():
                if json.loads(k)[-1] == "gpu" and len(cfg.block) == 2:
                    assert cfg.block[-1] % 32 == 0 or cfg.block[-1] < 32
        finally:
            tuning.clear_sidecar()
            tuning.clear_cache()

    def test_nearest_seed_never_crosses_strategy(self):
        """Satellite regression: nearest-shape seeding requires the
        strategy key component to match — a winner tuned under an 'mxu'
        pin must never seed an auto or 'lanes' tune."""
        sdef = BENCHMARKS["2d9pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        sig = tuning.plan_signature(plan)
        tuning.clear_sidecar()
        try:
            cfg = tuning.KernelConfig((8, 64), "shift_psum", "mxu")
            key = tuning._sidecar_key(sig, (128, 128), 1, (), "mxu")
            tuning._SIDECAR[key] = (cfg, 1.0, 2.0)
            assert tuning._nearest_sidecar(sig, (96, 96), 1, (), "mxu") == cfg
            assert tuning._nearest_sidecar(sig, (96, 96), 1, (), "auto") \
                is None
            assert tuning._nearest_sidecar(sig, (96, 96), 1, (), "lanes") \
                is None
        finally:
            tuning.clear_sidecar()

    def test_nearest_seed_never_crosses_backend(self):
        """v6 regression: a winner measured against the GPU warp tiling
        must never seed a TPU tune of the same plan/shape — the key's
        seventh component keeps the lowerings apart."""
        sdef = BENCHMARKS["2d9pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        sig = tuning.plan_signature(plan)
        tuning.clear_sidecar()
        try:
            cfg = tuning.KernelConfig((8, 64), "shift_psum")
            key = tuning._sidecar_key(sig, (128, 128), 1, (), "auto", "gpu")
            tuning._SIDECAR[key] = (cfg, 1.0, 2.0)
            assert tuning._nearest_sidecar(
                sig, (96, 96), 1, (), "auto", "gpu") == cfg
            assert tuning._nearest_sidecar(
                sig, (96, 96), 1, (), "auto", "tpu") is None
        finally:
            tuning.clear_sidecar()

    def test_nearest_seed_skips_unaligned_block(self):
        """A winner clamped to a small shape's 96-lane extent would tile
        a wider shape at 96-lane offsets, which Mosaic refuses: the TPU
        tuner seeds from the nearest *tile-aligned* winner instead."""
        sdef = BENCHMARKS["2d9pt"]
        plan = stencil2d_plan(sdef.offsets, coeffs=sdef.coeffs)
        sig = tuning.plan_signature(plan)
        clamped = tuning.KernelConfig((8, 96), "shift_psum")
        aligned = tuning.KernelConfig((16, 128), "shift_psum")
        assert tuning.tile_aligned(plan, clamped, (96, 96))
        assert not tuning.tile_aligned(plan, clamped, (96, 512))
        assert tuning.tile_aligned(plan, aligned, (96, 512))
        tuning.clear_sidecar()
        try:
            tuning._SIDECAR[tuning._sidecar_key(
                sig, (96, 96), 1, (), "auto")] = (clamped, 1.0, 2.0)
            tuning._SIDECAR[tuning._sidecar_key(
                sig, (16, 4096), 1, (), "auto")] = (aligned, 1.0, 2.0)
            usable = lambda c: tuning.tile_aligned(plan, c, (96, 512))
            assert tuning._nearest_sidecar(
                sig, (96, 512), 1, (), "auto") == clamped
            assert tuning._nearest_sidecar(
                sig, (96, 512), 1, (), "auto", "tpu", usable) == aligned
        finally:
            tuning.clear_sidecar()

    def test_stale_v5_sidecar_entries_ignored(self, tmp_path):
        """v5 sidecars predate the backend dimension (6-component keys,
        schema 5): the loader and the checkpoint merge path must drop
        every entry — a v5 winner never recorded which lowering it
        measured."""
        import json
        v5_key = json.dumps(["conv2d:5x3", [64, 64], 1, "cpu", [], "auto"])
        entries = {v5_key: {"block": [8, 128], "variant": "shift_psum",
                            "strategy": None, "model_cost": 1.0,
                            "measured_us": 5.0, "schema": 5}}
        path = tmp_path / "v5.json"
        path.write_text(json.dumps({"version": 1, "entries": entries}))
        tuning.clear_sidecar()
        try:
            assert tuning.load_sidecar(str(path)) == 0
            assert not tuning._SIDECAR
            assert tuning.merge_sidecar_entries(entries) == 0
            assert not tuning._SIDECAR
        finally:
            tuning.clear_sidecar()

    def test_stale_v4_sidecar_entries_ignored(self, tmp_path):
        """v4 sidecars predate the strategy dimension (no strategy field,
        5-component keys): both the file loader and the checkpoint merge
        path must drop every entry — a v4 winner was never tuned over
        the algorithm choice."""
        import json
        v4_key = json.dumps(["conv2d:5x3", [64, 64], 1, "cpu", []])
        entries = {v4_key: {"block": [8, 128], "variant": "shift_psum",
                            "model_cost": 1.0, "measured_us": 5.0,
                            "schema": 4}}
        path = tmp_path / "v4.json"
        path.write_text(json.dumps({"version": 1, "entries": entries}))
        tuning.clear_sidecar()
        try:
            assert tuning.load_sidecar(str(path)) == 0
            assert not tuning._SIDECAR
            assert tuning.merge_sidecar_entries(entries) == 0
            assert not tuning._SIDECAR
        finally:
            tuning.clear_sidecar()
