"""Chaos matrix for the guarded-execution layer (DESIGN.md §16).

Covers the PR-10 acceptance gates:

- fault injection units: spec grammar, registry-closed arming, crc32
  determinism, context-manager state restore, one-bool-read off path;
- guard lattice units: demotion order, counter/annotation emission,
  'raise' vs 'fallback' policy semantics, organic errors re-raised
  unchanged, numerics guard;
- the per-site chaos matrix: for every registered engine site, (a)
  'raise' surfaces a structured error naming the site, (b) 'fallback'
  serves an oracle-equal result with the degradation counter bumped,
  (c) nothing armed → zero fired faults and zero demotions;
- acceptance sweep: every site armed at prob 1.0 under 'fallback' →
  the full Table-3 zoo, NCHW conv, fused pipelines and the scan family
  stay reference-equal on both engine backends, demotions observable;
- tuner hardening: retry/backoff, quarantine, model-ranked fallback,
  measurement rejection, tuning budget, sidecar checksums + corrupt-file
  quarantine;
- serving hardening: failed steps surface or shed load per policy,
  deadlines sweep, every request always comes back ``done``.

The suite-wide policy is pinned to 'raise' in tests/conftest.py so the
rest of the test suite can never vacuously pass through a silent oracle
fallback; chaos tests opt into 'fallback' explicitly.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, robust
from repro.core import tuning
from repro.kernels import ops, ref
from repro.kernels.stencils import BENCHMARKS
from repro.robust import faults, guard


@pytest.fixture(autouse=True)
def _clean_slate():
    faults.disarm()
    obs.metrics.reset()
    tuning.clear_cache()
    yield
    faults.disarm()
    tuning.clear_cache()


def _x2d(shape=(48, 128), seed=0):
    return jnp.asarray(np.random.default_rng(seed)
                       .standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# Fault-injection units
# ---------------------------------------------------------------------------

class TestFaultSpec:
    def test_parse_single(self):
        assert faults.parse_spec("engine.window:1.0") == {
            "engine.window": (1.0, 0)}

    def test_parse_multi_with_seed(self):
        spec = faults.parse_spec("engine.scan:0.5:7, serve.step:0.25")
        assert spec == {"engine.scan": (0.5, 7), "serve.step": (0.25, 0)}

    def test_parse_all_arms_every_site(self):
        spec = faults.parse_spec("all:0.5:3")
        assert set(spec) == set(faults.SITES)
        assert all(v == (0.5, 3) for v in spec.values())

    def test_unknown_site_is_named_error(self):
        with pytest.raises(ValueError, match="registered sites"):
            faults.parse_spec("engine.wndow:1.0")
        with pytest.raises(ValueError, match="registered sites"):
            faults.arm({"no.such.site": (1.0, 0)})

    def test_bad_prob_rejected(self):
        with pytest.raises(ValueError, match="not a float"):
            faults.parse_spec("engine.window:high")
        with pytest.raises(ValueError, match=r"\[0, 1\]"):
            faults.parse_spec("engine.window:1.5")
        with pytest.raises(ValueError, match="site:prob"):
            faults.parse_spec("engine.window")

    def test_deterministic_firing(self):
        """Which occurrences fire is a pure function of (seed, site, n):
        two fresh armings replay the identical pattern."""
        def pattern():
            out = []
            with robust.inject("engine.window:0.5:11"):
                for _ in range(64):
                    try:
                        faults.check("engine.window")
                        out.append(0)
                    except faults.FaultInjected:
                        out.append(1)
            return out

        p1, p2 = pattern(), pattern()
        assert p1 == p2
        assert 0 < sum(p1) < 64          # p=0.5 actually mixes

    def test_different_seeds_differ(self):
        def pattern(seed):
            out = []
            with robust.inject(f"engine.window:0.5:{seed}"):
                for _ in range(64):
                    try:
                        faults.check("engine.window")
                        out.append(0)
                    except faults.FaultInjected:
                        out.append(1)
            return out

        assert pattern(1) != pattern(2)

    def test_fault_carries_site_and_occurrence(self):
        with robust.inject("engine.scan:1.0"):
            with pytest.raises(faults.FaultInjected) as ei:
                faults.check("engine.scan")
        assert ei.value.site == "engine.scan"
        assert ei.value.occurrence == 0

    def test_inject_restores_prior_state(self):
        faults.arm("serve.step:0.25:9")
        with robust.inject("engine.window:1.0"):
            assert "engine.window" in faults.armed_sites()
        assert faults.armed_sites() == {"serve.step": (0.25, 9)}
        faults.disarm()
        assert faults.armed_sites() == {}

    def test_unarmed_site_never_fires(self):
        with robust.inject("engine.window:1.0"):
            faults.check("engine.scan")     # not armed: no-op
        assert faults.fired_counts() == {}

    def test_disarmed_check_is_cheap(self):
        """The off path is one module-global bool read — bound it loosely
        (10 µs/call) so only a real regression (dict lookup, lock, raise
        machinery on the hot path) can trip it on a noisy host."""
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            faults.check("engine.window")
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 10e-6, f"{per_call * 1e6:.2f} µs per no-op check"


# ---------------------------------------------------------------------------
# Guard lattice units
# ---------------------------------------------------------------------------

class TestGuardLattice:
    def test_first_success_emits_nothing(self):
        out = guard.run("op", [("tuned", lambda: 42),
                               ("oracle", lambda: 0)])
        assert out == 42
        assert obs.metrics.counter_total("robust.demotion") == 0
        assert obs.metrics.counter_total("robust.served_degraded") == 0

    def test_fallback_walks_lattice_and_counts(self):
        def boom():
            raise faults.FaultInjected("engine.window", 0)

        with robust.failure_policy("fallback"):
            out = guard.run("stencil", [("tuned", boom),
                                        ("default", boom),
                                        ("oracle", lambda: 7)])
        assert out == 7
        dem = obs.metrics.counter("robust.demotion")
        assert dem["stencil:tuned->default"] == 1
        assert dem["stencil:default->oracle"] == 1
        assert obs.metrics.counter(
            "robust.served_degraded")["stencil:oracle"] == 1

    def test_raise_policy_structures_synthetic(self):
        def boom():
            raise faults.FaultInjected("engine.scan", 3)

        with robust.failure_policy("raise"):
            with pytest.raises(guard.GuardedExecutionError) as ei:
                guard.run("cumsum", [("tuned", boom), ("oracle", lambda: 0)])
        assert ei.value.site == "engine.scan"
        assert ei.value.op == "cumsum"
        assert "engine.scan" in str(ei.value)

    def test_raise_policy_reraises_organic_unchanged(self):
        def bad():
            raise ValueError("ops.stencil: some validation message")

        with robust.failure_policy("raise"):
            with pytest.raises(ValueError,
                               match="some validation message"):
                guard.run("stencil", [("tuned", bad), ("oracle", lambda: 0)])

    def test_exhausted_prefers_last_organic_error(self):
        def synth():
            raise faults.FaultInjected("engine.window", 0)

        def organic():
            raise RuntimeError("the real lowering bug")

        with robust.failure_policy("fallback"):
            with pytest.raises(RuntimeError, match="the real lowering bug"):
                guard.run("op", [("tuned", synth), ("oracle", organic)])
        assert obs.metrics.counter_total("robust.exhausted") == 1

    def test_exhausted_all_synthetic_is_structured(self):
        def synth():
            raise faults.FaultInjected("engine.window", 0)

        with robust.failure_policy("fallback"):
            with pytest.raises(guard.GuardedExecutionError) as ei:
                guard.run("op", [("tuned", synth), ("default", synth)])
        assert ei.value.site == "engine.window"
        assert [lvl for lvl, _ in ei.value.failures] == ["tuned", "default"]

    def test_numerics_guard_demotes_nonfinite(self):
        nan = jnp.full((4,), jnp.nan)
        fine = jnp.zeros((4,))
        with robust.failure_policy("fallback"), robust.checking_numerics():
            out = guard.run("op", [("tuned", lambda: nan),
                                   ("oracle", lambda: fine)])
        np.testing.assert_array_equal(np.asarray(out), np.zeros(4))
        assert obs.metrics.counter_total("robust.nonfinite") == 1

    def test_numerics_guard_off_by_default(self):
        nan = jnp.full((4,), jnp.nan)
        with robust.failure_policy("fallback"):
            out = guard.run("op", [("tuned", lambda: nan)])
        assert np.isnan(np.asarray(out)).all()

    def test_empty_levels_rejected(self):
        with pytest.raises(ValueError, match="no execution levels"):
            guard.run("op", [])

    @pytest.mark.parametrize("env,want", [(None, "raise"),
                                          ("fallback", "fallback"),
                                          ("raise", "raise")])
    def test_default_policy_is_raise(self, monkeypatch, env, want):
        """With no session pin, a failing kernel raises unless the
        caller asks for 'fallback' by name."""
        from repro import config
        monkeypatch.setattr(config, "_ON_FAILURE", None)
        if env is None:
            monkeypatch.delenv(config.ON_FAILURE_ENV, raising=False)
        else:
            monkeypatch.setenv(config.ON_FAILURE_ENV, env)
        assert guard.on_failure() == want

    def test_default_policy_surfaces_kernel_failure(self, monkeypatch):
        from repro import config
        monkeypatch.setattr(config, "_ON_FAILURE", None)
        monkeypatch.delenv(config.ON_FAILURE_ENV, raising=False)

        def bad_kernel():
            raise RuntimeError("Mosaic refused the kernel")

        with pytest.raises(RuntimeError, match="Mosaic refused"):
            guard.run("stencil", [("tuned", bad_kernel),
                                  ("oracle", lambda: 0)])
        assert obs.metrics.counter_total("robust.demotion") == 0


# ---------------------------------------------------------------------------
# Per-site chaos matrix over the real ops surfaces
# ---------------------------------------------------------------------------

# site → (engine thunk, oracle thunk). Keep in sync with faults.SITES:
# the registry-coverage test below fails when a site is added without a
# matrix entry (tuning/sidecar/serve sites have their own classes).
_X = (48, 128)
_ENGINE_MATRIX = {
    "engine.window": (
        lambda: ops.stencil(_x2d(_X), "2d5pt", impl="interpret"),
        lambda: ops.stencil(_x2d(_X), "2d5pt", impl="xla"),
    ),
    "engine.gpu.window": (
        lambda: ops.stencil(_x2d(_X), "2d5pt", impl="interpret",
                            backend="gpu"),
        lambda: ops.stencil(_x2d(_X), "2d5pt", impl="xla"),
    ),
    "engine.scan": (
        lambda: ops.cumsum(_x2d(_X), impl="interpret"),
        lambda: ops.cumsum(_x2d(_X), impl="xla"),
    ),
    "engine.gpu.scan": (
        lambda: ops.cumsum(_x2d(_X), impl="interpret", backend="gpu"),
        lambda: ops.cumsum(_x2d(_X), impl="xla"),
    ),
}


class TestChaosMatrix:
    def test_every_site_is_covered(self):
        covered = set(_ENGINE_MATRIX) | {
            "tuning.measure", "tuning.sidecar.load", "tuning.sidecar.save",
            "halo.exchange", "serve.step"}
        assert covered == set(faults.SITES)

    @pytest.mark.parametrize("site", sorted(_ENGINE_MATRIX))
    def test_raise_names_site(self, site):
        run, _ = _ENGINE_MATRIX[site]
        with robust.inject(f"{site}:1.0"), robust.failure_policy("raise"):
            with pytest.raises(guard.GuardedExecutionError) as ei:
                run()
        assert ei.value.site == site

    @pytest.mark.parametrize("site", sorted(_ENGINE_MATRIX))
    def test_fallback_serves_oracle_equal(self, site):
        run, oracle = _ENGINE_MATRIX[site]
        want = oracle()
        with robust.inject(f"{site}:1.0"), robust.failure_policy("fallback"):
            got = run()
            fired = faults.fired_counts()      # inject() restores on exit
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        assert obs.metrics.counter_total("robust.demotion") >= 1
        assert fired.get(site, 0) >= 1

    @pytest.mark.parametrize("site", sorted(_ENGINE_MATRIX))
    def test_off_means_off(self, site):
        run, oracle = _ENGINE_MATRIX[site]
        got = run()
        np.testing.assert_allclose(np.asarray(got), np.asarray(oracle()),
                                   rtol=1e-4, atol=1e-4)
        assert faults.fired_counts() == {}
        assert obs.metrics.counter_total("robust.demotion") == 0

    def test_halo_exchange_fallback_desharding(self):
        """halo.exchange down on a 1-device mesh: the guard deshards
        (boundary='zero' makes that exact) and the answer survives."""
        from repro.launch.mesh import make_domain_mesh
        mesh = make_domain_mesh((1,))
        x = _x2d(_X)
        want = ops.stencil(x, "2d5pt", impl="interpret")
        with robust.inject("halo.exchange:1.0"), \
                robust.failure_policy("fallback"):
            got = ops.stencil(x, "2d5pt", impl="interpret", mesh=mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert obs.metrics.counter_total("robust.demotion") >= 1

    def test_halo_exchange_raise(self):
        from repro.launch.mesh import make_domain_mesh
        mesh = make_domain_mesh((1,))
        with robust.inject("halo.exchange:1.0"), \
                robust.failure_policy("raise"):
            with pytest.raises(guard.GuardedExecutionError) as ei:
                ops.stencil(_x2d(_X), "2d5pt", impl="interpret", mesh=mesh)
        assert ei.value.site == "halo.exchange"


class TestChaosAcceptanceSweep:
    """Every site armed at prob 1.0 under 'fallback': the whole surface
    stays reference-equal (fp32) on both engine backends — the PR-10
    acceptance gate. Engine levels fail fast at their dispatch checks
    (before any pallas lowering), so only the XLA oracle computes."""

    @pytest.mark.parametrize("backend", ["tpu", "gpu"])
    def test_table3_zoo_reference_equal(self, backend):
        x2, x3 = _x2d(), _x2d((10, 16, 128), seed=1)
        with robust.inject("all:1.0"), robust.failure_policy("fallback"):
            for name, sdef in sorted(BENCHMARKS.items()):
                x = x2 if sdef.ndim == 2 else x3
                got = ops.stencil(x, name, impl="interpret", backend=backend)
                want = ops.stencil(x, name, impl="xla")
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want),
                    rtol=1e-4, atol=1e-4, err_msg=f"{name}/{backend}")
            fired = faults.fired_counts()      # inject() restores on exit
        assert obs.metrics.counter_total("robust.demotion") > 0
        # run_window_plan is the common dispatcher for both backends, so
        # with every site armed its check is always the first to fire
        assert fired.get("engine.window", 0) > 0

    @pytest.mark.parametrize("backend", ["tpu", "gpu"])
    def test_conv_pipeline_scans_reference_equal(self, backend):
        rng = np.random.default_rng(5)
        x = _x2d()
        xc = jnp.asarray(rng.standard_normal((2, 3, 24, 64))
                         .astype(np.float32))
        w = jnp.asarray(rng.standard_normal((4, 3, 3, 3)).astype(np.float32))
        a = jnp.asarray(rng.uniform(0.4, 0.9, (8, 256)).astype(np.float32))
        b = jnp.asarray(rng.standard_normal((8, 256)).astype(np.float32))
        with robust.inject("all:1.0"), robust.failure_policy("fallback"):
            np.testing.assert_allclose(
                np.asarray(ops.conv2d(xc, w, impl="interpret",
                                      backend=backend)),
                np.asarray(ops.conv2d(xc, w, impl="xla")),
                rtol=1e-4, atol=1e-4, err_msg="conv2d")
            np.testing.assert_allclose(
                np.asarray(ops.pipeline(x, ["2d5pt", "2d9pt"],
                                        impl="interpret", backend=backend)),
                np.asarray(ops.pipeline(x, ["2d5pt", "2d9pt"], impl="xla")),
                rtol=1e-4, atol=1e-4, err_msg="pipeline")
            for impl in ("engine", "engine_unchunked"):
                np.testing.assert_allclose(
                    np.asarray(ops.chunked_linear_recurrence(
                        a, b, chunk=64, impl=impl, backend=backend)),
                    np.asarray(ref.linear_recurrence(a, b)),
                    rtol=1e-4, atol=1e-4, err_msg=impl)
            np.testing.assert_allclose(
                np.asarray(ops.linear_recurrence(a, b, impl="interpret",
                                                 backend=backend)),
                np.asarray(ref.linear_recurrence(a, b)),
                rtol=1e-4, atol=1e-4, err_msg="linear_recurrence")
        assert obs.metrics.counter_total("robust.demotion") > 0


# ---------------------------------------------------------------------------
# Tuner hardening (§16.4)
# ---------------------------------------------------------------------------

class TestTunerHardening:
    def test_measure_us_rejects_nonfinite_output(self):
        with pytest.raises(guard.MeasurementError, match="non-finite"):
            tuning.measure_us(lambda: jnp.full((4,), jnp.nan), reps=1)

    def test_measure_candidate_retries_then_succeeds(self):
        calls = []

        def runner(cfg):
            calls.append(cfg)
            if len(calls) == 1:
                raise RuntimeError("transient")
            return tuning.Measurement(10.0, 0.0, 3)

        cfg = tuning.KernelConfig((8, 128))
        with robust.failure_policy("fallback"):
            us = tuning._measure_candidate(runner, cfg, backend="tpu",
                                           retries=2)
        assert float(us) == 10.0 and len(calls) == 2
        assert obs.metrics.counter_total("tuner.measure_retry") == 1

    def test_measure_candidate_quarantines_after_retries(self):
        def runner(cfg):
            raise RuntimeError("persistent")

        with robust.failure_policy("fallback"):
            out = tuning._measure_candidate(runner, tuning.KernelConfig((8, 128)),
                                            backend="tpu", retries=1)
        assert out is None
        assert obs.metrics.counter_total("tuner.quarantined") == 1
        assert obs.metrics.counter_total("tuner.measure_retry") == 2

    def test_measure_candidate_rejects_nonfinite_float(self):
        with robust.failure_policy("fallback"):
            out = tuning._measure_candidate(
                lambda cfg: float("nan"), tuning.KernelConfig((8, 128)),
                backend="tpu", retries=0)
        assert out is None
        assert obs.metrics.counter_total("tuner.measure_nonfinite") == 1

    def test_outlier_spread_remeasured(self):
        seen = []

        def runner(cfg):
            seen.append(1)
            if len(seen) == 1:       # IQR > half the median: noisy sample
                return tuning.Measurement(10.0, 9.0, 3)
            return tuning.Measurement(10.0, 0.1, 3)

        with robust.failure_policy("fallback"):
            us = tuning._measure_candidate(runner, tuning.KernelConfig((8, 128)),
                                           backend="tpu", retries=2)
        assert len(seen) == 2 and us.spread_us == 0.1
        assert obs.metrics.counter_total("tuner.measure_outlier") == 1

    def test_injected_measure_fault_raise_policy(self):
        with robust.inject("tuning.measure:1.0"), \
                robust.failure_policy("raise"):
            with pytest.raises(guard.GuardedExecutionError) as ei:
                tuning._measure_candidate(
                    lambda cfg: tuning.measure_us(lambda: jnp.zeros(4)),
                    tuning.KernelConfig((8, 128)), backend="tpu")
        assert ei.value.site == "tuning.measure"

    def test_all_quarantined_falls_back_to_model_ranking(self):
        from repro.core.plan import scan_plan
        plan = scan_plan(128)

        def runner(cfg):
            raise RuntimeError("measurement rig is down")

        with robust.failure_policy("fallback"):
            res = tuning.autotune(plan, (32, 256), runner=runner)
        assert res.source == "model_fallback"
        assert res.measured_us is None
        assert obs.metrics.counter_total("tuner.model_fallback") == 1
        # the model-ranked pick is cached, not persisted as a winner
        assert tuning.sidecar_entries() == {}

    def test_tuning_budget_skips_tail_not_head(self, monkeypatch):
        from repro.core.plan import scan_plan
        monkeypatch.setenv(tuning.TUNE_BUDGET_ENV, "1e-9")
        measured = []

        def runner(cfg):
            measured.append(cfg)
            return tuning.Measurement(5.0, 0.0, 3)

        with robust.failure_policy("fallback"):
            res = tuning.autotune(scan_plan(128), (32, 256), runner=runner)
        assert res.source == "measured"      # first candidate always measured
        assert len(measured) == 1
        assert obs.metrics.counter_total("tuner.budget_skipped") >= 1

    def test_sidecar_entry_crc_roundtrip_and_tamper(self):
        tuning.clear_sidecar()
        key = tuning._sidecar_key("sig-crc", (32, 256), 1, (), "auto", "tpu")
        tuning._SIDECAR[key] = (tuning.KernelConfig((16, 256)), 1.5, 42.0)
        entries = tuning.sidecar_entries()
        assert entries[key]["crc"] == tuning.entry_crc(entries[key])
        tuning.clear_sidecar()
        assert tuning.merge_sidecar_entries(entries) == 1
        tuning.clear_sidecar()
        tampered = json.loads(json.dumps(entries))
        tampered[key]["block"] = [8, 128]      # flip the winner, keep crc
        assert tuning.merge_sidecar_entries(tampered) == 0
        assert obs.metrics.counter_total("tuner.sidecar_corrupt_entry") == 1
        tuning.clear_sidecar()

    def test_corrupt_sidecar_file_quarantined(self, tmp_path):
        path = tmp_path / "tuning.json"
        path.write_text("{ this is not json")
        with robust.failure_policy("fallback"):
            assert tuning.load_sidecar(str(path)) == 0
        assert not path.exists()
        assert (tmp_path / "tuning.json.corrupt").exists()
        assert obs.metrics.counter_total("tuner.sidecar_quarantined") == 1

    def test_corrupt_sidecar_file_raise_policy(self, tmp_path):
        path = tmp_path / "tuning.json"
        path.write_text("[]")                  # parses, wrong shape
        with robust.failure_policy("raise"):
            with pytest.raises(guard.SidecarError,
                               match="tuning.sidecar.load"):
                tuning.load_sidecar(str(path))
        assert path.exists()                   # raise mode never renames

    def test_corrupt_entry_skipped_file_survives(self, tmp_path):
        tuning.clear_sidecar()
        key = tuning._sidecar_key("sig-ok", (32, 256), 1, (), "auto", "tpu")
        tuning._SIDECAR[key] = (tuning.KernelConfig((16, 256)), 1.0, 10.0)
        entries = tuning.sidecar_entries()
        bad = dict(entries)
        bad["garbage-key"] = {"block": 123,
                              "schema": tuning.ENGINE_SCHEMA_VERSION}
        path = tmp_path / "tuning.json"
        path.write_text(json.dumps({"version": 1, "entries": bad}))
        tuning.clear_sidecar()
        with robust.failure_policy("fallback"):
            assert tuning.load_sidecar(str(path)) == 1
        assert path.exists()                   # per-entry skip, no rename
        assert obs.metrics.counter_total("tuner.sidecar_corrupt_entry") == 1
        tuning.clear_sidecar()

    def test_sidecar_load_fault_quarantines(self, tmp_path):
        path = tmp_path / "tuning.json"
        path.write_text(json.dumps({"version": 1, "entries": {}}))
        with robust.inject("tuning.sidecar.load:1.0"), \
                robust.failure_policy("fallback"):
            assert tuning.load_sidecar(str(path)) == 0
        assert (tmp_path / "tuning.json.corrupt").exists()

    def test_sidecar_save_fault_both_policies(self, tmp_path):
        tuning.clear_sidecar()
        key = tuning._sidecar_key("sig-save", (32, 256), 1, (), "auto", "tpu")
        tuning._SIDECAR[key] = (tuning.KernelConfig((16, 256)), 1.0, 10.0)
        path = str(tmp_path / "tuning.json")
        with robust.inject("tuning.sidecar.save:1.0"):
            with robust.failure_policy("raise"):
                with pytest.raises(guard.SidecarError,
                                   match="tuning.sidecar.save"):
                    tuning.save_sidecar(path)
            with robust.failure_policy("fallback"):
                assert tuning.save_sidecar(path) is None
        assert obs.metrics.counter_total("tuner.sidecar_save_failed") == 1
        assert not os.path.exists(path)
        # faults gone: the very same store saves cleanly (data never lost)
        assert tuning.save_sidecar(path) == path
        assert len(json.load(open(path))["entries"]) == 1
        tuning.clear_sidecar()


# ---------------------------------------------------------------------------
# Serving hardening
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_model():
    from repro.config import get_config
    from repro.models import build_model
    from repro.nn.spec import init_params

    cfg = get_config("gemma3_1b", smoke=True)
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    return cfg, model, params


def _mk_requests(cfg, n, max_new=4, seed=0):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab, 4, dtype=np.int32), max_new)
            for i in range(n)]


class TestServeChaos:
    def test_step_fault_raise_policy(self, served_model):
        from repro.launch.serve import DecodeServer
        cfg, model, params = served_model
        srv = DecodeServer(model, params, slots=2, cache_len=32)
        with robust.inject("serve.step:1.0"), robust.failure_policy("raise"):
            with pytest.raises(guard.GuardedExecutionError) as ei:
                srv.run(_mk_requests(cfg, 1))
        assert ei.value.site == "serve.step"

    def test_poisoned_steps_shed_load_not_hang(self, served_model):
        """p=1.0: every request still comes back ``done`` with ``.error``
        set — the pre-hardening server looped forever here."""
        from repro.launch.serve import DecodeServer
        cfg, model, params = served_model
        srv = DecodeServer(model, params, slots=2, cache_len=32)
        with robust.inject("serve.step:1.0"), \
                robust.failure_policy("fallback"):
            done = srv.run(_mk_requests(cfg, 3))
        assert len(done) == 3
        assert all(r.done and r.error == "step_failure" for r in done)
        health = srv.health()
        assert health["step_failures"] > 0 and health["active_slots"] == 0
        assert obs.metrics.counter_total("serve.request_error") == 3

    def test_transient_faults_still_complete(self, served_model):
        from repro.launch.serve import DecodeServer
        cfg, model, params = served_model
        srv = DecodeServer(model, params, slots=2, cache_len=32)
        with robust.inject("serve.step:0.3:7"), \
                robust.failure_policy("fallback"):
            done = srv.run(_mk_requests(cfg, 4))
        assert len(done) == 4
        assert all(r.error is None and len(r.out) == 4 for r in done)
        assert srv.step_failures > 0          # faults really did fire

    def test_deadline_evicts(self, served_model):
        from repro.launch.serve import DecodeServer
        cfg, model, params = served_model
        srv = DecodeServer(model, params, slots=1, cache_len=32)
        [timed_out] = _mk_requests(cfg, 1)
        timed_out.deadline_s = 0.0
        [done] = srv.run([timed_out])
        assert done.done and done.error == "deadline"
        assert obs.metrics.counter_total("serve.deadline_exceeded") == 1

    def test_chaos_outputs_match_clean_run(self, served_model):
        """Greedy tokens are invariant under transient step faults: a
        failed step never advances slot state, so the retried step
        reproduces the clean trajectory exactly."""
        from repro.launch.serve import DecodeServer
        cfg, model, params = served_model
        clean = DecodeServer(model, params, slots=2, cache_len=32)
        want = {r.rid: r.out for r in clean.run(_mk_requests(cfg, 3))}
        chaotic = DecodeServer(model, params, slots=2, cache_len=32)
        with robust.inject("serve.step:0.3:7"), \
                robust.failure_policy("fallback"):
            done = chaotic.run(_mk_requests(cfg, 3))
        assert {r.rid: r.out for r in done} == want


# ---------------------------------------------------------------------------
# Zero overhead when off
# ---------------------------------------------------------------------------

class TestOffPathOverhead:
    def test_no_faults_no_robust_counters(self):
        out = ops.stencil(_x2d(), "2d5pt", impl="interpret")
        assert np.isfinite(np.asarray(out)).all()
        assert faults.fired_counts() == {}
        for name in ("robust.demotion", "robust.served_degraded",
                     "robust.exhausted", "robust.nonfinite"):
            assert obs.metrics.counter_total(name) == 0

    def test_guard_run_overhead_bounded(self):
        """The guard's happy path is one try around the primary thunk —
        bound it loosely (50 µs/call) against real regressions (config
        import per call, policy read before success, level prebuild)."""
        levels = [("tuned", lambda: 1)]
        n = 20_000
        guard.run("warm", levels)
        t0 = time.perf_counter()
        for _ in range(n):
            guard.run("hot", levels)
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 50e-6, f"{per_call * 1e6:.2f} µs per guarded call"
