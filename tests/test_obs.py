"""Observability layer (DESIGN.md §15): tracer, metrics, drift.

Covers:
- disabled-mode fast path: ``span()`` returns the shared no-op, a
  profiler session records no span, and the per-span overhead is
  bounded;
- spans on the profiler's clock: ``obs.tracing(dir)`` writes a
  ``jax.profiler`` trace whose host plane holds the spans, nested, with
  their attributes as stats;
- engine counters match known launch counts (eager launches vs
  per-compile lowerings; a jitted program launches nothing itself);
- drift recorder math (geomean ratios, backend pooling, worst-cell
  ranking) and the report CLI;
- serve latency histograms (p50/p99 in ``metrics.snapshot()``);
- ``measure_us`` spread + ``$REPRO_MEASURE_REPS`` and the v7 sidecar
  schema (spread persisted, stale v6 entries dropped on load).
"""
import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import tuning
from repro.kernels import ops


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts (and leaves) with telemetry off and empty."""
    obs.trace.disable()
    obs.metrics.reset()
    obs.drift.reset()
    yield
    obs.trace.disable()
    obs.metrics.reset()
    obs.drift.reset()


def _host_events(log_dir) -> dict:
    """``{name: [(start_ns, end_ns, stats), ...]}`` of the host events of
    the profiler trace written under ``log_dir``."""
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                out.setdefault(ev.name, []).append(
                    (s, s + int(ev.duration_ns), dict(ev.stats)))
    return out


class TestTracerDisabled:
    def test_span_is_shared_noop(self):
        assert obs.span("anything", key="val") is obs.trace.NULL
        assert obs.span("other") is obs.trace.NULL

    def test_no_events_collected(self, tmp_path):
        """A profiler session open while spans are off records none."""
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("a"):
                with obs.span("b"):
                    obs.trace.annotate(k=1)
        finally:
            jax.profiler.stop_trace()
        evs = _host_events(tmp_path)
        assert not {"a", "b", "annotate"} & set(evs)

    def test_disabled_overhead_bounded(self):
        """The no-op path is a function call + a bool read — bound it
        loosely (100 µs/span) so only a real regression (event append,
        clock read, allocation per call) can trip it on a noisy host."""
        n = 10_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("hot", a=1):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 100e-6, f"{per_span * 1e6:.2f} µs per no-op span"

    def test_traced_decorator_passthrough(self, monkeypatch):
        calls = []

        def no_annotation(*a):
            raise AssertionError("a disabled span made a TraceAnnotation")
        monkeypatch.setattr(obs.trace, "_annotation", no_annotation)

        @obs.trace.traced("deco")
        def fn(v):
            calls.append(v)
            return v + 1

        assert fn(1) == 2
        assert calls == [1]


class TestTracerEnabled:
    def test_nesting_and_parent_attribution(self, tmp_path):
        """The profiler nests spans by time on the thread's line."""
        with obs.tracing(tmp_path):
            with obs.span("outer"):
                with obs.span("inner"):
                    obs.trace.annotate(demoted="a->b")
        evs = _host_events(tmp_path)
        ((o0, o1, _),) = evs["outer"]
        ((i0, i1, _),) = evs["inner"]
        ((a0, a1, stats),) = evs["annotate"]
        assert o0 <= i0 <= a0 <= a1 <= i1 <= o1
        assert stats["demoted"] == "a->b"

    def test_chrome_trace_export_shape(self, tmp_path):
        """``obs.tracing(dir)`` writes the profiler's own trace (the
        ``.xplane.pb`` TensorBoard, Perfetto and ``bench/trace.py``
        read); a span's category and attributes are its event's stats."""
        with obs.tracing(tmp_path):
            with obs.span("work", cat="test", detail="x", n=3):
                pass
        assert not list(tmp_path.glob("*.json"))
        ((s, e, stats),) = _host_events(tmp_path)["work"]
        assert e >= s
        assert stats == {"cat": "test", "detail": "x", "n": 3}

    def test_env_dir_records_a_profile(self, tmp_path):
        """``REPRO_TRACE=<dir>``: a profiler trace into ``dir`` from the
        first span to the end of the process."""
        import os
        import subprocess
        import sys

        code = ("import jax.numpy as jnp\n"
                "from repro.kernels import ops\n"
                "ops.cumsum(jnp.ones((8, 256)), impl='interpret')\n")
        src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
        env = dict(os.environ, REPRO_TRACE=str(tmp_path), JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join(
                       [src, os.environ.get("PYTHONPATH", "")]))
        subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       timeout=120)
        evs = _host_events(tmp_path)
        assert "engine.run_scan_plan" in evs and "engine.lower" in evs

    def test_tracing_restores_prior_state(self):
        assert not obs.trace.enabled()
        with obs.tracing():
            assert obs.trace.enabled()
        assert not obs.trace.enabled()


HLO = """HloModule m

%fused (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  ROOT %neg = f32[16]{0} negate(%p), metadata={op_name="jit(f)/halo.frame/neg"}
}

ENTRY %main (x.1: f32[8]) -> f32[16] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %c = f32[] constant(0), metadata={op_name="jit(f)"}
  %copy-start = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%x.1)
  %copy-done = f32[8]{0:S(1)} copy-done(%copy-start)
  %pad.2 = f32[16]{0} pad(%copy-done, %c), padding=0_8, metadata={op_name="jit(f)/halo.interior/jit(g)/engine.pad/pad"}
  %fusion.3 = f32[16]{0} fusion(%pad.2), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/halo.exchange/concatenate" stack_frame_id=3}
  %copy-start.1 = (f32[16]{0}, f32[16]{0}, u32[]{:S(2)}) copy-start(%fusion.3)
  ROOT %copy-done.1 = f32[16]{0} copy-done(%copy-start.1)
}
"""


class TestScopes:
    def test_instruction_layers(self):
        """Own op_names as written; an inserted copy takes its operand's
        path, or its user's where the operand is a parameter."""
        paths = obs.scopes.instruction_layers(HLO)
        pad = "jit(f)/halo.interior/jit(g)/engine.pad/pad"
        concat = "jit(f)/halo.exchange/concatenate"
        assert paths == {
            "neg": "jit(f)/halo.frame/neg", "x.1": "x", "c": "jit(f)",
            "copy-start": pad, "copy-done": pad, "pad.2": pad,
            "fusion.3": concat, "copy-start.1": concat,
            "copy-done.1": concat}

    def test_layer_of_innermost_scope(self):
        path = "jit(f)/halo.interior/jit(g)/engine.pad/pad"
        assert obs.scopes.layer_of(path, "engine.") == "engine.pad"
        assert obs.scopes.layer_of(path, "halo.") == "halo.interior"
        assert obs.scopes.layer_of(
            "jit(f)/transpose(jvp(engine.crop))/slice",
            "engine.") == "engine.crop"
        assert obs.scopes.layer_of("x", "engine.") is None
        assert obs.scopes.layer_of(None, "halo.") is None


class TestMetrics:
    def test_counter_labels_and_total(self):
        obs.metrics.inc("t.c")
        obs.metrics.inc("t.c", "a", 2)
        snap = obs.metrics.snapshot()["counters"]["t.c"]
        assert snap["total"] == 3
        assert snap["by_label"] == {"": 1, "a": 2}
        assert obs.metrics.counter_total("t.c") == 3
        assert obs.metrics.counter_total("never.touched") == 0

    def test_reset_clears_in_place(self):
        c = obs.metrics.counter("t.alias")
        c["k"] += 5
        obs.metrics.reset()
        assert obs.metrics.counter("t.alias") is c     # same object
        assert c.total_count() == 0

    def test_histogram_percentiles(self):
        for v in range(1, 101):
            obs.metrics.observe("t.h", float(v))
        h = obs.metrics.snapshot()["histograms"]["t.h"]
        assert h["count"] == 100 and h["min"] == 1 and h["max"] == 100
        assert 49 <= h["p50"] <= 52
        assert 98 <= h["p99"] <= 100

    def test_backward_lowerings_is_registry_counter(self):
        from repro.core import adjoint
        adjoint.reset_lowering_counts()
        adjoint.record_lowering("adj_test")
        assert adjoint.BACKWARD_LOWERINGS["adj_test"] == 1
        snap = obs.metrics.snapshot()["counters"]
        assert snap["adjoint.backward_lowerings"]["by_label"]["adj_test"] == 1
        obs.metrics.reset()
        assert adjoint.BACKWARD_LOWERINGS["adj_test"] == 0   # alias stays live


class TestEngineCounters:
    def test_launch_count_matches_calls(self):
        x = jnp.ones((8, 256), jnp.float32)
        base = obs.metrics.counter_total("engine.launch")
        for _ in range(3):
            ops.cumsum(x, impl="interpret")
        assert obs.metrics.counter_total("engine.launch") == base + 3
        assert obs.metrics.counter("engine.launch")["tpu:add"] >= 3

    def test_lowering_counts_compiles_not_calls(self):
        x = jnp.ones((8, 320), jnp.float32)      # unique shape → fresh compile
        c = obs.metrics.counter("engine.lowering")
        before = dict(c)
        ops.cumsum(x, impl="interpret")
        ops.cumsum(x, impl="interpret")          # second call: jit cache hit
        delta = c["tpu:scan"] - before.get("tpu:scan", 0)
        assert delta == 1, f"expected 1 compile, counted {delta}"

    def test_jitted_calls_launch_nothing(self):
        """Under jit the dispatcher runs at trace time: one lowering, and
        no launch counted for the five calls of the compiled program."""
        x = jnp.ones((8, 448), jnp.float32)      # unique shape → fresh compile
        f = jax.jit(lambda v: ops.cumsum(v, impl="interpret"))
        for _ in range(5):
            f(x).block_until_ready()
        assert obs.metrics.counter_total("engine.launch") == 0
        assert obs.metrics.counter_total("engine.lowering") == 1

    def test_engine_spans_when_tracing(self, tmp_path):
        x = jnp.ones((8, 384), jnp.float32)
        with obs.tracing(tmp_path):
            ops.cumsum(x, impl="interpret")
            ops.cumsum(x, impl="interpret")
        evs = _host_events(tmp_path)
        runs = sorted(evs["engine.run_scan_plan"])
        assert len(runs) == 2                             # per call
        ((l0, l1, low),) = evs["engine.lower"]            # per compile
        assert runs[0][0] <= l0 <= l1 <= runs[0][1]       # nested in call 1
        assert low["cat"] == "engine" and low["backend"] == "tpu"
        assert low["plan"].startswith("scan-")
        assert low["model_cost"] > 0

    def test_backward_spans_when_tracing(self, tmp_path):
        x = jnp.ones((8, 256), jnp.float32)
        with obs.tracing(tmp_path):
            jax.grad(lambda v: ops.cumsum(v, impl="interpret").sum())(x)
        assert "ops.cumsum_bwd" in _host_events(tmp_path)


class TestDrift:
    def test_record_and_geomean(self):
        # two samples with ratios 2.0 and 8.0 → geomean 4.0
        obs.drift.record("sig-a", "tpu", "lanes", 10.0, 20.0)
        obs.drift.record("sig-a", "tpu", "lanes", 10.0, 80.0)
        (row,) = obs.drift.report()
        assert row["n"] == 2
        assert row["ratio_us_per_cyc"] == pytest.approx(4.0)
        assert row["min_ratio"] == pytest.approx(2.0)
        assert row["max_ratio"] == pytest.approx(8.0)
        # only cell of its backend → drift 1.0 against its own pool
        assert row["drift"] == pytest.approx(1.0)
        # log-space spread: exp(std([log2, log8])) = exp(log2) = 2
        assert row["spread_geo"] == pytest.approx(2.0, rel=1e-6)

    def test_backend_pooling_and_ranking(self):
        obs.drift.record("sig-a", "tpu", "lanes", 1.0, 4.0)    # ratio 4
        obs.drift.record("sig-b", "tpu", "lanes", 1.0, 1.0)    # ratio 1
        obs.drift.record("sig-c", "gpu", "lanes", 1.0, 7.0)
        rows = obs.drift.report()
        tpu = [r for r in rows if r["backend"] == "tpu"]
        assert all(r["backend_ratio"] == pytest.approx(2.0) for r in tpu)
        drifts = sorted(r["drift"] for r in tpu)
        assert drifts == [pytest.approx(0.5), pytest.approx(2.0)]
        # both tpu cells drift equally in |log|; the gpu cell not at all
        agg = obs.drift.aggregate()
        assert agg["gpu"]["max_drift"] == pytest.approx(1.0)
        assert agg["tpu"]["cells"] == 2 and agg["tpu"]["samples"] == 2
        assert agg["tpu"]["worst_signature"] in ("sig-a", "sig-b")

    def test_state_roundtrip_merge(self):
        obs.drift.record("sig-a", "tpu", "lanes", 1.0, 2.0, shape=(8, 128))
        doc = obs.drift.state()
        obs.drift.reset()
        assert obs.drift.report() == []
        assert obs.drift.load_state(doc) == 1
        obs.drift.record("sig-a", "tpu", "lanes", 1.0, 2.0)
        (row,) = obs.drift.report()
        assert row["n"] == 2 and row["last_shape"] == [8, 128]

    def test_ignores_nonpositive(self):
        obs.drift.record("s", "tpu", None, 0.0, 5.0)
        obs.drift.record("s", "tpu", None, 5.0, 0.0)
        assert obs.drift.report() == []

    def test_autotune_records_drift(self):
        tuning.clear_cache()
        x = jnp.ones((32, 256), jnp.float32)
        from repro.kernels import ssam_stencil2d
        from repro.kernels.stencils import BENCHMARKS
        sdef = BENCHMARKS["2d5pt"]
        plan = ssam_stencil2d.plan_for(sdef)
        runner = lambda cfg: tuning.measure_us(
            lambda: ops.stencil(x, sdef, impl="interpret",
                                **cfg.as_kwargs(plan)), reps=1)
        tuning.autotune(plan, x.shape, time_steps=1,
                        default=tuning.KernelConfig((8, 128)), runner=runner,
                        context=("test_obs_drift",))
        rows = obs.drift.report()
        assert rows, "measured autotune pass must feed the drift recorder"
        assert all(r["ratio_us_per_cyc"] > 0 for r in rows)
        assert {r["signature"] for r in rows} == {
            tuning.plan_signature(plan)}

    def test_report_cli_renders(self, tmp_path, capsys):
        from repro.obs import report
        obs.drift.record("sig-x", "tpu", "lanes", 1.0, 3.0, shape=(4, 128))
        path = tmp_path / "metrics.json"
        obs.metrics.export(str(path))
        assert report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "sig-x" in out and "backend" in out
        assert "[tpu]" in out

    def test_report_empty(self, capsys):
        from repro.obs import report
        assert report.main(["--live"]) == 0
        assert "no model-vs-measured samples" in capsys.readouterr().out


class TestMeasureUs:
    def test_measurement_carries_spread(self):
        m = tuning.measure_us(lambda: jnp.zeros(8), reps=5)
        assert isinstance(m, float)
        assert m > 0 and m.reps == 5
        assert m.spread_us >= 0.0

    def test_reps_env_override(self, monkeypatch):
        monkeypatch.setenv(tuning.MEASURE_REPS_ENV, "7")
        m = tuning.measure_us(lambda: jnp.zeros(8))
        assert m.reps == 7
        monkeypatch.setenv(tuning.MEASURE_REPS_ENV, "not-a-number")
        assert tuning.measure_us(lambda: jnp.zeros(8)).reps == 3

    def test_plain_float_runner_still_legal(self):
        """Monkeypatched measure_us stand-ins return bare floats
        (test_sharded does); spread access must degrade, not crash."""
        us = 17.0
        assert getattr(us, "spread_us", None) is None


class TestSidecarV7:
    def test_spread_persisted_roundtrip(self, tmp_path):
        tuning.clear_cache()
        tuning.clear_sidecar()
        key = tuning._sidecar_key("sig-v7", (32, 256), 1, (), "auto", "tpu")
        tuning._SIDECAR[key] = (tuning.KernelConfig((8, 128)), 1.5, 42.0)
        tuning._SIDECAR_SPREAD[key] = 3.25
        path = tmp_path / "sidecar.json"
        tuning.save_sidecar(str(path))
        doc = json.loads(path.read_text())
        (entry,) = doc["entries"].values()
        assert entry["schema"] == tuning.ENGINE_SCHEMA_VERSION == 7
        assert entry["spread_us"] == 3.25
        tuning.clear_sidecar()
        assert tuning.load_sidecar(str(path)) == 1
        assert tuning._SIDECAR_SPREAD[key] == 3.25
        tuning.clear_sidecar()

    def test_stale_v6_dropped_on_load(self, tmp_path):
        tuning.clear_sidecar()
        path = tmp_path / "sidecar.json"
        path.write_text(json.dumps({"version": 1, "entries": {
            "stale-key": {"block": [8, 128], "variant": "shift_psum",
                          "strategy": None, "model_cost": 1.0,
                          "measured_us": 5.0, "schema": 6},
        }}))
        assert tuning.load_sidecar(str(path)) == 0
        assert "stale-key" not in tuning._SIDECAR
        assert obs.metrics.counter_total("tuner.sidecar_stale") == 1

    def test_checkpoint_entries_carry_spread(self):
        tuning.clear_sidecar()
        key = tuning._sidecar_key("sig-ck", (8, 128), 1, (), "auto", "tpu")
        tuning._SIDECAR[key] = (tuning.KernelConfig((8, 128)), 1.0, 9.0)
        tuning._SIDECAR_SPREAD[key] = 0.5
        entries = tuning.sidecar_entries()
        assert entries[key]["spread_us"] == 0.5
        tuning.clear_sidecar()
        assert tuning.merge_sidecar_entries(entries) == 1
        assert tuning._SIDECAR_SPREAD[key] == 0.5
        tuning.clear_sidecar()


class TestTunerCounters:
    def test_hit_miss_seed_accounting(self):
        tuning.clear_cache()
        tuning.clear_sidecar()
        obs.metrics.reset()
        from repro.kernels import ssam_stencil2d
        from repro.kernels.stencils import BENCHMARKS
        plan = ssam_stencil2d.plan_for(BENCHMARKS["2d5pt"])
        ctx = ("test_obs_tuner",)
        tuning.autotune(plan, (32, 256), context=ctx)       # model-only: miss
        assert obs.metrics.counter_total("tuner.sidecar_miss") == 1
        tuning.autotune(plan, (32, 256), context=ctx)       # replay: cache hit
        assert obs.metrics.counter_total("tuner.cache_hit") == 1
        assert obs.metrics.counter_total("tuner.sidecar_hit") == 0


class TestServeHistograms:
    @pytest.fixture(scope="class")
    def server_metrics(self):
        from repro.config import get_config
        from repro.launch.serve import DecodeServer, Request
        from repro.models import build_model
        from repro.nn.spec import init_params

        obs.metrics.reset()
        cfg = get_config("gemma3_1b", smoke=True)
        model = build_model(cfg)
        params = init_params(model.specs(), jax.random.PRNGKey(0))
        server = DecodeServer(model, params, slots=2, cache_len=32)
        rng = np.random.default_rng(0)
        reqs = [Request(i, rng.integers(0, cfg.vocab, 4, dtype=np.int32), 3)
                for i in range(3)]
        done = server.run(reqs)
        return len(done), obs.metrics.snapshot()

    def test_request_latency_p50_p99(self, server_metrics):
        n_done, snap = server_metrics
        h = snap["histograms"]["serve.request_us"]
        assert h["count"] == n_done == 3
        assert 0 < h["p50"] <= h["p99"] <= h["max"]
        assert h["min"] > 0
        assert snap["counters"]["serve.requests"]["total"] == n_done

    def test_step_latency_histogram(self, server_metrics):
        _, snap = server_metrics
        h = snap["histograms"]["serve.step_us"]
        assert h["count"] >= 3                 # ≥ tokens decoded per request
        assert 0 < h["p50"] <= h["max"]
