"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the repo convention.

| function                  | paper analogue | what is measured here            |
|---------------------------|----------------|----------------------------------|
| bench_conv2d_filter_sweep | Fig. 4         | CPU wall-time: XLA direct conv vs SSAM systolic schedule (jit'd roll form); TPU perf-model Dif (Eq. 5) |
| bench_stencil_suite       | Table 3/Fig. 5 | GCells/s, jnp shift-add reference vs SSAM schedule |
| bench_temporal_blocking   | Fig. 6         | fused t-step stencil vs t separate steps |
| bench_perf_model          | Table 2/§5     | hardware latency tables, L_smem/L_reg/AvgDif, halo ratios |
| bench_scan                | §3.6           | Kogge–Stone cumsum / linear recurrence vs lax reference |
| bench_sharded (--mesh AxB)| (beyond paper) | sharded halo-exchange vs single device: per-device bandwidth + §5 scaling prediction |
| bench_grad (--grad)       | (beyond paper) | fwd vs fwd+bwd through the adjoint plans, vs §5 fwd+adjoint cost |
| bench_fused (--fused)     | (beyond paper) | fused plan pipelines + epilogues vs the unfused HBM-round-trip sequence (stencil chain, Whisper stem) |
| bench_scan_chunked (--scan-chunked) | (beyond paper) | chunk-streamed engine scans vs monolithic engine vs XLA chunked: tokens/sec + peak temp memory at long T |
| bench_strategy (--strategy) | §5 + (beyond paper) | lanes (VPU shift-fma) vs mxu (im2row matmul) lowering per shape class: MB/s both ways, the tuner's pick, and §5 predicted-vs-measured ranking agreement |
| bench_backend (--backend) | §4 + (beyond paper) | TPU lane-roll vs GPU warp-shift lowering of the same plans: per-backend MB/s + each backend's machine-model prediction |
| bench_obs (--obs)         | §5 + (beyond paper) | telemetry readout: tuner sidecar hit-rates, engine launch/recompile counts, per-backend model-vs-measured drift aggregates |
| bench_chaos (--chaos)     | (beyond paper) | guarded execution under injected faults: idle-guard overhead (< 1%), fallback vs engine MB/s at fault prob 0/0.5/1.0 with demotion counts, decode-server survival under step faults |
| bench_lm_roofline         | (assignment)   | summary of dry-run roofline artifacts |

``--json PATH`` additionally writes every row as machine-readable JSON
(name, µs, parsed derived fields + run metadata) — the committed
``BENCH_5.json`` perf-trajectory artifact comes from
``--fused --json BENCH_5.json``, ``BENCH_6.json`` from
``--scan-chunked --json BENCH_6.json``, ``BENCH_7.json`` from
``--strategy auto --json BENCH_7.json``, ``BENCH_8.json`` from
``--backend auto --json BENCH_8.json``, ``BENCH_9.json`` from
``--obs --json BENCH_9.json`` (with ``--trace``/``--metrics`` sidecars)
and ``BENCH_10.json`` from ``--chaos --json BENCH_10.json``.

The container is CPU-only: wall-times are CPU XLA numbers that compare
*schedules*, not TPU performance; TPU performance is reported by the
roofline pipeline (artifacts → benchmarks/roofline.py → EXPERIMENTS.md).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, reps: int = 3) -> float:
    """Median wall-time (µs) of a jitted call, post-warmup."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


_JSON_ROWS: list | None = None     # set by main() when --json is given


def _parse_derived(derived: str) -> dict:
    out = {}
    for part in derived.split(";"):
        if not part or "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v.rstrip("x").rstrip("cyc").rstrip("pct"))
        except ValueError:
            out[k] = v
    return out


def _row(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.1f},{derived}")
    if _JSON_ROWS is not None:
        _JSON_ROWS.append({"name": name, "us_per_call": round(us, 2),
                           "derived": _parse_derived(derived)})


def _git_sha() -> str | None:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        return None


def _write_json(path: str) -> None:
    from repro.core.tuning import ENGINE_SCHEMA_VERSION
    doc = {
        "meta": {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            # provenance: which code produced these numbers — a BENCH_N
            # row is only comparable to another measured at the same
            # engine schema (winners mean different kernels otherwise)
            "git_sha": _git_sha(),
            "engine_schema_version": ENGINE_SCHEMA_VERSION,
            "jax_version": jax.__version__,
            "note": "CPU interpret-mode wall-times compare schedules, "
                    "not TPU performance",
        },
        "rows": _JSON_ROWS,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {len(_JSON_ROWS)} rows to {path}")


# ---------------------------------------------------------------------------
# Fig. 4 — 2-D convolution, filter sizes 2×2 … 20×20
# ---------------------------------------------------------------------------

def bench_conv2d_filter_sweep(img: int = 256):
    from repro.core import conv2d_plan
    from repro.core.executor import execute_conv_global
    from repro.core.perfmodel import TPU_V5E, V100, dif_smem_reg
    from repro.kernels import ref

    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((img, img)), jnp.float32)
    print("# Fig4: 2D convolution filter sweep "
          f"(image {img}x{img}, CPU wall-time)")
    for m in (2, 3, 5, 7, 9, 13):   # (17/20 compile too slowly on CPU-XLA; model values in bench_perf_model)
        w = jnp.array(rng.standard_normal((m, m)), jnp.float32)
        direct = jax.jit(ref.conv2d_valid)
        plan = conv2d_plan(m, m, S=img, P=1)
        ssam = jax.jit(lambda xx, ww: execute_conv_global(plan, xx, ww))
        t_direct = _timeit(direct, x, w)
        t_ssam = _timeit(ssam, x, w)
        model_dif_v100 = dif_smem_reg(V100, m, m)
        model_dif_tpu = dif_smem_reg(TPU_V5E, m, m)
        cells = (img - m + 1) ** 2
        _row(f"conv2d_direct_{m}x{m}", t_direct,
             f"gcells_s={cells / t_direct / 1e3:.2f}")
        _row(f"conv2d_ssam_{m}x{m}", t_ssam,
             f"gcells_s={cells / t_ssam / 1e3:.2f};"
             f"dif_v100={model_dif_v100:.0f}cyc;dif_tpu={model_dif_tpu:.0f}cyc")


# ---------------------------------------------------------------------------
# Batched NCHW convolution through the reduce-axes engine (--batch/--channels)
# ---------------------------------------------------------------------------

def bench_conv2d_batched(batch: int = 4, channels: tuple[int, int] = (3, 8),
                         img: int = 64, filters: tuple[int, ...] = (3, 5)):
    """NCHW minibatch conv: engine reduce-axes plan vs XLA direct conv.

    Reports per-image achieved bandwidth (useful traffic: one f32 read
    of the C_in planes + one write of the C_out planes per image) next
    to the §5 model's predicted cycles per output element — the
    per-channel-iterate ``model_cost`` times ``C_in``, since the
    channel reduction runs the tap group once per input channel.
    Interpret-mode wall-times compare schedules, not TPU performance.
    """
    from repro.core import conv2d_nchw_plan, tuning
    from repro.kernels import ops, ref

    C_in, C_out = channels
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((batch, C_in, img, img)), jnp.float32)
    print(f"# NCHW conv2d: batch={batch} channels={C_in}->{C_out} "
          f"image {img}x{img} (interpret-mode wall-time)")
    for fs in filters:
        w = jnp.array(rng.standard_normal((C_out, C_in, fs, fs)), jnp.float32)
        t_xla = _timeit(jax.jit(lambda a, b: ref.conv2d_nchw(a, b, "same")),
                        x, w)
        t_eng = _timeit(lambda: ops.conv2d(x, w, impl="interpret"))
        plan = conv2d_nchw_plan(batch, C_in, C_out, fs, fs, mode="same")
        base = tuning.KernelConfig(tuple(min(b, img) for b in (8, 128)))
        # §5 prediction: per-output cycles = C_in channel iterates of the
        # per-iterate block cost (the tap-group cost of one reduce step).
        cyc = tuning.model_cost(plan, base) * C_in
        # useful traffic per image (bytes/µs = MB/s; batch cancels out of
        # the per-image rate, so it never enters the expression)
        bytes_per_img = (C_in + C_out) * img * img * 4
        _row(f"conv2d_nchw_xla_{fs}x{fs}", t_xla,
             f"mb_s_per_img={bytes_per_img / max(t_xla, 1e-9):.2f}")
        _row(f"conv2d_nchw_engine_{fs}x{fs}", t_eng,
             f"mb_s_per_img={bytes_per_img / max(t_eng, 1e-9):.2f};"
             f"model_cyc={cyc:.1f};xla_ratio={t_eng / t_xla:.2f}x")


# ---------------------------------------------------------------------------
# Table 3 / Fig. 5 — stencil suite
# ---------------------------------------------------------------------------

def bench_stencil_suite(size2d: int = 384, size3d: int = 40):
    from repro.kernels import ref
    from repro.kernels.stencils import BENCHMARKS

    rng = np.random.default_rng(0)
    print(f"# Table3/Fig5: stencil suite (2D {size2d}^2, 3D {size3d}^3, "
          "CPU wall-time)")
    for name, sdef in BENCHMARKS.items():
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
        else:
            x = jnp.array(rng.standard_normal((size3d,) * 3), jnp.float32)
        fn = jax.jit(lambda xx, s=sdef: ref.stencil_iterate(xx, s, 1))
        t = _timeit(fn, x)
        cells = x.size
        _row(f"stencil_{name}", t,
             f"gcells_s={cells / t / 1e3:.3f};"
             f"gflops_s={cells * sdef.fpp / t / 1e3:.2f};fpp={sdef.fpp}")


# ---------------------------------------------------------------------------
# Fig. 6 — temporal blocking
# ---------------------------------------------------------------------------

def bench_temporal_blocking(size: int = 384):
    from repro.kernels import ref
    from repro.kernels.stencils import BENCHMARKS

    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((size, size)), jnp.float32)
    print("# Fig6: temporal blocking (fused t steps in one program vs t "
          "separate launches)")
    for name in ("2d5pt", "2d9pt", "3d7pt"):
        sdef = BENCHMARKS[name]
        if sdef.ndim == 3:
            xx = jnp.array(rng.standard_normal((48, 48, 48)), jnp.float32)
        else:
            xx = x
        for t_steps in (1, 2, 4):
            fused = jax.jit(lambda v, s=sdef, n=t_steps: ref.stencil_iterate(v, s, n))
            single = jax.jit(lambda v, s=sdef: ref.stencil_iterate(v, s, 1))

            def unfused(v):
                for _ in range(t_steps):
                    v = single(v)
                return v

            tf = _timeit(fused, xx)
            tu = _timeit(unfused, xx)
            cells = xx.size * t_steps
            _row(f"temporal_{name}_t{t_steps}_fused", tf,
                 f"gcells_s={cells / tf / 1e3:.3f}")
            _row(f"temporal_{name}_t{t_steps}_unfused", tu,
                 f"gcells_s={cells / tu / 1e3:.3f};speedup={tu / tf:.2f}x")


# ---------------------------------------------------------------------------
# Table 2 / §5 — analytical performance model
# ---------------------------------------------------------------------------

def bench_perf_model():
    from repro.core import conv2d_plan
    from repro.core.perfmodel import (P100, TPU_V5E, V100,
                                      avg_dif_lower_bound, dif_smem_reg,
                                      l_reg, l_smem)

    print("# Table2/§5: analytical model (cycles; paper-measured GPU "
          "latencies + TPU estimates)")
    for hw in (P100, V100, TPU_V5E):
        _row(f"latency_{hw.name}_shfl", hw.t_shfl, "cycles")
        _row(f"latency_{hw.name}_mad", hw.t_mad, "cycles")
        _row(f"latency_{hw.name}_smem_read", hw.t_smem_read, "cycles")
    for m in (3, 5, 9, 20):
        for hw in (V100, TPU_V5E):
            _row(f"model_{hw.name}_L_smem_{m}x{m}", l_smem(hw, m, m), "cycles")
            _row(f"model_{hw.name}_L_reg_{m}x{m}", l_reg(hw, m, m),
                 f"dif={dif_smem_reg(hw, m, m):.0f}cyc")
    for S in (32, 128):
        plan = conv2d_plan(5, 5, S=S, P=4)
        _row(f"halo_ratio_S{S}_5x5_P4", plan.halo_ratio() * 100,
             f"paper_bound={plan.halo_ratio_paper_bound() * 100:.1f}pct;"
             f"avgdif_v100={avg_dif_lower_bound(V100, plan):.0f}cyc")


# ---------------------------------------------------------------------------
# §3.6 — scan operator
# ---------------------------------------------------------------------------

def bench_scan(rows: int = 64, T: int = 8192):
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((rows, T)), jnp.float32)
    a = jnp.array(rng.uniform(0.5, 1.0, (rows, T)), jnp.float32)
    print(f"# §3.6 scan: ({rows}, {T}) CPU wall-time")
    t_ref = _timeit(jax.jit(ref.cumsum), x)
    _row("cumsum_ref", t_ref, f"gelem_s={x.size / t_ref / 1e3:.3f}")
    t_seq = _timeit(jax.jit(ref.linear_recurrence), a, x)
    _row("linrec_sequential", t_seq, f"gelem_s={x.size / t_seq / 1e3:.3f}")
    ck = jax.jit(lambda aa, bb: ops.chunked_linear_recurrence(aa, bb, chunk=128))
    t_ck = _timeit(ck, a, x)
    _row("linrec_chunked_ssam", t_ck,
         f"gelem_s={x.size / t_ck / 1e3:.3f};speedup={t_seq / t_ck:.1f}x")
    xs = x[:, :1024]
    t_sat = _timeit(jax.jit(ref.sat), xs)
    _row("sat_ref_64x1024", t_sat, f"gelem_s={xs.size / t_sat / 1e3:.3f}")


# ---------------------------------------------------------------------------
# Chunk-streamed engine scans: O(chunk) memory at long T (--scan-chunked)
# ---------------------------------------------------------------------------

def _temp_bytes(fn, *args) -> int:
    """Peak temp allocation of the compiled computation (XLA cost
    analysis); -1 when the backend does not report one."""
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return int(getattr(ma, "temp_size_in_bytes", -1))
    except Exception:
        return -1


def bench_scan_chunked(rows: int = 8, T: int = 4096, chunk: int = 128):
    """Chunk-streamed engine scans vs the monolithic engine and the XLA
    chunked baseline (DESIGN.md §12) — the BENCH_6 artifact.

    Three comparisons, each with fwd and fwd+bwd wall-time, tokens/sec
    and the compiled computation's peak temp allocation:

    * ``chunked_linear_recurrence`` on ``(rows, T)``: impl='engine'
      (the (R, chunk)-slab ``lax.scan`` stream with checkpointed
      backward — O(R·chunk) live state) vs 'engine_unchunked' (the
      monolithic O(T) engine lowering) vs 'chunked' (the non-engine XLA
      schedule).
    * a Mamba selective-scan train step (grad of a scalar loss) over
      increasing T — the tokens/sec + peak-memory *trajectory*;
    * the same trajectory for the RWKV6 WKV recurrence.

    Interpret-mode wall-times compare schedules, not TPU performance;
    the memory column is the schedule property the tentpole is about.
    """
    from repro.kernels import ops
    from repro.nn import ssm

    rng = np.random.default_rng(0)
    a = jnp.array(rng.uniform(0.5, 1.0, (rows, T)), jnp.float32)
    b = jnp.array(rng.standard_normal((rows, T)), jnp.float32)
    print(f"# §12 chunk-streamed scans: linrec ({rows}, {T}) chunk={chunk}; "
          "Mamba/RWKV train-step trajectories (interpret-mode wall-time)")
    for impl in ("engine", "engine_unchunked", "chunked"):
        fwd = lambda aa, bb, _i=impl: ops.chunked_linear_recurrence(
            aa, bb, chunk=chunk, impl=_i)
        loss = lambda aa, bb, _i=impl: jnp.sum(fwd(aa, bb, _i=_i) ** 2)
        grad = jax.jit(jax.grad(loss, (0, 1)))
        t_f = _timeit(jax.jit(fwd), a, b)
        t_g = _timeit(grad, a, b)
        mb_f = _temp_bytes(fwd, a, b)
        mb_g = _temp_bytes(jax.grad(loss, (0, 1)), a, b)
        _row(f"scanchunk_linrec_{impl}_fwd", t_f,
             f"tok_s={rows * T / max(t_f, 1e-9) * 1e6:.0f};"
             f"temp_bytes={mb_f}")
        _row(f"scanchunk_linrec_{impl}_fwdbwd", t_g,
             f"tok_s={rows * T / max(t_g, 1e-9) * 1e6:.0f};"
             f"temp_bytes={mb_g}")

    # Train-step trajectories: tokens/sec + peak temp memory vs T.
    # 'engine' is the streamed schedule; 'chunked' the non-engine
    # baseline; the monolithic engine only at the shortest T (its O(T)
    # state is the thing the stream removes).
    Bsz, Di, N = 1, 4, 8
    H, K, V = 2, 4, 4
    for Tm in (256, 512, 1024):
        delta = jnp.array(rng.uniform(0.1, 0.4, (Bsz, Tm, Di)), jnp.float32)
        A_log = jnp.array(-rng.uniform(0.5, 1.5, (Di, N)), jnp.float32)
        Bm = jnp.array(rng.standard_normal((Bsz, Tm, N)), jnp.float32)
        Cm = jnp.array(rng.standard_normal((Bsz, Tm, N)), jnp.float32)
        xm = jnp.array(rng.standard_normal((Bsz, Tm, Di)), jnp.float32)
        for impl in ("engine", "chunked") + (
                ("engine_unchunked",) if Tm == 256 else ()):
            loss = lambda d, x_, _i=impl: jnp.sum(ssm.selective_scan(
                d, A_log, Bm, Cm, x_, chunk=64, impl=_i)[0] ** 2)
            grad = jax.jit(jax.grad(loss, (0, 1)))
            t_g = _timeit(grad, delta, xm)
            mb_g = _temp_bytes(jax.grad(loss, (0, 1)), delta, xm)
            _row(f"scanchunk_mamba_{impl}_T{Tm}", t_g,
                 f"tok_s={Bsz * Tm / max(t_g, 1e-9) * 1e6:.0f};"
                 f"temp_bytes={mb_g}")
        r = jnp.array(rng.standard_normal((Bsz, Tm, H, K)), jnp.float32)
        k = jnp.array(rng.standard_normal((Bsz, Tm, H, K)), jnp.float32)
        v = jnp.array(rng.standard_normal((Bsz, Tm, H, V)), jnp.float32)
        logw = jnp.array(-rng.uniform(0.05, 0.5, (Bsz, Tm, H, K)),
                         jnp.float32)
        u = jnp.array(rng.standard_normal((H, K)), jnp.float32)
        for impl in ("engine", "chunked") + (
                ("engine_unchunked",) if Tm == 256 else ()):
            loss = lambda rr, vv, _i=impl: jnp.sum(ssm.wkv6_chunked(
                rr, k, vv, logw, u, chunk=64, impl=_i)[0] ** 2)
            grad = jax.jit(jax.grad(loss, (0, 1)))
            t_g = _timeit(grad, r, v)
            mb_g = _temp_bytes(jax.grad(loss, (0, 1)), r, v)
            _row(f"scanchunk_rwkv_{impl}_T{Tm}", t_g,
                 f"tok_s={Bsz * Tm / max(t_g, 1e-9) * 1e6:.0f};"
                 f"temp_bytes={mb_g}")


# ---------------------------------------------------------------------------
# Autotuner: tuned vs default block configs for the Table 3 suite
# ---------------------------------------------------------------------------

def bench_autotune(size2d: int = 192, size3d: int = 32):
    """Tuned vs default engine configs (µs + §5 model cost) per stencil.

    The tuner measures its model's top candidates *and* the default, so
    ``speedup`` is ≥ ~1.0 up to timer noise. Sizes are kept modest: the
    interpret-mode Pallas kernels this container can run are far slower
    than compiled Mosaic, and the point here is config selection, not
    absolute throughput.
    """
    from repro.core import tuning
    from repro.kernels import ops
    from repro.kernels import ssam_stencil2d, ssam_stencil3d
    from repro.kernels.stencils import BENCHMARKS

    rng = np.random.default_rng(0)
    print(f"# Autotune: tuned vs default block configs (2D {size2d}^2, "
          f"3D {size3d}^3, interpret-mode wall-time)")
    for name, sdef in BENCHMARKS.items():
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
            mod, default = ssam_stencil2d, tuning.KernelConfig((8, 128))
        else:
            x = jnp.array(rng.standard_normal((size3d,) * 3), jnp.float32)
            mod, default = ssam_stencil3d, tuning.KernelConfig((4, 8, 128))
        plan = mod.plan_for(sdef)
        t_default = tuning.measure_us(
            lambda: ops.stencil(x, sdef, impl="interpret",
                                **default.as_kwargs(plan)))
        runner = lambda cfg: tuning.measure_us(
            lambda: ops.stencil(x, sdef, impl="interpret",
                                **cfg.as_kwargs(plan)))
        t0 = time.perf_counter()
        tuned = tuning.autotune(plan, x.shape, default=default, runner=runner)
        tune_s = time.perf_counter() - t0
        cfg = tuned.config
        t_tuned = tuning.measure_us(
            lambda: ops.stencil(x, sdef, impl="interpret",
                                **cfg.as_kwargs(plan)))
        dif = (tuning.model_cost(plan, default)
               - tuning.model_cost(plan, cfg))
        _row(f"autotune_{name}_default", t_default,
             f"cfg={'x'.join(map(str, default.block))}")
        _row(f"autotune_{name}_tuned", t_tuned,
             f"cfg={'x'.join(map(str, cfg.block))};variant={cfg.variant};"
             f"model_dif={dif:.1f}cyc;speedup={t_default / t_tuned:.2f}x;"
             f"tune_cost_s={tune_s:.1f}")


# ---------------------------------------------------------------------------
# Sharded halo-exchange: per-device bandwidth vs the §5 model (--mesh AxB)
# ---------------------------------------------------------------------------

def bench_sharded(mesh_shape: tuple[int, ...], size2d: int = 256,
                  size3d: int = 32, time_steps: int = 1):
    """Sharded vs single-device engine wall-time on an ``AxB`` host mesh.

    Reports per-device *achieved* bandwidth (8 bytes per cell per step:
    one f32 read + one write of useful traffic) next to the §5 model's
    per-element cost for the shard-local halo-extended block — whose
    ratio to the single-device cost is the model's predicted scaling
    efficiency (the halo a shard re-loads is exactly the §5.3
    redundancy term evaluated at the shard size).
    """
    import math as _math

    from repro.core import tuning
    from repro.kernels import ops
    from repro.kernels import ssam_stencil2d, ssam_stencil3d
    from repro.launch.mesh import make_domain_mesh
    from repro.kernels.stencils import BENCHMARKS

    ndev = _math.prod(mesh_shape)
    if jax.device_count() < ndev:
        print(f"# sharded: need {ndev} devices, have {jax.device_count()} — "
              "set XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{ndev} (or run on a {ndev}-chip mesh)")
        return
    mesh = make_domain_mesh(mesh_shape)
    rng = np.random.default_rng(0)
    print(f"# Sharded halo exchange on {'x'.join(map(str, mesh_shape))} mesh "
          f"(2D {size2d}^2, 3D {size3d}^3, t={time_steps}, interpret-mode "
          "wall-time; CPU numbers compare schedules, not TPU perf)")
    for name in ("2d5pt", "2d9pt", "2ds25pt", "2d121pt", "3d7pt", "poisson"):
        sdef = BENCHMARKS[name]
        from repro.distributed import halo_exchange as hx
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
            mod = ssam_stencil2d
        else:
            x = jnp.array(rng.standard_normal((size3d,) * 3), jnp.float32)
            mod = ssam_stencil3d
        plan = mod.plan_for(sdef)
        # Resolve the layout exactly the way the timed call will (the
        # rule-table default spec), so the reported geometry describes
        # the run that is measured.
        spec = hx.default_domain_spec(x.shape, mesh)
        per_axis = hx._axis_assignments(spec, mesh, plan.ndim_spatial)
        try:
            shard_shape = tuning.shard_tuning_shape(
                plan, x.shape, per_axis, time_steps)
        except ValueError as e:
            _row(f"sharded_{name}", 0.0, f"skipped={e}")
            continue
        t_single = _timeit(
            lambda: ops.stencil(x, sdef, time_steps=time_steps,
                                impl="interpret"))
        t_shard = _timeit(
            lambda: ops.stencil(x, sdef, time_steps=time_steps,
                                impl="interpret", mesh=mesh))
        from repro.core.halo import check_shard_geometry
        local = check_shard_geometry(plan, x.shape, tuple(per_axis),
                                     time_steps)
        base = (8, 128) if sdef.ndim == 2 else (4, 8, 128)
        # §5 prediction: the same default schedule, block clamped to the
        # global vs the shard-local extent — the shard's smaller lane
        # tile amortizes less halo (§5.3), which is the model's whole
        # forecast of sharding overhead.
        cyc_single = tuning.model_cost(plan, tuning.KernelConfig(
            tuple(min(b, n) for b, n in zip(base, x.shape))), time_steps)
        cyc_shard = tuning.model_cost(plan, tuning.KernelConfig(
            tuple(min(b, n) for b, n in zip(base, local))), time_steps)
        bytes_useful = x.size * 8 * time_steps
        mbs_dev = bytes_useful / max(t_shard, 1e-9) / ndev   # bytes/µs = MB/s
        mbs_single = bytes_useful / max(t_single, 1e-9)
        _row(f"sharded_{name}_single", t_single,
             f"mb_s={mbs_single:.2f};model_cyc={cyc_single:.1f}")
        _row(f"sharded_{name}_{'x'.join(map(str, mesh_shape))}", t_shard,
             f"mb_s_per_dev={mbs_dev:.2f};model_cyc={cyc_shard:.1f};"
             f"pred_eff={cyc_single / cyc_shard:.2f};"
             f"speedup={t_single / t_shard:.2f}x;"
             f"shard={'x'.join(map(str, shard_shape))}")


# ---------------------------------------------------------------------------
# Adjoint plans: fwd+bwd bandwidth vs the §5 model (--grad)
# ---------------------------------------------------------------------------

def bench_grad(size2d: int = 128, size3d: int = 24,
               batch: int = 2, channels: tuple[int, int] = (3, 8),
               img: int = 48):
    """Forward vs forward+backward wall-time per engine op, next to the
    §5 model's prediction that bwd ≈ fwd + the adjoint plan's cost.

    Table-3 stencils differentiate through the point-reflected adjoint
    plan (backward-input only — 'table' coefficients have no weight
    grad); NCHW conv adds the backward-weight correlation, whose cost
    the model approximates by a second forward sweep (it reads the same
    x volume once more against the cotangent). MB/s counts useful
    traffic: fwd = read+write of the domain; fwd+bwd = 3× (forward,
    cotangent in, input-grad out) per step. Interpret-mode wall-times
    compare schedules, not TPU performance.
    """
    import jax

    from repro.core import adjoint as adjoint_mod
    from repro.core import conv2d_nchw_plan, input_adjoint_plan, tuning
    from repro.kernels import ops
    from repro.kernels import ssam_stencil2d, ssam_stencil3d
    from repro.kernels.stencils import BENCHMARKS

    rng = np.random.default_rng(0)
    print(f"# Adjoint plans: fwd vs fwd+bwd (2D {size2d}^2, 3D {size3d}^3, "
          "interpret-mode wall-time; model: cyc_fwd + cyc_adj per element)")
    for name in ("2d5pt", "2d9pt", "2ds25pt", "2d121pt", "3d7pt", "poisson"):
        sdef = BENCHMARKS[name]
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
            mod, base = ssam_stencil2d, (8, 128)
        else:
            x = jnp.array(rng.standard_normal((size3d,) * 3), jnp.float32)
            mod, base = ssam_stencil3d, (4, 8, 128)
        plan = mod.plan_for(sdef)
        cfg = tuning.KernelConfig(tuple(min(b, n) for b, n in
                                        zip(base, x.shape)))
        fwd = jax.jit(lambda v: ops.stencil(v, sdef, impl="interpret"))
        vjp = jax.jit(jax.grad(lambda v: jnp.sum(
            ops.stencil(v, sdef, impl="interpret"))))
        t_fwd = _timeit(fwd, x)
        t_bwd = _timeit(vjp, x)
        cyc_f = tuning.model_cost(plan, cfg)
        cyc_a = tuning.model_cost(input_adjoint_plan(plan), cfg)
        mb_f = x.size * 8 / max(t_fwd, 1e-9)
        mb_b = x.size * 8 * 3 / max(t_bwd, 1e-9)
        _row(f"grad_{name}_fwd", t_fwd,
             f"mb_s={mb_f:.2f};model_cyc={cyc_f:.1f}")
        _row(f"grad_{name}_fwdbwd", t_bwd,
             f"mb_s={mb_b:.2f};model_cyc={cyc_f + cyc_a:.1f};"
             f"bwd_ratio={t_bwd / t_fwd:.2f}x;"
             f"model_ratio={(cyc_f + cyc_a) / cyc_f:.2f}x")

    C_in, C_out = channels
    x = jnp.array(rng.standard_normal((batch, C_in, img, img)), jnp.float32)
    w = jnp.array(rng.standard_normal((C_out, C_in, 3, 3)), jnp.float32)
    plan = conv2d_nchw_plan(batch, C_in, C_out, 3, 3, mode="same")
    cfg = tuning.KernelConfig((min(8, img), min(128, img)))
    fwd = jax.jit(lambda a, b: ops.conv2d(a, b, impl="interpret"))
    vjp = jax.jit(jax.grad(
        lambda a, b: jnp.sum(ops.conv2d(a, b, impl="interpret")), (0, 1)))
    t_fwd = _timeit(fwd, x, w)
    t0 = _timeit(lambda: vjp(x, w))
    cyc_f = tuning.model_cost(plan, cfg) * C_in
    cyc_a = tuning.model_cost(input_adjoint_plan(plan), cfg) * C_out
    bytes_img = (C_in + C_out) * img * img * 4
    _row(f"grad_nchw_{C_in}x{C_out}_fwd", t_fwd,
         f"mb_s_per_img={bytes_img / max(t_fwd, 1e-9):.2f};"
         f"model_cyc={cyc_f:.1f}")
    _row(f"grad_nchw_{C_in}x{C_out}_fwdbwd", t0,
         f"mb_s_per_img={3 * bytes_img / max(t0, 1e-9):.2f};"
         f"model_cyc={2 * cyc_f + cyc_a:.1f};"      # + wgrad ≈ one fwd sweep
         f"bwd_ratio={t0 / t_fwd:.2f}x")
    print(f"# backward lowerings: {dict(adjoint_mod.BACKWARD_LOWERINGS)}")


# ---------------------------------------------------------------------------
# Fused plan pipelines: epilogues + chain composition (--fused)
# ---------------------------------------------------------------------------

def bench_fused(size2d: int = 192, B: int = 1, n_mels: int = 8,
                d_model: int = 16, T: int = 256):
    """Fused pipelines vs the unfused HBM-round-trip sequence.

    Two workloads (DESIGN.md §11):

    * a 3-deep 2-D stencil chain — ``ops.pipeline(fuse=True)`` lowers
      ONE engine kernel over the chain-widened halo vs ``fuse=False``
      (three kernels, two full HBM round-trips of the activation).
      The §5 model prediction next to it: summed flop terms + one
      load/store for the fused chain vs a load/store per stage unfused.
    * the Whisper mel stem — two k=3 NCHW convs with bias+GELU fused as
      kernel epilogues and the second conv's stride-2 lowered as an
      output-strided grid (half the lanes), vs the unfused form (dense
      engine convs, XLA bias/GELU between them, subsample at the end).

    Both fused paths are fp32-tolerance identical to the unfused ones
    (asserted here, not just in tests) and differentiable with backward
    on the engine. Interpret-mode wall-times compare schedules, not TPU
    performance.
    """
    from repro.core import tuning
    from repro.core.fuse import fuse_plans
    from repro.kernels import ops
    from repro.kernels import ssam_stencil2d
    from repro.kernels.stencils import BENCHMARKS
    from repro.nn import layers as nnl

    rng = np.random.default_rng(0)
    chain = ["2d5pt", "2d9pt", "2d5pt"]
    x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
    print(f"# Fused pipelines: {'+'.join(chain)} chain ({size2d}^2) and the "
          f"Whisper stem (B={B}, {n_mels} mels -> d={d_model}, T={T}); "
          "interpret-mode wall-time")

    fused = jax.jit(lambda v: ops.pipeline(v, chain, impl="interpret",
                                           fuse=True))
    unfused = jax.jit(lambda v: ops.pipeline(v, chain, impl="interpret",
                                             fuse=False))
    np.testing.assert_allclose(np.asarray(fused(x)), np.asarray(unfused(x)),
                               rtol=1e-4, atol=1e-4)
    t_f = _timeit(fused, x)
    t_u = _timeit(unfused, x)
    plans = [ssam_stencil2d.plan_for(BENCHMARKS[n]) for n in chain]
    fplan = fuse_plans(*plans)
    cfg = tuning.KernelConfig(tuple(min(b, n) for b, n in
                                    zip((8, 128), x.shape)))
    cyc_f = tuning.model_cost(fplan, cfg)
    cyc_u = sum(tuning.model_cost(p, cfg) for p in plans)
    bytes_useful = x.size * 8            # one read + one write of the domain
    _row(f"fused_chain_{'+'.join(chain)}_unfused", t_u,
         f"mb_s={bytes_useful / max(t_u, 1e-9):.2f};model_cyc={cyc_u:.1f}")
    _row(f"fused_chain_{'+'.join(chain)}_fused", t_f,
         f"mb_s={bytes_useful / max(t_f, 1e-9):.2f};model_cyc={cyc_f:.1f};"
         f"speedup={t_u / t_f:.2f}x;model_speedup={cyc_u / cyc_f:.2f}x")

    # Whisper stem: conv(n_mels->d) + GELU, conv(d->d, stride 2) + GELU.
    p1 = {"w": jnp.array(rng.standard_normal((d_model, n_mels, 1, 3)),
                         jnp.float32) * 0.2,
          "b": jnp.array(rng.standard_normal((d_model,)), jnp.float32)}
    p2 = {"w": jnp.array(rng.standard_normal((d_model, d_model, 1, 3)),
                         jnp.float32) * 0.2,
          "b": jnp.array(rng.standard_normal((d_model,)), jnp.float32)}
    mel = jnp.array(rng.standard_normal((B, n_mels, 1, T)), jnp.float32)

    def stem_fused(v):
        h = nnl.conv2d_apply(p1, v, impl="interpret", activation="gelu")
        return nnl.conv2d_apply(p2, h, impl="interpret", stride=(1, 2),
                                activation="gelu")

    def stem_unfused(v):
        # pre-§11 engine form: dense conv kernels, bias/GELU in XLA
        # between the calls, stride as an output subsample.
        h = ops.conv2d(v, p1["w"], impl="interpret")
        h = jax.nn.gelu(h + p1["b"][:, None, None], approximate=True)
        h = ops.conv2d(h, p2["w"], impl="interpret")
        h = jax.nn.gelu(h + p2["b"][:, None, None], approximate=True)
        return h[..., ::2]

    jf, ju = jax.jit(stem_fused), jax.jit(stem_unfused)
    np.testing.assert_allclose(np.asarray(jf(mel)), np.asarray(ju(mel)),
                               rtol=1e-4, atol=1e-4)
    t_f = _timeit(jf, mel)
    t_u = _timeit(ju, mel)
    bytes_stem = (mel.size + B * d_model * (T // 2)) * 4
    _row("fused_whisper_stem_unfused", t_u,
         f"mb_s={bytes_stem / max(t_u, 1e-9):.2f}")
    _row("fused_whisper_stem_fused", t_f,
         f"mb_s={bytes_stem / max(t_f, 1e-9):.2f};"
         f"speedup={t_u / t_f:.2f}x")


# ---------------------------------------------------------------------------
# Lowering strategy: VPU lanes vs MXU im2row matmul (--strategy)
# ---------------------------------------------------------------------------

def bench_strategy(strategy: str = "auto", size2d: int = 160,
                   size3d: int = 24, batch: int = 2,
                   channels: tuple[int, int] = (4, 8), img: int = 48):
    """Lanes vs MXU lowering per shape class — the BENCH_7 artifact.

    For a tap-count sweep of Table-3 stencils plus an NCHW conv (whose
    ``C_in·taps`` contraction is the MXU's best case), measures the same
    plan through both lowerings (``strategy='lanes'`` shift-fma vs
    ``strategy='mxu'`` im2row matmul), then lets the §5+MXU cost model
    and the measuring tuner each pick — reporting, per shape:

    * MB/s of useful traffic under each strategy,
    * the model's predicted winner and the measured winner (their
      agreement fraction across shapes is the §5 validation number),
    * the tuner's recorded choice and its speedup over the fixed
      pre-v5 default (always-lanes).

    With ``strategy='lanes'`` or ``'mxu'`` only that lowering is
    measured (a pinned-strategy smoke run). Interpret-mode wall-times
    compare schedules, not TPU performance — but the *algorithm choice*
    is real work either way (taps·rolls vs one gathered contraction).
    """
    from repro.core import tuning
    from repro.kernels import ops
    from repro.kernels import ssam_conv2d, ssam_stencil2d, ssam_stencil3d
    from repro.kernels.stencils import BENCHMARKS

    rng = np.random.default_rng(0)
    strategies = ("lanes", "mxu") if strategy == "auto" else (strategy,)
    names = ["2d5pt", "2d9pt", "2d13pt", "2d25pt", "2d121pt",
             "3d7pt", "3d27pt"]
    print(f"# Strategy: lanes vs mxu lowering (2D {size2d}^2, 3D {size3d}^3, "
          f"NCHW {batch}x{channels[0]}->{channels[1]}x{img}^2; "
          "interpret-mode wall-time)")
    agree = total = 0

    def _report(tag, plan, shape, run_fixed, run_cfg):
        """Measure every strategy, then model-pick, measure-pick and
        tuner-pick; returns 1 if model and measurement agree."""
        nonlocal agree, total
        times, model = {}, {}
        bytes_useful = int(np.prod(shape)) * 8
        for s in strategies:
            t = tuning.measure_us(lambda: run_fixed(s))
            cands = [c for c in tuning.candidate_configs(plan, shape)
                     if c.strategy == s]
            cyc = min(tuning.model_cost(plan, c) for c in cands)
            times[s], model[s] = t, cyc
            _row(f"strategy_{tag}_{s}", t,
                 f"mb_s={bytes_useful / max(t, 1e-9):.2f};"
                 f"model_cyc={cyc:.1f}")
        if strategy != "auto":
            return
        predicted = min(model, key=model.get)
        measured = min(times, key=times.get)
        tuning.clear_cache()
        runner = lambda cfg: tuning.measure_us(lambda: run_cfg(cfg))
        tuned = tuning.autotune(plan, shape, runner=runner)
        choice = tuned.config.strategy or "lanes"
        t_choice = tuning.measure_us(lambda: run_cfg(tuned.config))
        total += 1
        agree += int(predicted == measured)
        # speedup vs the fixed pre-v5 default: always-lanes at the
        # family default block — the thing the strategy dimension (plus
        # per-strategy shortlists) exists to beat.
        _row(f"strategy_{tag}_choice", t_choice,
             f"tuner={choice};cfg={'x'.join(map(str, tuned.config.block))};"
             f"predicted={predicted};measured={measured};"
             f"agree={int(predicted == measured)};"
             f"speedup_vs_default={times['lanes'] / max(t_choice, 1e-9):.2f}x")

    for name in names:
        sdef = BENCHMARKS[name]
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
            mod = ssam_stencil2d
        else:
            x = jnp.array(rng.standard_normal((size3d,) * 3), jnp.float32)
            mod = ssam_stencil3d
        plan = mod.plan_for(sdef)
        _report(name, plan, x.shape,
                lambda s, x=x, sdef=sdef: ops.stencil(
                    x, sdef, impl="interpret", strategy=s),
                lambda cfg, x=x, sdef=sdef, plan=plan: ops.stencil(
                    x, sdef, impl="interpret", **cfg.as_kwargs(plan)))

    C_in, C_out = channels
    xn = jnp.array(rng.standard_normal((batch, C_in, img, img)), jnp.float32)
    w = jnp.array(rng.standard_normal((C_out, C_in, 3, 3)), jnp.float32)
    plan = ssam_conv2d.plan_for_nchw(xn.shape, w.shape, "same")
    _report(f"conv2d_nchw_{C_in}x{C_out}", plan, xn.shape,
            lambda s: ops.conv2d(xn, w, mode="same", impl="interpret",
                                 strategy=s),
            lambda cfg: ops.conv2d(xn, w, mode="same", impl="interpret",
                                   **cfg.as_kwargs(plan)))

    if strategy == "auto" and total:
        _row("strategy_model_agreement", 0.0,
             f"agree_frac={agree / total:.2f};n={total}")


# ---------------------------------------------------------------------------
# Engine backends: TPU lane rolls vs GPU warp shifts (--backend)
# ---------------------------------------------------------------------------

def bench_backend(backend: str = "auto", size2d: int = 160, size3d: int = 24,
                  rows: int = 8, T: int = 1024):
    """TPU vs GPU engine lowering of the same plans — the BENCH_8 artifact.

    The plan IR is backend-neutral; ``backend='tpu'`` lowers shifts as
    whole-lane ``jnp.roll`` (the VREG lattice), ``backend='gpu'`` as
    ``engine_gpu.warp_shift`` (intra-warp lane roll + SMEM-staged
    inter-warp hand-off, the ``__shfl_up_sync`` emulation). For a
    tap-count sweep of Table-3 stencils, a 5x5 conv and the scan pair,
    measures each requested backend and reports MB/s of useful traffic
    next to that backend's *own* machine-model prediction
    (``perfmodel.machine_for``: TPUv5e lane geometry vs A100 warp
    geometry — different latency tables, different best blocks).

    With ``--backend auto`` both lowerings run on every shape, their
    outputs are asserted fp32-identical, and each row carries the
    model's predicted winner next to the measured one. Interpret-mode
    wall-times compare schedules, not device performance: both backends
    execute on the CPU interpreter here, so the wall-time gap measures
    schedule overhead (warp staging vs whole-lane rolls) while the
    model columns carry the per-device forecasts.
    """
    from repro.core import tuning
    from repro.core.perfmodel import machine_for
    from repro.kernels import ops
    from repro.kernels import ssam_conv2d, ssam_stencil2d, ssam_stencil3d
    from repro.kernels.stencils import BENCHMARKS

    rng = np.random.default_rng(0)
    backends = ("tpu", "gpu") if backend == "auto" else (backend,)
    for b in backends:
        m = machine_for(b)
        _row(f"backend_machine_{b}", 0.0,
             f"model={m.name};warp={m.warp};lanes={m.lanes};"
             f"hbm_gbps={m.hbm_gbps}")
    names = ["2d5pt", "2d9pt", "2d25pt", "2d121pt", "3d7pt", "3d27pt"]
    print(f"# Backends {'+'.join(backends)}: stencils (2D {size2d}^2, "
          f"3D {size3d}^3), conv2d 5x5, scans ({rows}, {T}); "
          "interpret-mode wall-time")

    def _report(tag, plan, shape, nbytes, run):
        times, model = {}, {}
        for b in backends:
            t = tuning.measure_us(lambda: run(b))
            cyc = min(tuning.model_cost(plan, c, backend=b) for c in
                      tuning.candidate_configs(plan, shape, backend=b))
            times[b], model[b] = t, cyc
            _row(f"backend_{tag}_{b}", t,
                 f"mb_s={nbytes / max(t, 1e-9):.2f};model_cyc={cyc:.2f}")
        if len(backends) == 2:
            np.testing.assert_allclose(
                np.asarray(run("tpu")), np.asarray(run("gpu")),
                rtol=1e-5, atol=1e-5, err_msg=tag)
            _row(f"backend_{tag}_pick", 0.0,
                 f"predicted={min(model, key=model.get)};"
                 f"measured={min(times, key=times.get)}")

    for name in names:
        sdef = BENCHMARKS[name]
        if sdef.ndim == 2:
            x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
            mod = ssam_stencil2d
        else:
            x = jnp.array(rng.standard_normal((size3d,) * 3), jnp.float32)
            mod = ssam_stencil3d
        plan = mod.plan_for(sdef)
        _report(name, plan, x.shape, x.size * 8,
                lambda b, x=x, sdef=sdef: ops.stencil(
                    x, sdef, impl="interpret", backend=b))

    w = jnp.array(rng.standard_normal((5, 5)), jnp.float32)
    x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
    plan = ssam_conv2d.plan_for(w.shape, "same")
    _report("conv2d_5x5", plan, x.shape, x.size * 8,
            lambda b: ops.conv2d(x, w, impl="interpret", backend=b))

    a = jnp.array(rng.uniform(0.5, 1.0, (rows, T)), jnp.float32)
    bb = jnp.array(rng.standard_normal((rows, T)), jnp.float32)
    from repro.core.plan import linear_recurrence_plan, scan_plan
    _report("cumsum", scan_plan(T), bb.shape, bb.size * 8,
            lambda k: ops.cumsum(bb, impl="interpret", backend=k))
    _report("linrec", linear_recurrence_plan(T), bb.shape, bb.size * 12,
            lambda k: ops.linear_recurrence(a, bb, impl="interpret",
                                            backend=k))


# ---------------------------------------------------------------------------
# LM roofline summary (assignment §Roofline)
# ---------------------------------------------------------------------------

def bench_lm_roofline():
    sys.path.insert(0, os.path.dirname(__file__))
    import roofline as rl

    recs = rl.load_records()
    if not recs:
        print("# roofline: no artifacts found (run repro.launch.dryrun)")
        return
    print("# LM roofline summary (single-pod; seconds per step; "
          "full table in EXPERIMENTS.md)")
    for r in recs:
        if r["mesh"] != "pod16x16" or r["status"] != "ok":
            continue
        rr = rl.roofline_of(r)
        _row(f"roofline_{r['arch']}_{r['shape']}", rr.bound_s * 1e6,
             f"dominant={rr.dominant};frac={rr.roofline_fraction:.3f};"
             f"useful={rr.useful_flops_ratio:.2f}")


# ---------------------------------------------------------------------------
# Telemetry: tuner hit-rates + model-vs-measured drift (--obs, BENCH_9.json)
# ---------------------------------------------------------------------------

def bench_obs(size2d: int = 128):
    """Exercise tuner + both engine backends under telemetry and report
    what the observability layer saw (DESIGN.md §15): sidecar hit/seed/
    miss rates, engine launch and lowering (recompile) counts, and the
    per-backend model-vs-measured drift aggregates — the BENCH_9.json
    rows. Absolute µs are CPU interpret-mode; the drift *ratios* are the
    artifact (they recalibrate the §5 constants on real hardware)."""
    from repro import obs
    from repro.core import tuning
    from repro.kernels import ops, ssam_stencil2d
    from repro.kernels.stencils import BENCHMARKS

    obs.metrics.reset()
    obs.drift.reset()
    tuning.clear_cache()
    tuning.clear_sidecar()
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
    names = [n for n, s in BENCHMARKS.items() if s.ndim == 2][:3]
    print(f"# Telemetry: tuner + drift over {names} on tpu+gpu lowerings "
          f"({size2d}^2, interpret mode)")
    for backend in ("tpu", "gpu"):
        for name in names:
            sdef = BENCHMARKS[name]
            plan = ssam_stencil2d.plan_for(sdef)
            default = tuning.KernelConfig((8, 128))
            runner = lambda cfg: tuning.measure_us(
                lambda: ops.stencil(x, sdef, impl="interpret",
                                    backend=backend, **cfg.as_kwargs(plan)))
            tuning.autotune(plan, x.shape, default=default, runner=runner,
                            backend=backend)
            # replay: the second autotune of the same key must cache-hit
            tuning.autotune(plan, x.shape, default=default, runner=runner,
                            backend=backend)

    snap = obs.metrics.snapshot()
    counters = snap["counters"]

    def total(cname):
        return counters.get(cname, {}).get("total", 0.0)

    hits = total("tuner.cache_hit") + total("tuner.sidecar_hit")
    lookups = hits + total("tuner.sidecar_seed") + total("tuner.sidecar_miss")
    _row("obs_tuner_hit_rate", 0.0,
         f"hits={hits:.0f};lookups={lookups:.0f};"
         f"rate={hits / max(lookups, 1):.2f};"
         f"measured={total('tuner.measure'):.0f}")
    for label, n in sorted(
            counters.get("engine.launch", {}).get("by_label", {}).items()):
        _row(f"obs_launch_{label.replace(':', '_')}", 0.0, f"count={n:.0f}")
    _row("obs_recompiles", 0.0,
         f"count={total('engine.lowering'):.0f}")

    for backend, agg in sorted(obs.drift.aggregate().items()):
        _row(f"obs_drift_{backend}", 0.0,
             f"pooled_ratio={agg['pooled_ratio']:.4g};"
             f"cells={agg['cells']};samples={agg['samples']};"
             f"max_drift={agg['max_drift']:.3f}x;"
             f"worst={agg['worst_signature']}")
    from repro.obs import report as obs_report
    print("# drift table (python -m repro.obs.report):")
    for line in obs_report.render().splitlines():
        print(f"#   {line}")


def bench_chaos(size2d: int = 160):
    """Guarded execution under injected faults (DESIGN.md §16) — the
    BENCH_10.json artifact.

    Three sections: (1) overhead-when-off — the guarded engine dispatch
    vs the raw engine call with the robustness layer idle, asserted
    < 1% (the fault check is one bool read and the guard one try frame);
    (2) fault sweep — MB/s served at engine-site fault probabilities
    {0, 0.5, 1.0} under ``on_failure='fallback'`` with the demotion
    counts, quantifying what degraded (oracle) service costs next to the
    engine path; (3) serve chaos — decode-server tokens/sec clean vs
    under transient step faults, with shed-request counts. Absolute µs
    are CPU interpret-mode; the *ratios* and counters are the artifact.
    """
    from repro import obs, robust
    from repro.core import tuning
    from repro.kernels import ops, ssam_stencil2d
    from repro.kernels.stencils import BENCHMARKS
    from repro.robust import faults

    obs.metrics.reset()
    tuning.clear_cache()
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((size2d, size2d)), jnp.float32)
    sdef = BENCHMARKS["2d5pt"]
    plan = ssam_stencil2d.plan_for(sdef)
    mb = 2 * x.size * 4 / 1e6               # in + out, fp32, MB per call

    print(f"# Chaos: guarded dispatch, 2d5pt {size2d}^2, interpret mode")

    # -- 1. overhead when the robustness layer is off ----------------------
    # The interpret-mode engine call jitters a few percent run-to-run,
    # which swamps a µs-scale guard in any A/B wall-time comparison
    # (the A/B delta is reported as an informational field only). So
    # measure the machinery directly: the full guarded dispatch with
    # the engine op stubbed to identity is exactly what the guard adds
    # per call — level-list build + one try frame — and that cost is
    # asserted against the real engine call's wall-time.
    cfg = ops._window_cfg(plan, {}, interpret=True)
    raw_f = lambda: ops._window_op(cfg, x, None, ())
    grd_f = lambda: ops._guarded_window("stencil", cfg, x, None, (), None)
    raw_f(); grd_f()                      # warm the jit caches
    raw_s, grd_s = [], []
    for _ in range(40):                   # interleaved to cancel drift
        t0 = time.perf_counter(); raw_f().block_until_ready()
        raw_s.append((time.perf_counter() - t0) * 1e6)
        t0 = time.perf_counter(); grd_f().block_until_ready()
        grd_s.append((time.perf_counter() - t0) * 1e6)
    raw_us = float(np.median(raw_s))
    ab_delta_pct = (float(np.median(grd_s)) - raw_us) / raw_us * 100
    real_op = ops._window_op
    ops._window_op = lambda c, xx, ww, ee: xx      # identity engine stub
    try:
        guard_us = _timeit(
            lambda: ops._guarded_window("stencil", cfg, x, None, (), None),
            reps=200)
    finally:
        ops._window_op = real_op
    overhead_pct = guard_us / raw_us * 100
    _row("chaos_guard_overhead_off", raw_us,
         f"guard_us={guard_us:.2f};overhead_pct={overhead_pct:.4f};"
         f"ab_delta_pct={ab_delta_pct:.2f}")
    assert overhead_pct < 1.0, (
        f"idle guard machinery is {overhead_pct:.2f}% of an engine call "
        f"(>1% budget)")

    # -- 2. fault sweep: engine MB/s vs fallback (oracle) MB/s -------------
    for site, call in (
        ("engine.window",
         lambda: ops.stencil(x, sdef, impl="interpret")),
        ("engine.scan",
         lambda: ops.cumsum(x, impl="interpret")),
    ):
        for prob in (0.0, 0.5, 1.0):
            with robust.inject(f"{site}:{prob}:3"), \
                    robust.failure_policy("fallback"):
                d0 = obs.metrics.counter_total("robust.demotion")
                us = _timeit(call, reps=9)
                demoted = obs.metrics.counter_total("robust.demotion") - d0
                fired = faults.fired_counts().get(site, 0)
            tag = site.split(".")[1]
            _row(f"chaos_{tag}_p{int(prob * 100)}", us,
                 f"mbps={mb * 1e6 / us:.1f};prob={prob};"
                 f"demotions={demoted:.0f};fired={fired}")

    # -- 3. decode-server throughput under step faults ---------------------
    from repro.config import get_config
    from repro.launch.serve import DecodeServer, Request
    from repro.models import build_model
    from repro.nn.spec import init_params

    cfgm = get_config("gemma3_1b", smoke=True)
    model = build_model(cfgm)
    params = init_params(model.specs(), jax.random.PRNGKey(0))

    def serve_run(spec: str | None):
        srv = DecodeServer(model, params, slots=2, cache_len=32)
        reqs = [Request(i, rng.integers(0, cfgm.vocab, 4, dtype=np.int32), 8)
                for i in range(6)]
        t0 = time.perf_counter()
        with robust.failure_policy("fallback"):
            if spec:
                with robust.inject(spec):
                    done = srv.run(reqs)
            else:
                done = srv.run(reqs)
        dt = time.perf_counter() - t0
        tok = sum(len(r.out) for r in done if r.error is None)
        shed = sum(1 for r in done if r.error)
        return tok / dt, shed, srv.step_failures

    serve_run(None)                       # warm the serve_step jit cache
    clean_tps, _, _ = serve_run(None)
    chaos_tps, shed, failures = serve_run("serve.step:0.3:7")
    _row("chaos_serve_clean", 0.0, f"tok_per_s={clean_tps:.1f}")
    _row("chaos_serve_p30", 0.0,
         f"tok_per_s={chaos_tps:.1f};shed={shed};step_failures={failures};"
         f"ratio={chaos_tps / max(clean_tps, 1e-9):.3f}")


def main(argv=None) -> None:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--mesh", default=None, metavar="AxB",
        help="run the sharded halo-exchange bench on an AxB device mesh "
             "(e.g. 2x4 or 8x1); needs A*B devices — on CPU set "
             "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    p.add_argument(
        "--time-steps", type=int, default=1,
        help="fused temporal steps for the sharded bench (default 1)")
    p.add_argument(
        "--grad", action="store_true",
        help="run the adjoint-plan benchmark: fwd vs fwd+bwd MB/s for "
             "Table-3 stencils and NCHW conv next to the §5 model's "
             "fwd + adjoint-plan cost prediction")
    p.add_argument(
        "--batch", type=int, default=None, metavar="B",
        help="run the NCHW conv bench with a B-image minibatch through "
             "the reduce-axes engine plan")
    p.add_argument(
        "--channels", default=None, metavar="Cin,Cout",
        help="input,output channel counts for the NCHW conv bench "
             "(default 3,8; implies --batch 4 when only --channels given)")
    p.add_argument(
        "--fused", action="store_true",
        help="run the fused-pipeline benchmark: fused vs unfused wall-time "
             "and §5 cost for a 3-deep stencil chain (ops.pipeline) and "
             "the epilogue+strided Whisper mel stem")
    p.add_argument(
        "--scan-chunked", action="store_true",
        help="run the chunk-streamed scan benchmark: streamed engine vs "
             "monolithic engine vs XLA chunked linrec, plus Mamba/RWKV "
             "train-step tokens/sec + peak-temp-memory trajectories over "
             "increasing T (the BENCH_6.json artifact)")
    p.add_argument(
        "--strategy", default=None, choices=("lanes", "mxu", "auto"),
        help="run the lowering-strategy benchmark: lanes (VPU shift-fma) "
             "vs mxu (im2row matmul) MB/s per Table-3 shape class + NCHW "
             "conv, the tuner's per-shape pick and the §5 predicted-vs-"
             "measured ranking agreement (the BENCH_7.json artifact uses "
             "'auto'; 'lanes'/'mxu' measure only that lowering)")
    p.add_argument(
        "--backend", default=None, choices=("tpu", "gpu", "auto"),
        help="run the per-backend engine benchmark: TPU lane-roll vs GPU "
             "warp-shift lowering of the same plans, MB/s per backend next "
             "to each backend's machine-model prediction "
             "(perfmodel.machine_for); 'auto' measures both and asserts "
             "equivalence (the BENCH_8.json artifact uses 'auto')")
    p.add_argument(
        "--obs", action="store_true",
        help="run the telemetry benchmark: tuner sidecar hit-rates, engine "
             "launch/recompile counts and per-backend model-vs-measured "
             "drift aggregates (the BENCH_9.json artifact; pairs with "
             "--trace/--metrics)")
    p.add_argument(
        "--chaos", action="store_true",
        help="run the guarded-execution benchmark: idle-guard overhead "
             "(asserted < 1%%), MB/s served under injected engine faults "
             "at prob 0/0.5/1.0 with demotion counts, and decode-server "
             "throughput under transient step faults (the BENCH_10.json "
             "artifact)")
    p.add_argument(
        "--trace", default=None, metavar="DIR",
        help="record a jax.profiler trace of the whole run under DIR "
             "(DIR/plugins/profile/<run>/<host>.xplane.pb, which "
             "TensorBoard and Perfetto open), with the engine/tuner/halo "
             "spans on its host timeline")
    p.add_argument(
        "--metrics", default=None, metavar="PATH",
        help="write the metrics registry snapshot + drift recorder state "
             "as JSON to PATH at exit (render the drift table with "
             "python -m repro.obs.report PATH)")
    p.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write every benchmark row as machine-readable JSON "
             "(per-kernel µs, MB/s, tuned config, §5 prediction, fused vs "
             "unfused) to PATH")
    args = p.parse_args(argv)
    global _JSON_ROWS
    if args.json:
        _JSON_ROWS = []
    from repro import obs
    try:
        if args.trace:
            with obs.tracing(args.trace):
                _run_mode(args)
            print(f"# wrote a profiler trace under {args.trace}")
        else:
            _run_mode(args)
    finally:
        if args.metrics:
            print(f"# wrote metrics+drift to {obs.metrics.export(args.metrics)}")
        if args.json:
            _write_json(args.json)


def _run_mode(args):
    """Run the benchmarks the mode flags select."""
    if args.mesh:
        shape = tuple(int(v) for v in args.mesh.lower().split("x"))
        bench_sharded(shape, time_steps=args.time_steps)
    elif args.grad:
        bench_grad()
    elif args.fused:
        bench_fused()
    elif args.scan_chunked:
        bench_scan_chunked()
    elif args.strategy:
        bench_strategy(args.strategy)
    elif args.backend:
        bench_backend(args.backend)
    elif args.obs:
        bench_obs()
    elif args.chaos:
        bench_chaos()
    elif args.batch is not None or args.channels is not None:
        ch = tuple(int(v) for v in (args.channels or "3,8").split(","))
        bench_conv2d_batched(args.batch if args.batch is not None else 4,
                             ch)
    else:
        bench_perf_model()
        bench_conv2d_filter_sweep()
        bench_stencil_suite()
        bench_temporal_blocking()
        bench_scan()
        bench_autotune()
        bench_fused()
        bench_lm_roofline()


if __name__ == "__main__":
    main()
