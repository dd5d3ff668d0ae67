#!/usr/bin/env python3
"""Bring-up check on a TPU: the main paths at full size, against references.

One process drives every phase through the entry points a user calls and
checks each result against a plain reference:

* engine phase: ``repro.kernels.ops`` stencils, 2-D and NCHW convolutions
  (forward and gradients) and a linear recurrence at chip-filling sizes,
  compiled for the chip (``impl="pallas"``), each against
  ``repro.kernels.ref`` / ``lax.conv_general_dilated``;
* model phase: ``rwkv6-1.6b`` at its published widths with random weights
  from ``--seed``, served through ``repro.launch.serve.DecodeServer``; its
  prefill runs the chunk-streamed engine WKV scan and is checked against
  the same model on the XLA ``'chunked'`` scan;
* ``--chips 4`` runs only the sharded halo exchange on a 2x2 mesh, each
  boundary mode against the same stencil on one device.

Kernels run at their untuned family-default blocks; no tuning sidecar is
read. Matmuls and convolutions run at ``'highest'`` precision so the
XLA references are f32-accurate. Any failed phase raises and exits
non-zero, as does a host without a TPU; a guard demotion to a fallback
level counts as a failure. On success the last line of standard output
is ``{"ok": true, "device": {...}}``.

Usage::

    python3 chip_smoke.py            # one chip: engine + model phases
    python3 chip_smoke.py --chips 4  # 2x2 host: sharded stencil only
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
os.environ.pop("REPRO_TUNING_CACHE", None)   # untuned defaults, no sidecar

# Full sizes (one v5e chip holds 16 GB of HBM).
FIELD_2D = 8192            # 8192² f32 = 256 MiB per field
FIELD_3D = 512             # 512³ f32 = 512 MiB
NCHW = (8, 64, 256, 256)   # B, C_in = C_out, H, W; 3×3 filter
RECURRENCE = (2048, 16384)
SHARDED_2D = 16384         # 1 GiB, 256 MiB per chip on a 2x2 mesh
SLOTS, REQUESTS, PROMPT, NEW_TOKENS = 4, 8, 512, 16

# Tolerances on max|got − want| / max|want|.
TOL_FWD = 1e-4       # f32 forward kernels vs their references
TOL_GRAD = 1e-3      # gradients: sums over B·H·W products per weight
TOL_PREFILL = 1e-3   # 24-layer prefill logits, engine vs XLA chunked scan
TOL_SHARDED = 1e-6   # sharded vs one-device engine (same arithmetic)


class SmokeFailure(RuntimeError):
    pass


def rel_err(got, want) -> tuple[float, float]:
    """(max abs error, max abs error over max |want|); fails on non-finite
    output."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        raise SmokeFailure("non-finite output")
    err = float(jnp.max(jnp.abs(got - want)))
    return err, err / max(float(jnp.max(jnp.abs(want))), 1e-30)


def compile_and_run(fn, *args):
    """(output, compile seconds, run seconds) of ``jax.jit(fn)(*args)``."""
    import jax
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    return out, t1 - t0, time.perf_counter() - t1


def run_case(name, shape, fn, ref_fn, args, tol):
    """Compile and run one engine call, check it, print its line."""
    import jax
    out, tc, tr = compile_and_run(fn, *args)
    want = jax.jit(ref_fn)(*args)
    outs = out if isinstance(out, tuple) else (out,)
    wants = want if isinstance(want, tuple) else (want,)
    errs = [rel_err(o, w) for o, w in zip(outs, wants)]
    err = max(e[0] for e in errs)
    rel = max(e[1] for e in errs)
    print(f"{name:<30} shape={shape} max_err={err:.3e} rel={rel:.3e} "
          f"tol={tol:g} compile_s={tc:.2f} run_s={tr:.3f}", flush=True)
    if not rel <= tol:
        raise SmokeFailure(f"{name}: relative error {rel:.3e} > {tol:g}")


def engine_phase(impl, *, n2=FIELD_2D, n3=FIELD_3D, nchw=NCHW,
                 rec=RECURRENCE, seed=0):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.stencils import BENCHMARKS

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    normal = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)

    x2 = normal((n2, n2))
    for name, t in (("2d5pt", 1), ("2d5pt", 2), ("2d121pt", 1)):
        run_case(f"stencil {name} t={t}", x2.shape,
                 lambda x, name=name, t=t: ops.stencil(
                     x, name, time_steps=t, impl=impl),
                 lambda x, name=name, t=t: ref.stencil_iterate(
                     x, BENCHMARKS[name], t),
                 (x2,), TOL_FWD)
    for mode, (fh, fw), strategy in (("same", (5, 5), "lanes"),
                                     ("valid", (20, 20), "lanes"),
                                     ("same", (5, 5), "mxu")):
        w = normal((fh, fw))
        ref_fn = ref.conv2d_same if mode == "same" else ref.conv2d_valid
        run_case(f"conv2d {mode} {fh}x{fw} {strategy}", x2.shape,
                 lambda x, w, mode=mode, s=strategy: ops.conv2d(
                     x, w, mode=mode, strategy=s, impl=impl),
                 ref_fn, (x2, w), TOL_FWD)
    del x2

    x3 = normal((n3, n3, n3))
    run_case("stencil 3d27pt t=1", x3.shape,
             lambda x: ops.stencil(x, "3d27pt", impl=impl),
             lambda x: ref.stencil_iterate(x, BENCHMARKS["3d27pt"], 1),
             (x3,), TOL_FWD)
    del x3

    B, C, H, W = nchw
    xn, wn = normal(nchw), normal((C, C, 3, 3)) / (3 * C ** 0.5)
    run_case("conv2d nchw same 3x3", xn.shape,
             lambda x, w: ops.conv2d(x, w, mode="same", impl=impl),
             lambda x, w: ref.conv2d_nchw(x, w, "same"), (xn, wn), TOL_FWD)

    def grads(conv):
        # gradients of <conv(x, w), g> w.r.t. x (adjoint kernel) and w
        # (weight-gradient kernel), for a random cotangent g
        return lambda x, w, g: jax.grad(
            lambda x, w: jnp.vdot(conv(x, w), g), argnums=(0, 1))(x, w)

    g = normal(nchw)
    run_case("conv2d nchw grad x,w", xn.shape,
             grads(lambda x, w: ops.conv2d(x, w, mode="same", impl=impl)),
             grads(lambda x, w: ref.conv2d_nchw(x, w, "same")),
             (xn, wn, g), TOL_GRAD)
    del xn, wn, g

    a = jax.random.uniform(next(keys), rec, jnp.float32, 0.5, 1.0)
    b = normal(rec)
    run_case("linear_recurrence", a.shape,
             lambda a, b: ops.linear_recurrence(a, b, impl=impl),
             ref.linear_recurrence, (a, b), TOL_FWD)


def model_phase(*, arch="rwkv6-1.6b", smoke=False, seed=0, slots=SLOTS,
                requests=REQUESTS, prompt=PROMPT, new_tokens=NEW_TOKENS):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config import get_config
    from repro.launch.serve import DecodeServer, Request
    from repro.models import build_model
    from repro.nn.spec import init_params

    cfg = dataclasses.replace(get_config(arch, smoke=smoke),
                              scan_impl="engine")
    model = build_model(cfg)
    ref_model = build_model(dataclasses.replace(cfg, scan_impl="chunked"))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(model.specs(), jax.random.PRNGKey(seed)))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab}, {n_params} f32 parameters, "
          f"init_s={time.perf_counter() - t0:.2f}", flush=True)

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, (requests, prompt), dtype=np.int32)
    server = DecodeServer(model, params, slots=slots,
                          cache_len=prompt + new_tokens)

    # The server prefills prompt[:-1]; check that call on the first prompt.
    toks = jnp.asarray(prompts[:1, :-1])
    (logits, _), tc, tr = compile_and_run(model.prefill, params, toks)
    (want, _), tc_ref, _ = compile_and_run(ref_model.prefill, params, toks)
    err, rel = rel_err(logits, want)
    print(f"{'prefill engine vs chunked':<30} shape={tuple(logits.shape)} "
          f"tokens={toks.shape[1]} max_err={err:.3e} rel={rel:.3e} "
          f"tol={TOL_PREFILL:g} compile_s={tc:.2f} run_s={tr:.3f} "
          f"ref_compile_s={tc_ref:.2f}", flush=True)
    if not rel <= TOL_PREFILL:
        raise SmokeFailure(f"prefill: relative error {rel:.3e}")

    reqs = [Request(i, prompts[i], new_tokens) for i in range(requests)]
    t0 = time.perf_counter()
    done = server.run(reqs)
    run_s = time.perf_counter() - t0
    outs = [r.out for r in done]
    bad = [r.rid for r in done if r.error or len(r.out) != new_tokens
           or not all(0 <= t < cfg.vocab for t in r.out)]
    generated = sum(len(o) for o in outs)
    print(f"{'serve DecodeServer':<30} slots={slots} requests={len(done)} "
          f"prompt={prompt} tokens_generated={generated} "
          f"decode_steps={server.steps} run_s={run_s:.2f} "
          f"(includes compiles)", flush=True)
    if len(done) != requests or bad:
        raise SmokeFailure(f"serve: {len(done)}/{requests} requests done, "
                           f"bad requests {bad}")


def sharded_phase(impl, *, n=SHARDED_2D, mesh_shape=(2, 2), seed=0):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels import ops
    from repro.kernels.stencils import BENCHMARKS
    from repro.launch.mesh import make_domain_mesh

    name, t = "2d25pt", 2
    sdef = BENCHMARKS[name]
    mesh = make_domain_mesh(mesh_shape)
    one = jax.devices()[0]
    x = jax.device_put(jax.random.normal(jax.random.PRNGKey(seed), (n, n),
                                         jnp.float32), one)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
    # wrap on one device: pad periodically by the t-step footprint, run the
    # zero-boundary engine, keep the interior it computes exactly
    halo = [(t * -min(o[a] for o in sdef.offsets),
             t * max(o[a] for o in sdef.offsets)) for a in range(2)]
    single = {
        "zero": lambda x: ops.stencil(x, name, time_steps=t, impl=impl),
        "wrap": lambda x: ops.stencil(
            jnp.pad(x, halo, mode="wrap"), name, time_steps=t, impl=impl,
        )[halo[0][0]:halo[0][0] + n, halo[1][0]:halo[1][0] + n],
    }
    for boundary in ("zero", "wrap"):
        got, tc, tr = compile_and_run(
            lambda x, b=boundary: ops.stencil(
                x, name, time_steps=t, mesh=mesh, boundary=b, impl=impl), xs)
        want, tc1, tr1 = compile_and_run(single[boundary], x)
        err, rel = rel_err(jax.device_put(got, one), want)
        print(f"sharded {name} t={t} {boundary:<4}            "
              f"shape={x.shape} mesh={mesh_shape} max_err={err:.3e} "
              f"rel={rel:.3e} tol={TOL_SHARDED:g} compile_s={tc:.2f} "
              f"run_s={tr:.3f} one_device_compile_s={tc1:.2f} "
              f"one_device_run_s={tr1:.3f}", flush=True)
        if not rel <= TOL_SHARDED:
            raise SmokeFailure(f"sharded {boundary}: relative error {rel:.3e}")
        del got, want


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded stencil on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro import obs
    from repro.config import set_on_failure
    from repro.launch import compile_cache

    cache = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    jax.config.update("jax_default_matmul_precision", "highest")
    set_on_failure("raise")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache}; tuning: no sidecar, untuned family "
          f"default blocks; failure policy: raise", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase("pallas", seed=args.seed)
    else:
        engine_phase("pallas", seed=args.seed)
        model_phase(seed=args.seed)
    demotions = obs.metrics.counter_total("robust.demotion")
    if demotions:
        raise SmokeFailure(f"{demotions:g} robust.demotion events")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s; "
          f"robust.demotion=0", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
