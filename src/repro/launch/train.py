"""Production training driver.

Single-host entrypoint that exercises the full stack end-to-end: config →
mesh → sharded init → jitted train step (loss/grads/AdamW) → data
pipeline → checkpoint/restart → straggler watchdog. On a real multi-pod
cluster the same driver runs under ``jax.distributed.initialize`` with
``make_production_mesh``; here the mesh spans however many (real or
XLA-forced) host devices exist.

Fault tolerance:
* checkpoints every ``--ckpt-every`` steps (async, atomic COMMIT marker);
* on start, resumes from the latest committed step automatically;
* ``--fail-at-step N`` raises mid-run (after the step, before its
  checkpoint) to let tests prove bit-exact restart;
* a step-time watchdog EMA flags stragglers (>2.5σ) — on TPU pods this
  is where you would trigger data-shard re-balancing; we log and count.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--conv-frontend", action="store_true",
                    help="audio archs: train the real mel conv stem "
                         "through the SSAM engine instead of the stub "
                         "frame embeddings (whisper)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at-step", type=int, default=-1,
                    help="inject a crash after this step (fault-tolerance tests)")
    ap.add_argument("--scan-impl", default=None,
                    choices=("engine", "engine_unchunked", "chunked"),
                    help="recurrence schedule for ssm/rwkv archs: "
                         "'engine' streams (R, chunk) slabs through the "
                         "chunk-streamed engine scan (O(chunk) memory, "
                         "DESIGN.md §12); default picks per backend")
    ap.add_argument("--metrics-file", default="")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()

    from repro.checkpointing import CheckpointManager
    from repro.config import ShapeConfig, get_config
    from repro.data import TokenDataset
    from repro.distributed.sharding import mesh_context, shardings_for_specs
    from repro.launch.cell import build_cell
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.nn.spec import abstract_params, init_params
    from repro.optim import adamw_state_specs

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.conv_frontend:
        if cfg.family != "audio":
            ap.error(f"--conv-frontend is for audio archs, not {cfg.family}")
        n_mels = cfg.n_mels or (8 if args.smoke else 80)
        cfg = dataclasses.replace(cfg, conv_frontend=True, n_mels=n_mels)
    if args.scan_impl:
        cfg = dataclasses.replace(cfg, scan_impl=args.scan_impl)
    mesh = make_host_mesh(args.model_axis)
    shape = ShapeConfig("custom_train", "train", args.seq, args.batch)
    cell = build_cell(cfg, shape, mesh, dtype=args.dtype, lr=args.lr,
                      lr_warmup=max(args.steps // 10, 10),
                      lr_total=max(args.steps, 100))
    model = build_model(dataclasses.replace(cfg, dtype=args.dtype))
    pspecs = model.specs()
    ospecs = adamw_state_specs(pspecs)
    params_sh = shardings_for_specs(pspecs, mesh)
    opt_sh = shardings_for_specs(ospecs, mesh)

    with mesh, mesh_context(mesh):
        init_fn = jax.jit(lambda k: init_params(pspecs, k),
                          out_shardings=params_sh)
        params = init_fn(jax.random.PRNGKey(args.seed))
        opt_state = jax.jit(lambda k: init_params(ospecs, k),
                            out_shardings=opt_sh)(jax.random.PRNGKey(0))

        start_step = 0
        ckpt = None
        if args.ckpt_dir:
            ckpt = CheckpointManager(args.ckpt_dir)
            restored = ckpt.restore_latest(
                {"params": abstract_params(pspecs),
                 "opt": abstract_params(ospecs)},
                shardings={"params": params_sh, "opt": opt_sh})
            if restored[0] is not None:
                start_step = restored[0]
                params = restored[1]["params"]
                opt_state = restored[1]["opt"]
                print(f"[train] resumed from step {start_step}", flush=True)

        step_fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                          out_shardings=cell.out_shardings,
                          donate_argnums=cell.donate_argnums)

        ds = TokenDataset(cfg.vocab, args.seq, seed=args.seed)
        times, losses = [], []
        ema, emvar = None, 0.0
        stragglers = 0
        metrics_f = open(args.metrics_file, "a") if args.metrics_file else None

        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     ds.batch(step, args.batch).items()}
            if cfg.family == "audio":
                if cfg.conv_frontend:
                    batch["mel"] = jax.random.normal(
                        jax.random.PRNGKey(step),
                        (args.batch, cfg.n_mels, 2 * cfg.n_frames),
                        cfg.param_dtype)
                else:
                    batch["frames"] = jax.random.normal(
                        jax.random.PRNGKey(step), (args.batch, cfg.n_frames,
                                                   cfg.d_model),
                        cfg.param_dtype)
            if cfg.family == "vlm":
                batch["prefix_embeds"] = jax.random.normal(
                    jax.random.PRNGKey(step), (args.batch, cfg.n_prefix,
                                               cfg.d_model), cfg.param_dtype)
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            times.append(dt)
            losses.append(loss)
            # straggler watchdog (EMA ± 2.5σ)
            if ema is None:
                ema = dt
            else:
                d = dt - ema
                ema += 0.1 * d
                emvar = 0.9 * (emvar + 0.1 * d * d)
                if step > start_step + 5 and dt > ema + 2.5 * max(emvar, 1e-12) ** 0.5 and dt > 1.5 * ema:
                    stragglers += 1
                    print(f"[watchdog] step {step} straggled: {dt:.3f}s "
                          f"(ema {ema:.3f}s)", flush=True)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):8.3f} {dt*1e3:7.1f}ms",
                      flush=True)
            if metrics_f:
                metrics_f.write(json.dumps(
                    {"step": step, "loss": loss, "dt": dt}) + "\n")
                metrics_f.flush()
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          meta={"arch": cfg.name})
            if args.fail_at_step == step:
                if ckpt:
                    ckpt.wait()   # durable writes survive the crash; the
                    # in-flight-write case is covered by the COMMIT-marker
                    # atomicity test.
                raise RuntimeError(f"injected failure at step {step}")

        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state},
                      meta={"arch": cfg.name})
            ckpt.wait()
        if metrics_f:
            metrics_f.close()
        print(f"[train] done: loss {losses[0]:.4f} → {losses[-1]:.4f}, "
              f"median step {np.median(times)*1e3:.1f}ms, "
              f"stragglers flagged: {stragglers}", flush=True)
        return losses


if __name__ == "__main__":
    main()
