"""Public jit'd API over the SSAM kernels, with backend dispatch.

Every op takes ``impl``:

* ``"interpret"`` (default here, CPU container) — the engine-lowered
  Pallas kernel executed by the Pallas interpreter: validates the real
  kernel schedule.
* ``"pallas"``    — compiled Mosaic kernel (real TPU only).
* ``"xla"``       — the pure-jnp oracle from :mod:`repro.kernels.ref`;
  shardable under pjit, used by the full-scale models and the dry-run.

``default_impl()`` picks "pallas" on TPU backends and "xla" elsewhere, so
model code can stay backend-agnostic.

Every non-xla op also takes ``autotune``: when True, the block config
(and schedule variant) is chosen by the §5 perf-model autotuner
(:mod:`repro.core.tuning`) — the model ranks candidates, the top few are
measured (the family default always included, so tuning never regresses
it), and winners are cached per (plan, shape, backend). Explicit block
kwargs win over tuned values.

``ops.stencil`` / ``ops.conv2d`` additionally take ``mesh=`` /
``in_specs=`` / ``boundary=``: with a mesh, the domain is sharded per
the PartitionSpec (default: the rule tables via
``halo_exchange.default_domain_spec``) and the plan runs through the
:mod:`repro.distributed.halo_exchange` layer — ppermute halo pushes
once per call, interior compute overlapped with the exchange. Sharding
problems in the resolved layout (an explicitly requested mesh axis that
does not divide the domain, a halo wider than the whole domain axis)
raise ``ValueError`` here, before any ``pallas_call``; a halo wider
than one *shard* is fine — the exchange chains ppermute hops across as
many neighbors as it spans. A *default* spec
follows the rule tables' divisibility fallback and leaves a
non-dividing axis replicated instead. Autotuning under a mesh targets
the *shard-local* halo-extended shape, so the winner is exactly the
per-device kernel.

Fusion surfaces (DESIGN.md §11): windowed ops take ``epilogue=`` /
``epilogue_args=`` — elementwise output stages (bias/gelu/silu/relu/
scale/residual_add) applied in VMEM between the accumulator flush and
the output store, killing the HBM round-trip of a conv→activation seam
— and ``ops.conv2d`` takes ``stride=`` (an output-strided grid that
computes only the kept lanes). :func:`pipeline` chains shape-preserving
windowed stages into ONE fused engine kernel via
:func:`repro.core.fuse.fuse_plans` (``fuse='auto'`` falls back to the
unfused pad-once sequence when the chain does not qualify). Scan ops
reject all of these with named pre-pallas errors — a scan's output is
also its sequential inter-block carry.

Every engine-lowered op is differentiable: the ops are ``custom_vjp``
wrappers whose backward rules rebuild the **adjoint plan**
(:mod:`repro.core.adjoint` — point-reflected taps with swapped
lead/trail for backward-input, the batch+spatial-reduce correlation for
backward-weight, time-reversed scans for the scan family) and lower it
through the same engine; sharded forward ⇒ sharded backward (reversed
ppermute pushes, psum'd weight grads). With ``autotune=True`` the
backward-input plan is tuned independently under its own §5 signature.
``impl="xla"`` keeps JAX's native AD of the oracle — the gradcheck
reference.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import adjoint as adj
from repro.core import tuning
from repro.robust import guard as rguard
from repro.core.engine import run_weight_grad_plan, run_window_plan
from repro.core.fuse import fuse_plans
from repro.core.plan import (SystolicPlan, epilogue_operand_stages,
                             normalize_epilogue)
from . import ref
from . import ssam_conv1d as _c1
from . import ssam_conv2d as _c2
from . import ssam_scan as _sc
from . import ssam_stencil2d as _s2
from . import ssam_stencil3d as _s3
from .stencils import BENCHMARKS, StencilDef


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def default_engine_impl() -> str:
    """The engine-lowered path for the current backend: compiled Mosaic
    on real TPU, the Pallas interpreter elsewhere.

    This is the layer/training default (``nn/layers.conv2d_apply``,
    ``nn/ssm.mamba_apply``): with the adjoint-plan subsystem
    (:mod:`repro.core.adjoint`) every engine op is a ``custom_vjp``
    whose backward pass lowers through the same plan engine, so model
    code no longer silently differentiates through the XLA oracle
    off-TPU. ``default_impl()`` remains the serving/oracle default
    (pjit-shardable XLA off-TPU)."""
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def _interp(impl: str) -> bool:
    if impl not in ("interpret", "pallas"):
        raise ValueError(impl)
    return impl == "interpret"


# Family blocks: the tuner's starting point, the §16.3 lattice's
# 'default' level and the other half of a half-given block. A 2-D call
# given no block at all leaves it to the TPU lowering, which sizes it
# from the call's shape (_engine_block, DESIGN.md §17).
_DEFAULTS = {
    "conv2d": tuning.KernelConfig((8, 128)),
    "conv2d_nchw": tuning.KernelConfig((8, 128)),
    "stencil2d": tuning.KernelConfig((8, 128)),
    "stencil3d": tuning.KernelConfig((4, 8, 128)),
    "conv1d": tuning.KernelConfig((128, 128)),
    "scan": tuning.KernelConfig((8, 128)),
    "recurrence": tuning.KernelConfig((8, 128)),
}


def engine_interpret() -> bool:
    """Whether engine-lowered paths should run the Pallas interpreter
    (non-TPU backends) or compiled Mosaic (real TPU)."""
    return jax.default_backend() != "tpu"


def _default_cfg(plan) -> tuning.KernelConfig:
    """Family default block config; fused-pipeline kinds fall back to the
    dimensionality default (the chain is one windowed kernel)."""
    cfg = _DEFAULTS.get(plan.kind)
    if cfg is not None:
        return cfg
    if plan.combine != "fma":
        return tuning.KernelConfig((8, 128))
    return tuning.KernelConfig((4, 8, 128) if plan.ndim_spatial == 3
                               else (8, 128))


def _strategy_plan(plan, strategy, op: str):
    """Pin a lowering strategy onto the plan IR (named pre-pallas check).

    The strategy lives on the *plan*, not on the call: adjoints and
    fused chains derive their plans with ``dataclasses.replace``, so an
    mxu forward transposes to an mxu backward with no extra plumbing
    (DESIGN.md §13). ``None``/'auto' leave the plan as-is — the
    autotuner then owns the algorithm choice.
    """
    if strategy in (None, "auto"):
        return plan
    if strategy not in ("lanes", "mxu"):
        raise ValueError(
            f"ops.{op}: strategy must be 'lanes', 'mxu', 'auto' or None, "
            f"got {strategy!r}")
    return dataclasses.replace(plan, strategy=strategy)


def _engine_block(plan, kw: dict) -> tuple[tuple[int, ...] | None, str, dict]:
    """Split family kwargs into (engine block tuple, variant, rest).

    A 2-D plan given neither ``block_h`` nor ``block_w`` gets ``None``:
    the TPU lowering sizes its block from each call's own shape
    (:func:`repro.core.engine.window_block`). A half-given block takes
    the other half from the family default.
    """
    kw = dict(kw)
    d = _default_cfg(plan).block
    if plan.kind == "conv1d":
        block = (kw.pop("block_t", d[0]), kw.pop("block_d", d[1]))
    elif plan.ndim_spatial == 3:
        block = (kw.pop("block_z", d[0]), kw.pop("block_h", d[1]),
                 kw.pop("block_w", d[2]))
    elif "block_h" in kw or "block_w" in kw:
        block = (kw.pop("block_h", d[0]), kw.pop("block_w", d[1]))
    else:
        block = None
    return block, kw.pop("variant", "shift_psum"), kw


def _engine_runner(plan, x, w, interpret, *, epi_args=(), time_steps=1,
                   backend=None):
    """Generic tuning-measurement closure: lower ``plan`` itself.

    The thin family wrappers rebuild their plan without epilogue/stride/
    stages, so ops that carry those must measure the *actual* plan — the
    kernel the tuned config will run."""
    def call(**k):
        blk, variant, rest = _engine_block(plan, dict(k))
        t = rest.pop("time_steps", time_steps)
        acc = rest.pop("acc_dtype", jnp.float32)
        strat = rest.pop("strategy", None)
        if rest:
            raise TypeError(f"unexpected kwargs for {plan.kind!r}: "
                            f"{sorted(rest)}")
        return run_window_plan(x, w, plan=plan, block=blk, variant=variant,
                               time_steps=t, interpret=interpret,
                               acc_dtype=acc, epilogue_args=epi_args,
                               strategy=strat, backend=backend)
    return call


def _epilogue_spec(epilogue, epilogue_args, op: str):
    """Normalize + validate an op's epilogue kwargs, pre-pallas."""
    stages = normalize_epilogue(epilogue)
    need = [s.op for s in epilogue_operand_stages(stages)]
    args = tuple(epilogue_args)
    if len(args) != len(need):
        raise ValueError(
            f"ops.{op}: epilogue {tuple(s.op for s in stages)} needs "
            f"{len(need)} runtime operand(s) ({need}) in epilogue_args, "
            f"got {len(args)}")
    return stages, args


def _check_epilogue_operands(plan, args, op: str, x, w=None,
                             time_steps: int = 1) -> None:
    """Named pre-pallas shape validation of epilogue operands.

    Bias follows the plan's layout — per-C_out for out-axes plans,
    per-lane for perlane plans, a scalar otherwise — and a residual
    must be shaped exactly like the op's output. Raised here so the
    failure names the op instead of surfacing as an assert/BlockSpec
    error inside the jitted engine (the mesh path included).
    """
    nb, nr, no = plan.batch_axes, plan.reduce_axes, plan.out_axes
    out_sp = plan.out_shape(tuple(x.shape[nb + nr:]), time_steps)
    for st, arr in zip(epilogue_operand_stages(plan.final_epilogue()), args):
        shape = tuple(getattr(arr, "shape", ()))
        if st.op == "bias":
            if no:
                want = tuple(w.shape[:no])
                what = f"a per-C_out {want} row"
            elif plan.coeff_mode == "perlane":
                want = (x.shape[-1],)
                what = f"a per-channel {want} row (channels are the lanes)"
            else:
                if _shape_size(shape) == 1:
                    continue
                raise ValueError(
                    f"ops.{op}: bias epilogue wants a scalar for "
                    f"{plan.kind!r} plans (no channel axis), got shape "
                    f"{shape}")
            if shape != want:
                raise ValueError(
                    f"ops.{op}: bias epilogue wants {what}, got shape "
                    f"{shape}")
        elif st.op == "residual_add":
            want = tuple(x.shape[:nb]) + (tuple(w.shape[:no]) if no
                                          else ()) + out_sp
            if shape != want:
                raise ValueError(
                    f"ops.{op}: residual_add epilogue wants an "
                    f"output-shaped {want} operand, got shape {shape}")


def _shape_size(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _check_backend(backend, op: str):
    """Named pre-pallas validation of an op's ``backend=`` kwarg.

    ``None`` defers to :func:`repro.config.engine_backend` at engine
    dispatch time; 'auto'/'tpu'/'gpu' pass through unresolved (the
    engine resolves 'auto' per call) but unknown names fail here with
    the op's name instead of deep inside a jitted engine call."""
    if backend is not None:
        from repro.config import resolve_engine_backend
        try:
            resolve_engine_backend(backend)
        except ValueError as e:
            raise ValueError(f"ops.{op}: {e}") from None
    return backend


def _reject_sharded_residual(epi_stages, mesh) -> None:
    """Shared mesh guard: an output-shaped residual cannot replicate."""
    if mesh is not None and any(s.op == "residual_add" for s in epi_stages):
        raise ValueError(
            "a residual_add epilogue cannot ride a sharded call: the "
            "residual operand is output-shaped and would need the same "
            "sharding; add the residual outside the mesh call")


# ---------------------------------------------------------------------------
# Differentiable engine cores (custom_vjp over adjoint plans)
#
# Every engine-lowered op routes through one of these wrappers. The
# forward is exactly the plan engine (single-device ``run_window_plan``
# or the sharded halo-exchange layer); the backward rule rebuilds the
# *adjoint* plan symbolically (:mod:`repro.core.adjoint`) and lowers it
# through the same engine — point-reflected taps with swapped lead/trail
# for backward-input, the batch+spatial-reduce correlation
# (``run_weight_grad_plan``) for backward-weight, time-reversed scans
# for the scan family. Sharded forward ⇒ sharded backward: the adjoint
# plan's swapped lead/trail reverses the ppermute halo pushes through
# the unchanged halo-exchange layer, and the weight grad psums partial
# filter blocks across the mesh.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _WindowCfg:
    """Static (nondiff) configuration of one windowed engine call."""

    plan: SystolicPlan
    block: tuple[int, ...] | None    # None: sized per call by the lowering
    time_steps: int = 1
    variant: str = "shift_psum"
    interpret: bool = True
    acc_dtype: object = jnp.float32
    mesh: object = None              # jax.sharding.Mesh | None
    in_specs: object = None          # PartitionSpec | None (rule-table default)
    boundary: str = "zero"
    overlap: bool = True
    bwd_tune: tuple | None = None    # tuner context → adjoint tuned on its
    #                                  own plan signature; None → reuse block
    backend: str | None = None       # engine lowering ("tpu"/"gpu"/"auto");
    #                                  None follows config.engine_backend()


def _window_forward(cfg: _WindowCfg, x, w, epi=()):
    if cfg.mesh is not None:
        from repro.distributed import halo_exchange as hx
        return hx.sharded_window_plan(
            x, w, plan=cfg.plan, mesh=cfg.mesh, in_spec=cfg.in_specs,
            block=cfg.block, time_steps=cfg.time_steps, variant=cfg.variant,
            boundary=cfg.boundary, overlap=cfg.overlap,
            interpret=cfg.interpret, acc_dtype=cfg.acc_dtype,
            epilogue_args=epi, backend=cfg.backend)
    return run_window_plan(
        x, w, plan=cfg.plan, block=cfg.block, time_steps=cfg.time_steps,
        variant=cfg.variant, interpret=cfg.interpret, acc_dtype=cfg.acc_dtype,
        epilogue_args=epi, backend=cfg.backend)


def _tuned_adjoint_config(aplan, g_shape, g_dtype, w, cfg: _WindowCfg):
    """Tune the backward-input plan independently of the forward.

    The adjoint is a *different* kernel (its own taps/halo), so it gets
    its own §5 tuner/sidecar signature; measurement runs on zeros of the
    cotangent's (static) shape, which keeps it legal even while the
    backward pass itself is being traced under jit.
    """
    zeros = jnp.zeros(g_shape, g_dtype)
    wa = None if w is None else adj.adjoint_coeff_array(
        cfg.plan, jnp.zeros(w.shape, w.dtype))
    runner = lambda c: tuning.measure_us(lambda: run_window_plan(
        zeros, wa, plan=aplan, block=c.block, time_steps=cfg.time_steps,
        variant=c.variant, interpret=cfg.interpret, acc_dtype=cfg.acc_dtype,
        strategy=c.strategy, backend=cfg.backend))
    res = tuning.autotune(
        aplan, g_shape, time_steps=cfg.time_steps,
        default=tuning.KernelConfig(cfg.block, cfg.variant), runner=runner,
        context=cfg.bwd_tune, backend=cfg.backend)
    return res.config.block, res.config.variant, res.config.strategy


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _window_op(cfg: _WindowCfg, x, w, epi):
    return _window_forward(cfg, x, w, epi)


# custom_vjp rules run at (backward) trace time, so these spans mark
# one adjoint derivation + lowering each, not per-step runtime.
@obs.trace.traced("ops.window_fwd", cat="ops")
def _window_op_fwd(cfg, x, w, epi):
    return _window_forward(cfg, x, w, epi), (x, w, epi)


@obs.trace.traced("ops.window_bwd", cat="ops")
def _window_op_bwd(cfg, res, g):
    x, w, epi = res
    plan = cfg.plan
    if plan.stages:
        return _pipeline_bwd(cfg, x, w, epi, g)
    if cfg.time_steps != 1 and plan.coeff_mode != "table":
        raise ValueError(
            "gradients of temporally-blocked convolutions are not "
            "supported (the weight enters every fused iterate); stencil "
            "plans (compile-time coefficients) differentiate at any "
            "time_steps")
    depi = ()
    if plan.epilogue:
        # The epilogue makes the op affine/nonlinear: recompute the
        # pre-activation z with the *linear* plan, differentiate the
        # elementwise chain there, and feed the remaining cotangent to
        # the linear adjoint plan below (DESIGN.md §11.4).
        lin_plan = dataclasses.replace(plan, epilogue=())
        lin_cfg = dataclasses.replace(cfg, plan=lin_plan)
        z = _window_forward(lin_cfg, x, w, ())
        _, epi_vjp = jax.vjp(
            lambda zz, aa: adj.apply_epilogue(plan, zz, aa), z, epi)
        g, depi = epi_vjp(g.astype(z.dtype))
        plan, cfg = lin_plan, lin_cfg
    if any(v > 1 for v in plan.stride_per_axis()):
        # Transpose of the output-strided grid: scatter the cotangent
        # into the dense output lattice (zeros between kept lanes), then
        # transpose the stride-free plan through the engine as usual.
        dense_plan = dataclasses.replace(plan, stride=None)
        nb, nr = plan.batch_axes, plan.reduce_axes
        dense_out = dense_plan.out_shape(x.shape[nb + nr:], 1)
        lead_nd = g.ndim - plan.ndim_spatial
        gd = jnp.zeros(g.shape[:lead_nd] + dense_out, g.dtype)
        g = gd.at[(slice(None),) * lead_nd + tuple(
            slice(None, None, v) for v in plan.stride_per_axis())].set(g)
        plan = dense_plan
        cfg = dataclasses.replace(cfg, plan=dense_plan)
    if cfg.boundary == "replicate" and cfg.mesh is not None:
        return _replicate_bwd(cfg, plan, x, w, g, depi)
    aplan = adj.input_adjoint_plan(plan)
    block, variant = cfg.block, cfg.variant
    if cfg.bwd_tune is not None and cfg.mesh is None:
        block, variant, astrat = _tuned_adjoint_config(
            aplan, g.shape, g.dtype, w, cfg)
        if astrat is not None:
            # the adjoint is its own kernel: when the forward was auto,
            # the backward tuner picks the adjoint's strategy on the
            # adjoint's own signature (a pinned forward stays pinned —
            # input_adjoint_plan carried the strategy over already)
            aplan = dataclasses.replace(aplan, strategy=astrat)
    acfg = dataclasses.replace(cfg, plan=aplan, block=block, variant=variant,
                               bwd_tune=None)
    adj.record_lowering(aplan.kind)
    dx = _window_forward(acfg, g, adj.adjoint_coeff_array(plan, w))
    dx = dx.astype(x.dtype)
    if w is None or plan.coeff_mode == "table":
        return dx, None, depi
    adj.record_lowering(adj.weight_adjoint_plan(plan).kind)
    wg_block = _wgrad_block(cfg)
    if cfg.mesh is not None:
        from repro.distributed import halo_exchange as hx
        dw = hx.sharded_weight_grad(
            x, g, plan=plan, mesh=cfg.mesh, in_spec=cfg.in_specs,
            block=wg_block, boundary=cfg.boundary, interpret=cfg.interpret,
            acc_dtype=cfg.acc_dtype)
    else:
        dw = run_weight_grad_plan(
            x, g, plan=plan, block=wg_block, interpret=cfg.interpret,
            acc_dtype=cfg.acc_dtype)
    return dx, dw.astype(w.dtype), depi


def _wgrad_block(cfg: _WindowCfg) -> tuple[int, int]:
    """The weight-gradient tile: the forward's block, or the family
    default where the forward's block is sized per call."""
    return (cfg.block or _default_cfg(cfg.plan).block)[-2:]


def _replicate_bwd(cfg, plan, x, w, g, depi):
    """Backward of a ``boundary='replicate'`` (edge-clamp) sharded call.

    The forward is ``y = V(E x)``: the valid-mode plan ``V`` on the
    edge-extended input ``E x``. The transpose splits cleanly:
    ``dx = Eᵀ(Vᵀ g)``. ``Vᵀ`` is the input adjoint of the valid-mode
    plan — a full-mode kernel whose output lives on the *widened*
    lattice (``N + lead + trail`` rows per axis); that lattice does not
    divide the mesh, so this one backward kernel runs unsharded on the
    gathered cotangent. ``Eᵀ`` then folds the halo bands back onto the
    edge rows they were clamped from
    (:func:`repro.core.adjoint.fold_replicate_edges`). The weight grad
    needs no transpose at all — it is the same correlation against the
    edge-extended input the forward saw — so it reuses the sharded
    halo-exchange correlation with the replicate slabs unchanged.
    """
    valid = dataclasses.replace(plan, lead=None, trail=None)
    aplan = adj.input_adjoint_plan(valid)
    adj.record_lowering(aplan.kind)
    dxp = run_window_plan(
        g, adj.adjoint_coeff_array(valid, w), plan=aplan, block=cfg.block,
        variant=cfg.variant, interpret=cfg.interpret,
        acc_dtype=cfg.acc_dtype, backend=cfg.backend)
    dx = adj.fold_replicate_edges(plan, dxp).astype(x.dtype)
    if w is None or plan.coeff_mode == "table":
        return dx, None, depi
    from repro.distributed import halo_exchange as hx
    adj.record_lowering(adj.weight_adjoint_plan(plan).kind)
    dw = hx.sharded_weight_grad(
        x, g, plan=plan, mesh=cfg.mesh, in_spec=cfg.in_specs,
        block=_wgrad_block(cfg), boundary=cfg.boundary,
        interpret=cfg.interpret, acc_dtype=cfg.acc_dtype)
    return dx, dw.astype(w.dtype), depi


def _pipeline_bwd(cfg, x, ws, epi, g):
    """Backward of a fused pipeline: stage-by-stage in reverse.

    A purely linear table-coefficient chain transposes to ONE fused
    adjoint kernel (the reversed chain of stage adjoints, DESIGN.md
    §11.4). Chains with epilogues or dense weights recompute the
    pad-once stage inputs/pre-activations forward (engine calls on the
    valid-mode stage plans), then walk the chain backwards: epilogue
    VJPs at the saved pre-activations, per-stage weight-grad
    correlations, and each stage's input-adjoint plan — every linear
    piece lowers through the engine, so training stays on the engine
    path end-to-end.
    """
    plan = cfg.plan
    stages = plan.stages
    if cfg.mesh is not None:
        raise ValueError(
            "gradients of a sharded fused pipeline are not supported yet; "
            "train with fuse=False under a mesh (per-stage sharded "
            "adjoints) or shard the fused forward only")
    if (not any(s.epilogue for s in stages)
            and all(s.coeff_mode == "table" for s in stages)):
        aplan = adj.input_adjoint_plan(plan)        # fused reversed chain
        adj.record_lowering(aplan.kind)
        acfg = dataclasses.replace(cfg, plan=aplan, bwd_tune=None)
        dx = _window_forward(acfg, g, tuple(None for _ in stages), ())
        return dx.astype(x.dtype), tuple(None for _ in stages), ()

    lead, trail = plan.lead_trail()
    nb = plan.batch_axes
    pads = [(0, 0)] * nb + [(l, r) for l, r in zip(lead, trail)]
    h = jnp.pad(x, pads)
    epi_splits = _pipeline_epi_splits(stages, epi)
    hs, zs, valids = [], [], []
    for i, s in enumerate(stages):
        sv = dataclasses.replace(s, lead=None, trail=None, epilogue=())
        w_s = ws[i] if s.coeff_mode == "dense" else None
        hs.append(h)
        valids.append(sv)
        z = run_window_plan(h, w_s, plan=sv, block=cfg.block,
                            variant=cfg.variant, interpret=cfg.interpret,
                            acc_dtype=cfg.acc_dtype, backend=cfg.backend)
        se = dataclasses.replace(sv, epilogue=s.epilogue)
        h = adj.apply_epilogue(se, z, epi_splits[i]).astype(x.dtype)
        zs.append(z)

    depi_parts = [()] * len(stages)
    dws = [None] * len(stages)
    for i in reversed(range(len(stages))):
        s, sv = stages[i], valids[i]
        if s.epilogue:
            se = dataclasses.replace(sv, epilogue=s.epilogue)
            _, epi_vjp = jax.vjp(
                lambda zz, aa, _se=se: adj.apply_epilogue(_se, zz, aa),
                zs[i], epi_splits[i])
            g, depi_parts[i] = epi_vjp(g.astype(zs[i].dtype))
        if s.coeff_mode == "dense":
            adj.record_lowering("wgrad_" + sv.kind)
            dws[i] = run_weight_grad_plan(
                hs[i], g, plan=sv, block=_wgrad_block(cfg),
                interpret=cfg.interpret,
                acc_dtype=cfg.acc_dtype).astype(ws[i].dtype)
        ap = adj.input_adjoint_plan(sv)     # valid ⇒ full: output grows back
        adj.record_lowering(ap.kind)
        g = run_window_plan(
            g, ws[i] if s.coeff_mode == "dense" else None, plan=ap,
            block=cfg.block, variant=cfg.variant, interpret=cfg.interpret,
            acc_dtype=cfg.acc_dtype, backend=cfg.backend).astype(x.dtype)
    # transpose of the pad-once zero pad: crop the summed lead/trail;
    # epilogue-operand cotangents reassemble in chain order
    depi = tuple(d for part in depi_parts for d in part)
    sl = (slice(None),) * nb + tuple(
        slice(l, l + n) for l, n in zip(lead, x.shape[nb:]))
    return g[sl].astype(x.dtype), tuple(dws), depi


_window_op.defvjp(_window_op_fwd, _window_op_bwd)


# ---------------------------------------------------------------------------
# Guarded dispatch: the degradation lattice (DESIGN.md §16.3)
#
# Every engine-lowered ops.* surface routes its forward call through
# repro.robust.guard with an ordered level list: the tuned/requested
# config first, then the family default block, then the alternate
# lowering (strategy for mxu-pinned plans, the other engine backend
# otherwise), and finally the pure-XLA reference oracle that shares no
# lowering code with the engine. Each step down gives up performance
# before it gives up the engine, and gives up the engine before it
# gives up the answer. Fallback configs are built lazily inside their
# thunks, so the no-failure path pays only closure creation; under
# on_failure='raise' the guard surfaces injected faults as structured
# errors and re-raises organic exceptions (validation ValueErrors etc.)
# completely unchanged.
#
# Scope: the *forward* dispatch is guarded. custom_vjp backward rules
# lower through the same engine but outside the lattice — an adjoint
# failure surfaces under both policies (a silently-demoted gradient
# would be worse than a loud one).
# ---------------------------------------------------------------------------


def _flip_backend(backend) -> str:
    """The other engine lowering: resolve the effective backend, flip it."""
    from repro.config import engine_backend, resolve_engine_backend
    cur = (resolve_engine_backend(backend) if backend is not None
           else engine_backend())
    return "tpu" if cur == "gpu" else "gpu"


def _safe_variant(plan) -> str:
    """The variant the default/alternate levels retreat to: strided grids
    require the data-stationary read; everything else takes shift_psum."""
    return ("shift_data" if any(v > 1 for v in plan.stride_per_axis())
            else "shift_psum")


def _guarded_window(op: str, cfg: _WindowCfg, x, w, epi, oracle=None):
    """One windowed engine call through the §16.3 lattice.

    ``oracle`` is the op's pure-XLA reference closure (same output to
    fp32 tolerance); None drops the level — used where no oracle can
    represent the call (sharded wrap/replicate boundaries). Sharded
    calls with boundary='zero' also get an ``unsharded`` level: the
    halo-exchange layer exists to make the sharded result equal the
    single-device engine, so desharding is an exact fallback when the
    collective itself is what failed.
    """
    if cfg.mesh is not None:
        # configuration errors (sharded reduce axes, non-shape-preserving
        # plans, halo-vs-shard geometry) surface before the lattice: the
        # unsharded/oracle levels drop the mesh and would otherwise
        # "recover" from user misuse by computing something else.
        from repro.distributed import halo_exchange as hx
        hx.validate_sharded_call(x, cfg.plan, cfg.mesh, cfg.in_specs,
                                 time_steps=cfg.time_steps,
                                 boundary=cfg.boundary)

    def default_level():
        c = dataclasses.replace(cfg, block=_default_cfg(cfg.plan).block,
                                variant=_safe_variant(cfg.plan),
                                bwd_tune=None)
        return _window_op(c, x, w, epi)

    def alternate_level():
        c = dataclasses.replace(cfg, block=_default_cfg(cfg.plan).block,
                                variant=_safe_variant(cfg.plan),
                                bwd_tune=None)
        if (c.plan.strategy or "lanes") == "mxu":
            # an mxu lowering bug: retreat to the paper's VPU schedule
            c = dataclasses.replace(
                c, plan=dataclasses.replace(c.plan, strategy="lanes"))
        else:
            c = dataclasses.replace(c, backend=_flip_backend(c.backend))
        return _window_op(c, x, w, epi)

    levels = [
        ("tuned", lambda: _window_op(cfg, x, w, epi)),
        ("default", default_level),
        ("alternate", alternate_level),
    ]
    if cfg.mesh is not None and cfg.boundary == "zero":
        levels.append(("unsharded", lambda: _window_op(
            dataclasses.replace(cfg, mesh=None, in_specs=None), x, w, epi)))
    if oracle is not None and (cfg.mesh is None or cfg.boundary == "zero"):
        levels.append(("oracle", oracle))
    return rguard.run(op, levels)


def _guarded_scan(op: str, cfg: _ScanCfg, call, oracle=None):
    """One scan engine call through the lattice: tuned block → default
    (8, 128) block → the other backend → reference oracle. ``call`` maps
    a (possibly demoted) :class:`_ScanCfg` to the engine invocation, so
    the same helper serves monolithic and chunk-streamed schedules."""
    d = _DEFAULTS["scan"].block
    bt = min(d[1], cfg.chunk) if cfg.chunk else d[1]

    def default_level():
        return call(dataclasses.replace(cfg, block_r=d[0], block_t=bt))

    def alternate_level():
        return call(dataclasses.replace(
            cfg, block_r=d[0], block_t=bt,
            backend=_flip_backend(cfg.backend)))

    levels = [("tuned", lambda: call(cfg)),
              ("default", default_level),
              ("alternate", alternate_level)]
    if oracle is not None:
        levels.append(("oracle", oracle))
    return rguard.run(op, levels)


@dataclasses.dataclass(frozen=True)
class _ScanCfg:
    """Static configuration of one scan-engine call.

    ``chunk`` selects the chunk-streamed schedule (DESIGN.md §12): the
    sequence axis streams through a ``lax.scan`` in ``(R, chunk)`` slabs
    with the inter-chunk carry as the scan state — O(R·chunk) live
    state. ``None`` keeps the monolithic O(R·T) lowering.
    """

    block_r: int = 8
    block_t: int = 128
    interpret: bool = True
    acc_dtype: object = jnp.float32
    chunk: int | None = None
    backend: str | None = None       # engine lowering; None → config default


def _cumsum_run(cfg: _ScanCfg, x):
    return _sc.cumsum(x, block_r=cfg.block_r, block_t=cfg.block_t,
                      interpret=cfg.interpret, acc_dtype=cfg.acc_dtype,
                      backend=cfg.backend)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cumsum_op(cfg: _ScanCfg, x):
    return _cumsum_run(cfg, x)


@obs.trace.traced("ops.cumsum_fwd", cat="ops")
def _cumsum_op_fwd(cfg, x):
    return _cumsum_run(cfg, x), None


@obs.trace.traced("ops.cumsum_bwd", cat="ops")
def _cumsum_op_bwd(cfg, _, g):
    # (cumsum)ᵀ = the time-reversed scan plan: rev ∘ cumsum ∘ rev.
    adj.record_lowering("adj_scan")
    return (adj.time_reversed(_cumsum_run(cfg, adj.time_reversed(g))),)


_cumsum_op.defvjp(_cumsum_op_fwd, _cumsum_op_bwd)


def _linrec_run(cfg: _ScanCfg, a, b):
    return _sc.linear_recurrence(a, b, block_r=cfg.block_r,
                                 block_t=cfg.block_t,
                                 interpret=cfg.interpret,
                                 acc_dtype=cfg.acc_dtype,
                                 backend=cfg.backend)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linrec_op(cfg: _ScanCfg, a, b):
    return _linrec_run(cfg, a, b)


@obs.trace.traced("ops.linrec_fwd", cat="ops")
def _linrec_op_fwd(cfg, a, b):
    h = _linrec_run(cfg, a, b)
    return h, (a, h)


@obs.trace.traced("ops.linrec_bwd", cat="ops")
def _linrec_op_bwd(cfg, res, g):
    # λ_t = g_t + a_{t+1}·λ_{t+1}: the same recurrence, time-reversed,
    # with shifted coefficients — lowered through the same scan engine.
    a, h = res
    adj.record_lowering("adj_recurrence")
    abar = adj.reversed_recurrence_coeffs(a)
    lam = adj.time_reversed(_linrec_run(
        cfg, adj.time_reversed(abar), adj.time_reversed(g)))
    da = (lam.astype(jnp.float32)
          * adj.shifted_state(h).astype(jnp.float32)).astype(a.dtype)
    return da, lam.astype(a.dtype)


_linrec_op.defvjp(_linrec_op_fwd, _linrec_op_bwd)


def _linrec_carry_run(cfg: _ScanCfg, a, b, h0):
    return _sc.linear_recurrence(a, b, block_r=cfg.block_r,
                                 block_t=cfg.block_t,
                                 interpret=cfg.interpret,
                                 acc_dtype=cfg.acc_dtype,
                                 carry=h0, return_carry=True,
                                 backend=cfg.backend)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linrec_carry_op(cfg: _ScanCfg, a, b, h0):
    """One chunk of the streamed recurrence: ``(h, h_T)`` from carry ``h0``."""
    return _linrec_carry_run(cfg, a, b, h0)


@obs.trace.traced("ops.linrec_carry_fwd", cat="ops")
def _linrec_carry_op_fwd(cfg, a, b, h0):
    h, hT = _linrec_carry_run(cfg, a, b, h0)
    return (h, hT), (a, h, h0)


@obs.trace.traced("ops.linrec_carry_bwd", cat="ops")
def _linrec_carry_op_bwd(cfg, res, cts):
    # Chunk-local adjoint (DESIGN.md §12): the carry-out cotangent gc
    # folds into the last in-chunk λ seed (h_T *is* h[:, -1]), the λ
    # recurrence runs reversed through the same engine, and the carry-in
    # cotangent a₀·λ₀ exits as this chunk's gc for the next-older chunk —
    # lax.scan's carry cotangent streams it, no O(T) state saved.
    a, h, h0 = res
    g, gc = cts
    adj.record_lowering("adj_recurrence_chunk")
    g = g.astype(jnp.float32).at[..., -1:].add(
        gc.astype(jnp.float32).reshape(g.shape[:-1] + (1,)))
    abar = adj.reversed_recurrence_coeffs(a)
    lam = adj.time_reversed(_linrec_run(
        cfg, adj.time_reversed(abar), adj.time_reversed(g)))
    da = (lam.astype(jnp.float32)
          * adj.shifted_state(h, h0).astype(jnp.float32)).astype(a.dtype)
    dh0 = adj.chunk_carry_cotangent(a, lam).astype(h0.dtype).reshape(h0.shape)
    return da, lam.astype(a.dtype), dh0


_linrec_carry_op.defvjp(_linrec_carry_op_fwd, _linrec_carry_op_bwd)


def linear_recurrence_carry(a, b, h0, *, impl: str | None = None, **kw):
    """``h_t = a_t·h_{t−1} + b_t`` over (R, T) rows with explicit carry.

    Returns ``(h, h_T)`` where ``h_T`` is the final raw state ``(R, 1)``;
    ``h0`` is ``(R,)`` or ``(R, 1)``. This is the per-chunk engine
    primitive of the streamed schedule (DESIGN.md §12): differentiable
    both through ``h`` and through the carry pair, so ``lax.scan`` over
    chunks composes the λ-recurrence across chunk boundaries for free.
    """
    _reject_scan_kwargs("linear_recurrence_carry", kw)
    impl = impl or default_engine_impl()
    interpret = _interp(impl)
    cfg = _scan_cfg(kw, interpret=interpret, op="linear_recurrence_carry")
    h0c = h0.reshape(a.shape[0], 1)

    def oracle():
        # fold the carry into the first step: h_1 = a_1·h_0 + b_1
        b2 = b.at[:, :1].add(a[:, :1] * h0c)
        h = ref.linear_recurrence(a, b2)
        return h, h[:, -1:]

    return _guarded_scan("linear_recurrence_carry",
                         dataclasses.replace(cfg, chunk=None),
                         lambda c: _linrec_carry_op(c, a, b, h0c), oracle)


def _linrec_stream(cfg: _ScanCfg, a, b):
    """Stream ``(R, T)`` rows through ``(R, chunk)`` engine slabs.

    ``lax.scan`` carries the per-row state between chunks; the body is
    ``jax.checkpoint``-wrapped so reverse-mode saves only the O(T/chunk)
    chunk-boundary carries and re-runs each chunk's engine kernel to
    recover in-chunk state — both directions engine-lowered, peak live
    state O(R·chunk).
    """
    R, T = a.shape
    chunk = cfg.chunk
    nc = -(-T // chunk)
    pad = ((0, 0), (0, nc * chunk - T))
    ap = jnp.pad(a, pad, constant_values=1)   # identity transfers in the tail
    bp = jnp.pad(b, pad)
    inner = dataclasses.replace(cfg, chunk=None)

    def body(c, i):
        asl = jax.lax.dynamic_slice_in_dim(ap, i * chunk, chunk, 1)
        bsl = jax.lax.dynamic_slice_in_dim(bp, i * chunk, chunk, 1)
        h, c_new = _linrec_carry_op(inner, asl, bsl, c)
        return c_new, h

    c0 = jnp.zeros((R, 1), a.dtype)
    _, hs = jax.lax.scan(jax.checkpoint(body), c0, jnp.arange(nc))
    return jnp.moveaxis(hs, 0, 1).reshape(R, nc * chunk)[:, :T]


def _shard_tuning_call(plan, x, mesh, in_specs, time_steps, boundary):
    """(shape, context) the sharded autotune must target: the per-device
    halo-extended block, keyed so winners never leak across meshes or
    boundary modes. For batched plans the leading batch axes shrink to
    their per-shard extent (reduce axes are never sharded)."""
    from repro.distributed import halo_exchange as hx
    spec = in_specs if in_specs is not None else \
        hx.default_plan_spec(plan, x.shape, mesh)
    nb, nr = plan.batch_axes, plan.reduce_axes
    assigns = hx._axis_assignments(spec, mesh, nb + nr + plan.ndim_spatial)
    spatial = tuning.shard_tuning_shape(
        plan, x.shape[nb + nr:], assigns[nb + nr:], time_steps, boundary)
    shape = tuple(
        n // (a[1] if a else 1)
        for n, a in zip(x.shape[:nb], assigns[:nb])
    ) + x.shape[nb:nb + nr] + spatial
    return shape, ("sharded", boundary) + tuple(
        f"{a[0]}:{a[1]}" if a else "-" for a in assigns)


def _measured(operands):
    """``operands`` as the tuner's timing runs see them: values only.
    Under ``jax.grad`` a traced operand would pull the timed kernel calls
    into the linearization, which fails; timing needs no derivative."""
    return jax.lax.stop_gradient(operands)


def _tuned_kwargs(plan, shape, call, user_kw, *, time_steps: int = 1,
                  context: tuple = (), chunked: bool = False,
                  default=None, backend=None) -> dict:
    """Autotune block kwargs for ``call``; explicit user kwargs win.

    The cache context carries everything that changes what the runner
    measures beyond (plan, shape): op mode/impl and any caller-forced
    kwargs — without it a winner measured under one context would be
    silently replayed under another. ``chunked=True`` tunes the streamed
    scan schedule: candidates grow the chunk-length dimension
    (``(BR, BT, chunk)``, DESIGN.md §12).
    """
    runner = lambda cfg: tuning.measure_us(
        lambda: call(**{**cfg.as_kwargs(plan), **user_kw}))
    res = tuning.autotune(plan, shape, time_steps=time_steps,
                          default=default or _default_cfg(plan),
                          runner=runner,
                          context=context + tuple(sorted(user_kw.items())),
                          fixed=user_kw, chunked=chunked, backend=backend)
    return {**res.config.as_kwargs(plan), **user_kw}


def _conv2d_grouped(x, w, *, groups, mode, impl, autotune, mesh, stride,
                    epi_stages, epi_args, strategy, backend, kw):
    """Grouped NCHW conv as per-group reduce slices (ISSUE 7 satellite).

    Each group is an ordinary reduce-axes conv on its
    ``(C_in/groups, C_out/groups)`` operand slice — every group lowers
    the *same* plan signature, so the tuner measures group 0 and replays
    the winner for the rest — and the group outputs concatenate along
    C_out. Per-C_out epilogue operands (a bias row, a residual) slice
    along the same axis. ``groups == C_in`` is depthwise-2d.
    """
    if x.ndim != 4:
        raise ValueError(
            f"conv2d: groups={groups} needs a 4-D NCHW input against an "
            f"OIHW filter (grouped channels), got a {x.ndim}-D input")
    if w.ndim != 4:
        raise ValueError(
            f"conv2d: groups={groups} needs an OIHW "
            f"(C_out, C_in/groups, N, M) filter, got w shape "
            f"{tuple(w.shape)}")
    if mesh is not None:
        raise ValueError(
            "sharded grouped conv2d is not supported: each group is its "
            "own engine call and would need its own halo exchange; run "
            "groups under pjit with impl='xla', or shard with groups=1")
    # the plan builder owns the named divisibility checks (pre-pallas)
    plan = _c2.plan_for_nchw(x.shape, w.shape, mode, groups)
    if impl == "xla":
        y = ref.conv2d_nchw(x, w, mode, groups)
        if stride is not None:
            y = y[..., ::stride[0], ::stride[1]]
        if epi_stages:
            y = adj.apply_epilogue(
                dataclasses.replace(plan, epilogue=epi_stages), y, epi_args)
        return y
    Cg = x.shape[1] // groups
    Og = w.shape[0] // groups
    op_stages = epilogue_operand_stages(epi_stages)
    outs = []
    for g in range(groups):
        args_g = tuple(
            arr[g * Og:(g + 1) * Og]
            if (st.op == "bias" and getattr(arr, "ndim", 0) == 1)
            else (arr[:, g * Og:(g + 1) * Og] if st.op == "residual_add"
                  else arr)
            for st, arr in zip(op_stages, epi_args))
        outs.append(conv2d(
            x[:, g * Cg:(g + 1) * Cg], w[g * Og:(g + 1) * Og], mode=mode,
            impl=impl, autotune=autotune, stride=stride,
            epilogue=epi_stages, epilogue_args=args_g, strategy=strategy,
            backend=backend, **kw))
    return jnp.concatenate(outs, axis=1)


def conv2d(x, w, *, mode: str = "same", impl: str | None = None,
           autotune: bool = False, mesh=None, in_specs=None,
           boundary: str = "zero", stride=None, epilogue=None,
           epilogue_args=(), strategy: str | None = None, groups: int = 1,
           backend: str | None = None, **kw):
    """2-D convolution, dispatched on input rank:

    * ``(H, W)``            — single image, single channel (the paper's
      Listing 1 plan).
    * ``(B, H, W)``         — minibatch of single-channel images against
      one ``(N, M)`` filter (block-1 batch grid axis).
    * ``(B, C_in, H, W)``   — NCHW minibatch against an OIHW
      ``(C_out, C_in, N, M)`` filter through the reduce-axes plan: the
      engine grid iterates batch × C_out × spatial × C_in with an fp32
      accumulator across the channel reduction — no Python loop over
      batch or channels.

    ``stride=(sh, sw)`` lowers an **output-strided grid**: the kernel
    computes only every ``s``-th output lane instead of the dense result
    a subsample would discard (DESIGN.md §11.3). ``epilogue=`` fuses
    elementwise output stages (``bias``/``gelu``/``silu``/``relu``/
    ``scale``/``residual_add``) into the kernel between the accumulator
    flush and the output store; runtime operands (a per-C_out bias row,
    a residual) ride in ``epilogue_args``. Both key the tuner cache
    apart automatically (the plan signature carries them).

    ``strategy=`` pins the engine's lowering for the tap-set contraction
    ('lanes' — the paper's VPU shift schedule — or 'mxu', the im2row
    dot_general of DESIGN.md §13); the default/'auto' leaves the choice
    to the autotuner (falling back to 'lanes' untuned). ``groups=``
    (4-D NCHW only) runs a grouped convolution as per-group reduce
    slices — ``groups == C_in`` is depthwise-2d — with an OIHW filter of
    shape ``(C_out, C_in/groups, N, M)``, matching ``lax``'s
    ``feature_group_count``.

    Tuner contexts carry the rank tag and the full operand shape, so
    batched/NCHW winners never collide with single-image winners in the
    cache or the JSON sidecar.

    ``backend=`` selects the engine *lowering* of the plan ('tpu' — the
    sublane/lane tiling — or 'gpu' — the §14 warp-shuffle tiling;
    'auto' follows the jax platform, ``None`` the
    ``repro.config.engine_backend()`` session default). Orthogonal to
    ``impl``: interpret-mode runs either lowering on any host. Tuned
    winners are cached and sidecar'd per backend (DESIGN.md §14).
    """
    impl = impl or default_impl()
    backend = _check_backend(backend, "conv2d")
    epi_stages, epi_args = _epilogue_spec(epilogue, epilogue_args, "conv2d")
    if stride is not None:
        stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        if len(stride) != 2 or any(int(v) != v or v < 1 for v in stride):
            raise ValueError(f"conv2d: stride must be two ints >= 1, "
                             f"got {stride}")
        stride = tuple(int(v) for v in stride)
        if stride == (1, 1):
            stride = None
    if mesh is not None and stride is not None:
        raise ValueError(
            "sharded strided conv2d is not supported: an output stride "
            "breaks shape preservation, so shards would not own equal "
            "input and output slices; subsample after the sharded call")
    _reject_sharded_residual(epi_stages, mesh)
    if int(groups) != groups or groups < 1:
        raise ValueError(f"conv2d: groups must be an int >= 1, got {groups}")
    if groups != 1:
        return _conv2d_grouped(
            x, w, groups=int(groups), mode=mode, impl=impl,
            autotune=autotune, mesh=mesh, stride=stride,
            epi_stages=epi_stages, epi_args=epi_args, strategy=strategy,
            backend=backend, kw=kw)
    if x.ndim == 4:
        if w.ndim != 4:
            raise ValueError(
                f"conv2d on a 4-D NCHW input needs an OIHW "
                f"(C_out, C_in, N, M) filter, got w shape {tuple(w.shape)}")
        tag = "conv2d_nchw"
        ref_fn = lambda xx, m: ref.conv2d_nchw(xx, w, m)
        plan_fn = lambda: _c2.plan_for_nchw(x.shape, w.shape, mode)
        kernel = lambda xs, ws, **k: _c2.conv2d_nchw(xs, ws, mode=mode, **k)
    elif x.ndim == 3:
        if w.ndim != 2:
            raise ValueError(
                f"conv2d on a 3-D (B, H, W) stack needs a 2-D (N, M) "
                f"filter, got w shape {tuple(w.shape)}; for a multi-channel "
                "minibatch pass a 4-D NCHW input with an OIHW filter")
        tag = "conv2d_batched"
        ref_fn = lambda xx, m: ref.conv2d_batched(xx, w, m)
        plan_fn = lambda: _c2.plan_for_batched(w.shape, mode)
        kernel = lambda xs, ws, **k: _c2.conv2d_batched(xs, ws, mode=mode,
                                                        **k)
    else:
        tag = "conv2d"
        ref_fn = lambda xx, m: (ref.conv2d_same(xx, w) if m == "same"
                                else ref.conv2d_valid(xx, w))
        plan_fn = lambda: _c2.plan_for(w.shape, mode)
        kernel = lambda xs, ws, **k: (
            _c2.conv2d_same(xs, ws, **k) if mode == "same"
            else _c2.conv2d_valid(xs, ws, **k))
    plan = _strategy_plan(plan_fn(), strategy, "conv2d")
    if stride is not None or epi_stages:
        plan = dataclasses.replace(plan, stride=stride, epilogue=epi_stages)
        _check_epilogue_operands(plan, epi_args, "conv2d", x, w)
    if impl == "xla":
        if mesh is not None:
            raise ValueError("mesh= needs the engine path; the 'xla' oracle "
                             "is already shardable under pjit")
        y = ref_fn(x, mode)
        if stride is not None:
            y = y[..., ::stride[0], ::stride[1]]
        if epi_stages:
            y = adj.apply_epilogue(plan, y, epi_args)
        return y

    def oracle():
        # the impl='xla' branch above, as the lattice's level of last
        # resort — stride subsample + epilogue replay included
        y = ref_fn(x, mode)
        if stride is not None:
            y = y[..., ::stride[0], ::stride[1]]
        if epi_stages:
            y = adj.apply_epilogue(plan, y, epi_args)
        return y

    return _conv2d_engine(x, w, plan=plan, kernel=kernel, tag=tag,
                          mode=mode, impl=impl, autotune=autotune, mesh=mesh,
                          in_specs=in_specs, boundary=boundary, kw=kw,
                          epi_args=epi_args, backend=backend, oracle=oracle)


def _window_cfg(plan, kw, *, interpret, time_steps=1, mesh=None,
                in_specs=None, boundary="zero", bwd_tune=None,
                backend=None) -> _WindowCfg:
    """Resolve family kwargs into the static config of one engine call."""
    block, variant, rest = _engine_block(plan, kw)
    # a tuned winner (or an explicit caller) may carry the lowering
    # strategy as a kwarg — it pins the plan IR, like ``stride=`` does
    plan = _strategy_plan(plan, rest.pop("strategy", None), plan.kind)
    cfg = _WindowCfg(
        plan=plan, block=block, variant=variant, interpret=interpret,
        time_steps=rest.pop("time_steps", time_steps),
        acc_dtype=rest.pop("acc_dtype", jnp.float32),
        mesh=mesh, in_specs=in_specs, boundary=boundary,
        overlap=rest.pop("overlap", True), bwd_tune=bwd_tune,
        backend=rest.pop("backend", backend))
    if rest:
        raise TypeError(f"unexpected kwargs for {plan.kind!r}: "
                        f"{sorted(rest)}")
    return cfg


def _conv2d_engine(x, w, *, plan, kernel, tag, mode, impl, autotune, mesh,
                   in_specs, boundary, kw, epi_args=(), backend=None,
                   oracle=None):
    """Shared mesh/autotune scaffolding for every conv2d rank.

    ``kernel(xs, interpret=..., **block_kwargs)`` lowers the engine call
    on ``xs`` for tuning measurements; ``plan`` is its schedule; ``tag``
    keys the tuner context. Plans carrying a stride or an epilogue are
    measured through the generic :func:`_engine_runner` instead — the
    thin wrappers would rebuild the plan without them. The actual call
    goes through the differentiable ``_window_op`` core, so ``jax.grad``
    of any conv2d rank lowers its backward pass through the adjoint
    plans.
    """
    interpret = _interp(impl)
    plain = not plan.epilogue and plan.stride is None
    # a pinned strategy must reach the thin measurement wrappers too —
    # they rebuild the plan from kwargs (candidates restate the pin, but
    # the family *default* config carries none)
    pin = {"strategy": plan.strategy} if plan.strategy else {}
    if mesh is not None:
        if mode != "same":
            raise ValueError(
                "sharded conv2d supports mode='same' only: 'valid' shrinks "
                "the domain, so shards would not own equal output slices")
        if autotune:
            shape, sctx = _shard_tuning_call(plan, x, mesh, in_specs, 1,
                                             boundary)
            zeros = jnp.zeros(shape, x.dtype)
            wm, epm = _measured((w, epi_args))
            sharded_kw = {k: kw.pop(k) for k in ("overlap",) if k in kw}
            call = (lambda **k: kernel(zeros, wm, interpret=interpret,
                                       backend=backend, **{**pin, **k})) \
                if plain else _engine_runner(plan, zeros, wm, interpret,
                                             epi_args=epm,
                                             backend=backend)
            kw = _tuned_kwargs(plan, shape, call, kw,
                               context=(tag, mode, impl) + sctx,
                               backend=backend)
            kw.update(sharded_kw)
        cfg = _window_cfg(plan, kw, interpret=interpret, mesh=mesh,
                          in_specs=in_specs, boundary=boundary,
                          backend=backend)
        return _guarded_window(tag, cfg, x, w, epi_args, oracle)
    bwd_tune = None
    if autotune:
        xm, wm, epm = _measured((x, w, epi_args))
        call = (lambda **k: kernel(xm, wm, interpret=interpret,
                                   backend=backend, **{**pin, **k})) \
            if plain else _engine_runner(plan, xm, wm, interpret,
                                         epi_args=epm, backend=backend)
        kw = _tuned_kwargs(plan, x.shape, call, kw, context=(tag, mode, impl),
                           backend=backend)
        bwd_tune = ("adjoint", tag, mode, impl)
    return _guarded_window(tag, _window_cfg(plan, kw, interpret=interpret,
                                            bwd_tune=bwd_tune,
                                            backend=backend),
                           x, w, epi_args, oracle)


def conv1d_causal(x, w, *, impl: str | None = None, autotune: bool = False,
                  epilogue=None, epilogue_args=(), strategy: str | None = None,
                  backend: str | None = None, **kw):
    """Depthwise causal conv through the D-optimal plan (§5.4).

    ``epilogue=`` fuses elementwise output stages into the kernel —
    ``bias`` takes a per-channel ``(D,)`` row (channels are the plan's
    lanes), which is exactly Mamba's ``conv → +b → silu`` seam without
    the HBM round-trip between the conv and the activation.
    """
    impl = impl or default_impl()
    backend = _check_backend(backend, "conv1d_causal")
    if w.shape[-1] != x.shape[-1]:
        # checked for every impl — the oracle would otherwise silently
        # broadcast a mismatched filter across channels
        raise ValueError(f"conv1d_causal: filter lanes {w.shape} do not "
                         f"match input channels {x.shape}")
    epi_stages, epi_args = _epilogue_spec(epilogue, epilogue_args,
                                          "conv1d_causal")
    plan = _strategy_plan(_c1.plan_for(w.shape[0]), strategy,
                          "conv1d_causal")
    if epi_stages:
        plan = dataclasses.replace(plan, epilogue=epi_stages)
        _check_epilogue_operands(plan, epi_args, "conv1d_causal", x)
    if impl == "xla":
        y = ref.conv1d_causal(x, w)
        return adj.apply_epilogue(plan, y, epi_args) if epi_stages else y
    interpret = _interp(impl)
    bwd_tune = None
    if autotune:
        pin = {"strategy": plan.strategy} if plan.strategy else {}
        xm, wm, epm = _measured((x, w, epi_args))
        call = (lambda **k: _c1.conv1d_causal(xm, wm, interpret=interpret,
                                              backend=backend,
                                              **{**pin, **k})) \
            if not epi_stages else _engine_runner(plan, xm, wm, interpret,
                                                  epi_args=epm,
                                                  backend=backend)
        kw = _tuned_kwargs(plan, x.shape, call, kw, context=("conv1d", impl),
                           backend=backend)
        bwd_tune = ("adjoint", "conv1d", impl)
    plan = _strategy_plan(plan, kw.pop("strategy", None), "conv1d_causal")
    d = _DEFAULTS["conv1d"].block
    cfg = _WindowCfg(
        plan=plan, block=(kw.pop("block_t", d[0]), kw.pop("block_d", d[1])),
        interpret=interpret, acc_dtype=kw.pop("acc_dtype", jnp.float32),
        bwd_tune=bwd_tune, backend=backend)
    if kw:
        raise TypeError(f"unexpected kwargs for conv1d_causal: {sorted(kw)}")

    def oracle():
        y = ref.conv1d_causal(x, w)
        return adj.apply_epilogue(plan, y, epi_args) if epi_stages else y

    return _guarded_window("conv1d_causal", cfg, x, w, epi_args, oracle)


def stencil(x, sdef: StencilDef | str, *, time_steps: int = 1,
            impl: str | None = None, autotune: bool = False, mesh=None,
            in_specs=None, boundary: str = "zero", epilogue=None,
            epilogue_args=(), strategy: str | None = None,
            backend: str | None = None, **kw):
    impl = impl or default_impl()
    backend = _check_backend(backend, "stencil")
    if isinstance(sdef, str):
        sdef = BENCHMARKS[sdef]
    epi_stages, epi_args = _epilogue_spec(epilogue, epilogue_args, "stencil")
    _reject_sharded_residual(epi_stages, mesh)
    mod = _s2 if sdef.ndim == 2 else _s3
    fn = mod.stencil2d if sdef.ndim == 2 else mod.stencil3d
    plan = _strategy_plan(mod.plan_for(sdef), strategy, "stencil")
    if epi_stages:
        plan = dataclasses.replace(plan, epilogue=epi_stages)
        _check_epilogue_operands(plan, epi_args, "stencil", x,
                                 time_steps=time_steps)
    if impl == "xla":
        if mesh is not None:
            raise ValueError("mesh= needs the engine path; the 'xla' oracle "
                             "is already shardable under pjit")
        y = ref.stencil_iterate(x, sdef, time_steps)
        return adj.apply_epilogue(plan, y, epi_args) if epi_stages else y
    interpret = _interp(impl)
    pin = {"strategy": plan.strategy} if plan.strategy else {}

    def oracle():
        y = ref.stencil_iterate(x, sdef, time_steps)
        return adj.apply_epilogue(plan, y, epi_args) if epi_stages else y

    if mesh is not None:
        if autotune:
            shape, sctx = _shard_tuning_call(plan, x, mesh, in_specs,
                                             time_steps, boundary)
            zeros = jnp.zeros(shape, x.dtype)
            # tune with the single-device engine on a shard-shaped block;
            # sharded-layer-only kwargs stay out of the measured closure
            sharded_kw = {k: kw.pop(k) for k in ("overlap",) if k in kw}
            call = (lambda **k: fn(zeros, sdef, time_steps=time_steps,
                                   interpret=interpret, backend=backend,
                                   **{**pin, **k})) \
                if not epi_stages else _engine_runner(
                    plan, zeros, None, interpret, epi_args=epi_args,
                    time_steps=time_steps, backend=backend)
            kw = _tuned_kwargs(plan, shape, call, kw, time_steps=time_steps,
                               context=("stencil", impl) + sctx,
                               backend=backend)
            kw.update(sharded_kw)
        cfg = _window_cfg(plan, kw, interpret=interpret,
                          time_steps=time_steps, mesh=mesh,
                          in_specs=in_specs, boundary=boundary,
                          backend=backend)
        return _guarded_window("stencil", cfg, x, None, epi_args, oracle)
    bwd_tune = None
    if autotune:
        call = (lambda **k: fn(x, sdef, time_steps=time_steps,
                               interpret=interpret, backend=backend,
                               **{**pin, **k})) \
            if not epi_stages else _engine_runner(
                plan, x, None, interpret, epi_args=epi_args,
                time_steps=time_steps, backend=backend)
        kw = _tuned_kwargs(plan, x.shape, call, kw, time_steps=time_steps,
                           context=("stencil", impl), backend=backend)
        bwd_tune = ("adjoint", "stencil", impl)
    return _guarded_window(
        "stencil",
        _window_cfg(plan, kw, interpret=interpret, time_steps=time_steps,
                    bwd_tune=bwd_tune, backend=backend),
        x, None, epi_args, oracle)


# ---------------------------------------------------------------------------
# Fused plan pipelines: ops.pipeline (DESIGN.md §11)
# ---------------------------------------------------------------------------

def _pipeline_stage_plan(x, desc, idx: int):
    """Resolve one pipeline stage descriptor → (plan, w_or_None).

    A descriptor is a Table-3 name / :class:`StencilDef` (table-coeff
    stencil stage), a 2-D filter array (dense 'same'-mode conv stage),
    or a ``(descriptor, epilogue)`` pair attaching elementwise stages
    after it. Stages apply over the domain's *trailing* spatial axes:
    a 2-D stage on a ``(B, H, W)`` stack or ``(B, C, H, W)`` NCHW
    tensor (and a 3-D stage on a batched volume) rides the extra
    leading axes as block-1 batch grid axes — the fused chain stays
    one engine kernel per batch item, no Python loop. Anything else —
    scan ops, OIHW reduce filters — gets a named pre-pallas
    ``ValueError`` (a channel *reduction* still cannot chain-fuse: the
    next stage may only read the summed output after the full
    accumulator sweep).
    """
    epilogue = None
    if (isinstance(desc, tuple) and len(desc) == 2
            and isinstance(desc[0], (str, StencilDef, jax.Array))):
        desc, epilogue = desc
    if isinstance(desc, str):
        if desc not in BENCHMARKS:
            raise ValueError(
                f"ops.pipeline: stage {idx} names unknown stencil "
                f"{desc!r}; known Table-3 stencils: "
                f"{sorted(BENCHMARKS)}")
        desc = BENCHMARKS[desc]
    if isinstance(desc, StencilDef):
        if desc.ndim > x.ndim:
            raise ValueError(
                f"ops.pipeline: stage {idx} ({desc.name}) is "
                f"{desc.ndim}-D but the domain is {x.ndim}-D")
        mod = _s2 if desc.ndim == 2 else _s3
        plan, w = mod.plan_for(desc), None
        if x.ndim > desc.ndim:
            plan = dataclasses.replace(plan, batch_axes=x.ndim - desc.ndim)
    elif isinstance(desc, jax.Array) or hasattr(desc, "ndim"):
        if desc.ndim == 4:
            raise ValueError(
                f"ops.pipeline: stage {idx} is an OIHW (NCHW conv) "
                "filter — reduce plans cannot chain-fuse (the channel "
                "reduction must finish its accumulator sweep first); "
                "run ops.conv2d / nn.layers.conv2d_apply with a fused "
                "epilogue= instead")
        if desc.ndim != 2 or x.ndim < 2:
            raise ValueError(
                f"ops.pipeline: stage {idx} filter must be a 2-D (N, M) "
                f"array on a >= 2-D domain, got filter "
                f"{tuple(desc.shape)} on a {x.ndim}-D domain")
        plan, w = _c2.plan_for(desc.shape, "same"), desc
        if x.ndim > 2:
            plan = dataclasses.replace(plan, batch_axes=x.ndim - 2)
    else:
        raise ValueError(
            f"ops.pipeline: stage {idx} descriptor {type(desc).__name__} "
            "is not a stencil name/StencilDef/2-D filter array; scan ops "
            "(cumsum/linear_recurrence) cannot sit in a spatial chain")
    if epilogue is not None:
        plan = dataclasses.replace(plan,
                                   epilogue=normalize_epilogue(epilogue))
    return plan, w


def _pipeline_epi_splits(plans, epi_args):
    """Split chain-ordered ``epilogue_args`` into per-stage tuples, one
    per plan, in application order (DESIGN.md §11)."""
    out, off = [], 0
    for p in plans:
        k = len(epilogue_operand_stages(p.epilogue))
        out.append(tuple(epi_args[off:off + k]))
        off += k
    return out


def _pipeline_ref(x, plans, ws, epi_args):
    """Pure-jnp oracle of a pipeline: pad-once, then valid stage
    applications (each stage's dense filter materialized from its taps)
    with the stage epilogues replayed elementwise. The gradcheck
    reference for fused backward. Leading batch axes flatten into the
    conv's N dimension — stages convolve the trailing spatial axes per
    batch item exactly as the engine's block-1 batch grid does."""
    import numpy as np
    from repro.core.fuse import summed_lead_trail
    lead, trail = summed_lead_trail(plans)
    nb, nd = plans[0].batch_axes, plans[0].ndim_spatial
    splits = _pipeline_epi_splits(plans, epi_args)
    h = jnp.pad(x, [(0, 0)] * nb + list(zip(lead, trail)))
    h = h.astype(jnp.float32)
    for i, p in enumerate(plans):
        if p.coeff_mode == "dense":
            f = ws[i].astype(jnp.float32)
        else:
            fa = np.zeros(p.exts, np.float32)
            for off, cid in adj.iter_tap_offsets(p):
                fa[off] = p.coeffs[cid[-1]]
            f = jnp.array(fa)
        batch = h.shape[:nb]
        hb = h.reshape((-1, 1) + h.shape[nb:])     # (B_flat, C=1, *spatial)
        if nd == 2:
            hb = jax.lax.conv_general_dilated(
                hb, f[None, None], (1, 1), "VALID")
        else:
            hb = jax.lax.conv_general_dilated(
                hb, f[None, None], (1, 1, 1), "VALID",
                dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
        h = hb.reshape(batch + hb.shape[2:])
        h = adj.apply_epilogue(p, h, splits[i])
    return h.astype(x.dtype)


def pipeline(x, stages, *, impl: str | None = None, autotune: bool = False,
             fuse="auto", epilogue_args=(), mesh=None, in_specs=None,
             boundary: str = "zero", strategy: str | None = None,
             backend: str | None = None, **kw):
    """Run a chain of shape-preserving windowed ops as ONE fused engine
    kernel — partial activations between stages never leave VMEM
    (DESIGN.md §11).

    ``stages`` is a list of stage descriptors applied left to right:
    Table-3 stencil names / :class:`StencilDef`\\ s, 2-D 'same'-mode
    conv filters, each optionally paired with an epilogue as
    ``(stage, "gelu")``. Stages apply over the domain's trailing
    spatial axes: on a batched ``(B, H, W)`` stack or an NCHW
    ``(B, C, H, W)`` tensor the extra leading axes ride the engine
    grid as block-1 batch axes, so the chain stays fused per item. Mid-chain epilogues must fix zero (preserving
    the pad-once boundary) or be a *scalar* ``bias``; the final stage
    may also take ``residual_add``. ``epilogue_args`` carries the
    operands of every operand-bearing stage in application (chain)
    order — mid-chain biases first, the final stage's operands last.

    Semantics are pad-once (trapezoidal), shared with temporal blocking:
    zero-pad once by the summed stage leads/trails, then apply the
    stages as valid windows — identical to a chain of same-shape per-op
    calls on the interior at distance > Σ radius from the boundary.

    ``fuse``: ``'auto'`` (default) fuses when the chain qualifies and
    silently falls back to the unfused pad-once sequence otherwise;
    ``True`` raises the named legality error instead of falling back;
    ``False`` forces the unfused sequence (one engine call per stage —
    the HBM-round-trip baseline the benchmarks compare against).

    Under ``mesh=`` the *fused* chain runs through the halo-exchange
    layer with one chain-widened halo per call; the unfused fallback
    cannot shard (its stages are valid-mode plans, not shape-preserving).
    """
    impl = impl or default_impl()
    backend = _check_backend(backend, "pipeline")
    if fuse not in (True, False, "auto"):
        raise ValueError(f"ops.pipeline: fuse must be True/False/'auto', "
                         f"got {fuse!r}")
    if not stages:
        raise ValueError("ops.pipeline needs at least one stage")
    resolved = [_pipeline_stage_plan(x, d, i) for i, d in enumerate(stages)]
    nd0 = resolved[0][0].ndim_spatial
    for i, (p, _) in enumerate(resolved):
        if p.ndim_spatial != nd0:
            raise ValueError(
                f"ops.pipeline: stage {i} is {p.ndim_spatial}-D but stage "
                f"0 is {nd0}-D; on a batched domain every stage must "
                "window the same trailing spatial axes")
    # one strategy for the whole chain: every stage shares the VMEM tile,
    # so the pin rides each stage plan and fuse_plans carries it onto
    # the composite (stages keep their own copy for the unfused path)
    plans = [_strategy_plan(p, strategy, "pipeline") for p, _ in resolved]
    ws = tuple(w for _, w in resolved)
    need = [s.op for p in plans for s in epilogue_operand_stages(p.epilogue)]
    if len(tuple(epilogue_args)) != len(need):
        raise ValueError(
            f"ops.pipeline: the chain's epilogues need {len(need)} runtime "
            f"operand(s) ({need}, application order) in epilogue_args, got "
            f"{len(tuple(epilogue_args))}")
    epi_args = tuple(epilogue_args)
    epi_splits = _pipeline_epi_splits(plans, epi_args)
    for i, p in enumerate(plans[:-1]):
        bad = [s.op for s in epilogue_operand_stages(p.epilogue)
               if s.op != "bias"]
        if bad:
            raise ValueError(
                f"ops.pipeline: stage {i} carries a residual_add epilogue "
                "mid-chain; the residual operand is output-shaped and "
                "would materialize the intermediate it skips — only bias "
                "may sit mid-chain, residual_add goes on the final stage")
        for arr in epi_splits[i]:
            if _shape_size(tuple(getattr(arr, "shape", ()))) != 1:
                raise ValueError(
                    f"ops.pipeline: stage {i}'s mid-chain bias must be a "
                    "scalar (it applies to the whole pad-once "
                    "intermediate), got shape "
                    f"{tuple(getattr(arr, 'shape', ()))}")
    if plans[-1].epilogue:
        # pipeline stages are shape-preserving, so the final stage's own
        # layout validates its epilogue operands (named errors)
        _check_epilogue_operands(plans[-1], epi_splits[-1], "pipeline", x)
    if impl == "xla":
        if mesh is not None:
            raise ValueError("mesh= needs the engine path; the 'xla' oracle "
                             "is already shardable under pjit")
        return _pipeline_ref(x, plans, ws, epi_args)
    interpret = _interp(impl)

    fused_plan, fuse_err = None, None
    try:
        fused_plan = fuse_plans(*plans)
    except ValueError as e:
        fuse_err = e
    if fuse is True and fused_plan is None:
        raise fuse_err
    if fuse == "auto" and fused_plan is None or fuse is False:
        if mesh is not None:
            raise ValueError(
                "an unfused pipeline cannot shard: its stages are "
                "valid-mode (pad-once) plans, not shape-preserving; fuse "
                "the chain or run per-op ops.stencil calls under the mesh")
        # Unfused fallback: identical pad-once math, one engine call —
        # and one full HBM round-trip of the activation — per stage.
        # The lattice wraps the whole sequence (a per-stage lattice would
        # fall back stage-by-stage into mixed lowerings): any stage
        # failure retreats to the pure-XLA chain oracle.
        def unfused():
            from repro.core.fuse import summed_lead_trail
            lead, trail = summed_lead_trail(plans)
            h = jnp.pad(x, [(0, 0)] * plans[0].batch_axes
                        + list(zip(lead, trail)))
            for i, p in enumerate(plans):
                pv = dataclasses.replace(p, lead=None, trail=None)
                a = epi_splits[i]
                skw = dict(kw)
                if autotune:
                    skw = _tuned_kwargs(
                        pv, h.shape,
                        _engine_runner(pv, h, ws[i], interpret, epi_args=a,
                                       backend=backend),
                        skw, context=("pipeline_stage", i, impl),
                        backend=backend)
                cfg = _window_cfg(pv, skw, interpret=interpret,
                                  backend=backend)
                h = _window_op(cfg, h, ws[i], a)
            return h

        return rguard.run("pipeline", [
            ("unfused", unfused),
            ("oracle", lambda: _pipeline_ref(x, plans, ws, epi_args))])
    if autotune:
        if mesh is not None:
            shape, sctx = _shard_tuning_call(fused_plan, x, mesh, in_specs,
                                             1, boundary)
            zeros = jnp.zeros(shape, x.dtype)
            sharded_kw = {k: kw.pop(k) for k in ("overlap",) if k in kw}
            kw = _tuned_kwargs(
                fused_plan, shape,
                _engine_runner(fused_plan, zeros,
                               ws if fused_plan.stages else ws[0],
                               interpret, epi_args=epi_args,
                               backend=backend),
                kw, context=("pipeline", impl) + sctx, backend=backend)
            kw.update(sharded_kw)
        else:
            kw = _tuned_kwargs(
                fused_plan, x.shape,
                _engine_runner(fused_plan, x,
                               ws if fused_plan.stages else ws[0],
                               interpret, epi_args=epi_args,
                               backend=backend),
                kw, context=("pipeline", impl), backend=backend)
    cfg = _window_cfg(fused_plan, kw, interpret=interpret, mesh=mesh,
                      in_specs=in_specs, boundary=boundary, backend=backend)
    return _guarded_window("pipeline", cfg, x,
                           ws if fused_plan.stages else ws[0], epi_args,
                           lambda: _pipeline_ref(x, plans, ws, epi_args))


def _reject_scan_kwargs(op: str, kw: dict) -> None:
    """Scan ops cannot shard over the halo-exchange layer and cannot
    take windowed-op fusion kwargs — say so loudly (pre-pallas) instead
    of silently ignoring unknown kwargs."""
    bad = sorted(k for k in ("mesh", "in_specs", "boundary") if k in kw)
    if bad:
        raise ValueError(
            f"ops.{op} does not take {', '.join(bad)}: scan plans carry a "
            "sequential inter-block carry along the lane axis, so the "
            "halo-exchange layer cannot shard them; shard the row axis "
            "under pjit with impl='xla' instead")
    bad = sorted(k for k in ("epilogue", "epilogue_args", "stride",
                             "strategy") if k in kw)
    if bad:
        raise ValueError(
            f"ops.{op} does not take {', '.join(bad)}: fused epilogues, "
            "output strides, chain fusion and the lanes/mxu lowering "
            "strategy are windowed-plan features (DESIGN.md §11/§13) — a "
            "scan's tap contraction is a carried recurrence, not a "
            "matmul, and a fused activation would corrupt the carry; "
            "apply the elementwise stage in XLA after the scan, or fuse "
            "windowed stages with ops.pipeline")


# kept under the old name for callers/tests that used the PR 4 guard
_reject_scan_mesh = _reject_scan_kwargs


def _scan_cfg(kw: dict, *, interpret: bool, op: str) -> _ScanCfg:
    d = _DEFAULTS["scan"].block
    cfg = _ScanCfg(block_r=kw.pop("block_r", d[0]),
                   block_t=kw.pop("block_t", d[1]),
                   interpret=interpret,
                   acc_dtype=kw.pop("acc_dtype", jnp.float32),
                   chunk=kw.pop("chunk", None),
                   backend=_check_backend(kw.pop("backend", None), op))
    if kw:
        raise TypeError(f"unexpected kwargs for ops.{op}: {sorted(kw)}")
    return cfg


def cumsum(x, *, impl: str | None = None, autotune: bool = False, **kw):
    _reject_scan_kwargs("cumsum", kw)
    impl = impl or default_impl()
    if impl == "xla":
        return ref.cumsum(x)
    interpret = _interp(impl)
    if autotune:
        from repro.core.plan import scan_plan
        plan = scan_plan(128)          # schedule signature for the cache key
        kw = _tuned_kwargs(
            plan, x.shape,
            lambda **k: _sc.cumsum(x, interpret=interpret, **k), kw,
            context=("cumsum", impl), backend=kw.get("backend"))
    return _guarded_scan("cumsum",
                         _scan_cfg(kw, interpret=interpret, op="cumsum"),
                         lambda c: _cumsum_op(c, x),
                         lambda: ref.cumsum(x))


def sat(x, *, impl: str | None = None, **kw):
    """Summed-area table (§3.6 / the paper's companion SAT work [7]):
    two passes of the SSAM Kogge–Stone cumsum — rows, then columns."""
    _reject_scan_kwargs("sat", kw)
    rows = cumsum(x, impl=impl, **kw)
    return cumsum(rows.T, impl=impl, **kw).T


def linear_recurrence(a, b, *, impl: str | None = None,
                      autotune: bool = False, **kw):
    """h_t = a_t·h_{t−1} + b_t along the last axis of (R, T)-shaped a, b."""
    _reject_scan_kwargs("linear_recurrence", kw)
    impl = impl or default_impl()
    if impl == "xla":
        return ref.linear_recurrence(a, b)
    interpret = _interp(impl)
    if autotune:
        from repro.core.plan import linear_recurrence_plan
        plan = linear_recurrence_plan(128)
        kw = _tuned_kwargs(
            plan, a.shape,
            lambda **k: _sc.linear_recurrence(a, b, interpret=interpret, **k),
            kw, context=("linrec", impl), backend=kw.get("backend"))
    return _guarded_scan(
        "linear_recurrence",
        _scan_cfg(kw, interpret=interpret, op="linear_recurrence"),
        lambda c: _linrec_op(c, a, b),
        lambda: ref.linear_recurrence(a, b))


# ---------------------------------------------------------------------------
# Shardable chunked recurrence for full-scale models (beyond-paper path).
#
# The elementwise SSAM recurrence is the paper-faithful execution; at
# production sequence lengths the framework uses this chunk-parallel form:
# an associative (Kogge–Stone, same algebra as the SSAM plan) scan within
# chunks under lax.scan state-passing across chunks — O(T·log L) work,
# O(B·L·C) live memory, shardable over batch/channel axes under pjit.
#
# ``impl="engine"`` routes the same math through the chunk-streamed
# engine schedule (DESIGN.md §12): leading axes flatten to the engine's
# row axis and the sequence streams through ``(R, chunk)`` ``run_scan_plan``
# slabs inside a ``lax.scan`` whose carry is the per-row state — O(R·chunk)
# live state forward AND backward (chunk-boundary checkpointing), the
# production LM path exercising the exact kernel the benchmarks measure.
# ``impl="engine_unchunked"`` keeps the monolithic O(R·T) lowering as the
# validation reference.
# ---------------------------------------------------------------------------

def default_scan_impl() -> str:
    """Per-backend default for the production scan surfaces
    (:func:`chunked_linear_recurrence`, ``nn/ssm.selective_scan``,
    ``nn/ssm.wkv6_chunked``): the chunk-streamed engine schedule on real
    TPU, the pjit-shardable XLA chunk form elsewhere (the Pallas
    interpreter is far too slow to be anyone's training default)."""
    return "engine" if jax.default_backend() == "tpu" else "chunked"


@functools.partial(jax.jit, static_argnames=("chunk",))
def _chunked_linrec_xla(a: jax.Array, b: jax.Array, *, chunk: int):
    """Non-engine chunk form: associative scan within chunks, lax.scan
    state-passing across chunks — O(T·log L) work, shardable under pjit."""
    T = a.shape[-1]
    pad = (-T) % chunk
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)], constant_values=1)
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 1) + [(0, pad)])
    nc = a.shape[-1] // chunk
    ac = a.reshape(a.shape[:-1] + (nc, chunk))
    bc = b.reshape(b.shape[:-1] + (nc, chunk))
    ac = jnp.moveaxis(ac, -2, 0)  # (nc, ..., chunk)
    bc = jnp.moveaxis(bc, -2, 0)

    def combine(x, y):
        ax, bx = x
        ay, by = y
        return ax * ay, bx * ay + by  # f_y ∘ f_x (x earlier)

    def chunk_step(h, ab):
        a_k, b_k = ab
        A, B = jax.lax.associative_scan(combine, (a_k, b_k), axis=-1)
        h_t = A * h[..., None] + B
        return h_t[..., -1], h_t

    h0 = jnp.zeros(a.shape[:-1], a.dtype)
    _, hs = jax.lax.scan(chunk_step, h0, (ac, bc))
    out = jnp.moveaxis(hs, 0, -2).reshape(a.shape[:-1] + (nc * chunk,))
    return out[..., :T]


def chunked_linear_recurrence(a: jax.Array, b: jax.Array, *,
                              chunk: int = 128, impl: str | None = None,
                              autotune: bool = False, **kw):
    """Same math as :func:`linear_recurrence`; a, b shaped (..., T).

    ``impl``: ``None`` resolves per backend (:func:`default_scan_impl`);
    ``"engine"`` streams ``(R, chunk)`` slabs through the scan engine
    with the inter-chunk carry in the ``lax.scan`` state (O(R·chunk)
    live state, checkpointed backward); ``"engine_unchunked"`` is the
    monolithic O(R·T) engine lowering; ``"chunked"`` is the non-engine
    XLA associative-scan form. ``autotune=True`` tunes
    ``(block_r, block_t, chunk)`` through the §5 model + sidecar for the
    streamed path (``(block_r, block_t)`` for the monolithic one).
    """
    impl = impl or default_scan_impl()
    if impl not in ("engine", "engine_unchunked", "chunked"):
        raise ValueError(impl)
    T = a.shape[-1]
    if impl == "chunked":
        if kw:
            raise TypeError(
                f"unexpected kwargs for ops.chunked_linear_recurrence"
                f"(impl='chunked'): {sorted(kw)}")
        return _chunked_linrec_xla(a, b, chunk=chunk)

    rows_a, rows_b = a.reshape(-1, T), b.reshape(-1, T)
    interpret = engine_interpret()
    streamed = impl == "engine"
    if autotune:
        from repro.core.plan import linear_recurrence_plan
        plan = linear_recurrence_plan(128)   # schedule signature (cache key)

        def call(**k):
            ck = k.pop("chunk", chunk)
            cfg = _ScanCfg(interpret=interpret,
                           chunk=ck if streamed else None, **k)
            return (_linrec_stream(cfg, rows_a, rows_b) if streamed
                    else _linrec_op(cfg, rows_a, rows_b))

        kw = _tuned_kwargs(
            plan, rows_a.shape, call, kw,
            context=("linrec_stream" if streamed else "linrec", impl),
            chunked=streamed,
            default=tuning.KernelConfig((8, 128, chunk)) if streamed
            else None, backend=kw.get("backend"))
    chunk = kw.pop("chunk", chunk)
    if streamed:
        cfg = _scan_cfg(kw, interpret=interpret,
                        op="chunked_linear_recurrence")
        cfg = dataclasses.replace(cfg, chunk=chunk,
                                  block_t=min(cfg.block_t, chunk))
        from repro.core import engine as _eng
        from repro.core.plan import linear_recurrence_plan
        _eng.check_chunk_geometry(
            linear_recurrence_plan(_sc._lane_tile(cfg.block_t, chunk)), chunk)
        out = _guarded_scan(
            "chunked_linear_recurrence", cfg,
            lambda c: _linrec_stream(c, rows_a, rows_b),
            lambda: _chunked_linrec_xla(rows_a, rows_b, chunk=chunk))
    else:
        cfg = _ScanCfg(block_r=kw.pop("block_r", 8),
                       block_t=kw.pop("block_t", chunk),
                       interpret=interpret,
                       acc_dtype=kw.pop("acc_dtype", jnp.float32),
                       backend=_check_backend(
                           kw.pop("backend", None),
                           "chunked_linear_recurrence"))
        if kw:
            raise TypeError(
                f"unexpected kwargs for ops.chunked_linear_recurrence: "
                f"{sorted(kw)}")
        out = _guarded_scan(
            "chunked_linear_recurrence", cfg,
            lambda c: _linrec_op(c, rows_a, rows_b),
            lambda: ref.linear_recurrence(rows_a, rows_b))
    return out.reshape(a.shape)
