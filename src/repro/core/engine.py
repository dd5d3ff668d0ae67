"""Generic plan→Pallas lowering — one engine for every systolic plan.

This module is the "compiler" half of the SSAM formulation: a
:class:`repro.core.plan.SystolicPlan` is pure data (𝒥 = (O, D, X, Y),
§3.4) and the engine lowers *any* plan to a Pallas TPU kernel. The five
former per-family kernels (``ssam_conv1d/conv2d/stencil2d/stencil3d/
ssam_scan``) are now ~20-line plan builders over two lowerings here:

* :func:`run_window_plan` — the windowed (conv/stencil) family. From the
  plan's geometry it derives the overlapped-block ``pl.Element``
  BlockSpecs (§4.5), the pad/halo arithmetic (lead/trail origin padding,
  tile round-up), temporal blocking (t-step fusion inside the block,
  §6.4), the valid-lane crop (outputs live in lanes ``[M−1, S)``, §4.4)
  and both schedule variants (DESIGN.md §2):

  - ``variant='shift_psum'`` — paper-faithful: the *partial sums* roll
    along the lane axis (the ``__shfl_up_sync`` of §4.4).
  - ``variant='shift_data'`` — re-associated: the accumulator stays put
    and the *data* rolls by the cumulative shift instead; the rolls of
    all M steps become independent of the accumulator chain and can
    issue in parallel with the FMAs. Per output lane the same products
    are added in the same order, so results agree to the last ulp modulo
    XLA's FMA-contraction choices (observed ≤ 1 ulp on CPU).

* :func:`run_scan_plan` — the scan family (cumsum / linear recurrence):
  Kogge–Stone masked shift-accumulate over the lane axis (§3.6, Fig. 1e)
  with an inter-block carry in VMEM scratch — scratchpad used only
  *between* systolic blocks, exactly as SSAM prescribes (§1).

Everything the lowering needs — footprint extents, origin padding, batch
axes, coefficient source — comes from plan fields, so a new kernel family
is a new plan builder, not a new kernel body.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.obs import scopes
from repro.robust import faults as rfaults

from .fuse import pipeline_coeff_count
from .halo import origin_pads
from .plan import (EPILOGUE_OPERANDS, EpilogueStage, SystolicPlan, Tap,
                   chain_epilogue_operand_stages, epilogue_operand_stages)


def _obs_lowering(*, plan: SystolicPlan, block, backend: str,
                  time_steps: int = 1, variant: str = "shift_psum"):
    """Trace-time telemetry for one plan lowering (both backends call it).

    This runs inside the ``jax.jit``-ed lowering bodies, so its Python
    side effects fire once per *compilation*, not per call: the
    ``engine.lowering`` counter is the lowering-cache-miss (recompile)
    count, and the returned span — the "one span per plan lowering"
    event — times the trace+lower work itself, carrying the plan
    signature, strategy, block and the §5 predicted cost. Disabled
    tracing pays one counter bump and one boolean check.
    """
    strategy = (plan.strategy or "lanes") if plan.combine == "fma" \
        else plan.combine
    obs.metrics.inc("engine.lowering", f"{backend}:{plan.kind}")
    if not obs.trace.enabled():
        return obs.trace.NULL
    from . import tuning
    try:
        cost = tuning.model_cost(
            plan, tuning.KernelConfig(tuple(block), variant, plan.strategy),
            time_steps, tuning.machine_for(backend))
    except Exception:
        cost = None       # telemetry never turns a lowering into an error
    return obs.span(
        "engine.lower", cat="engine", plan=tuning.plan_signature(plan),
        kind=plan.kind, backend=backend, strategy=strategy,
        block=list(block), time_steps=time_steps, model_cost=cost)


def _obs_call_drift(plan: SystolicPlan, block, backend: str, time_steps: int,
                    variant: str, out, t0: float, shape) -> None:
    """Opt-in per-call model-vs-measured sample (``REPRO_DRIFT``).

    Blocks on ``out`` — which defeats async dispatch, hence opt-in —
    and records wall µs against the launch's predicted §5 cost. Skipped
    under an enclosing jit trace (there is nothing to time).
    """
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) * 1e6
    from . import tuning
    try:
        cost = tuning.model_cost(
            plan, tuning.KernelConfig(tuple(block), variant, plan.strategy),
            time_steps, tuning.machine_for(backend))
    except Exception:
        return
    obs.drift.record(tuning.plan_signature(plan), backend, plan.strategy,
                     cost, us, shape=shape, source="call")


# ---------------------------------------------------------------------------
# Windowed family: conv1d / conv2d / stencil2d / stencil3d
# ---------------------------------------------------------------------------

def _coeff(plan: SystolicPlan, w_ref, tap: Tap, acc_dtype):
    """Resolve a tap's coefficient per the plan's coeff_mode.

    For reduce plans the coefficient block carries ``out_axes +
    reduce_axes`` leading block-1 axes (the grid already selected the
    (c_out, c_in) slice via the BlockSpec index map), so the tap's
    ``coeff_id`` is prefixed with zeros — the *channel-reduction tap
    group*: same taps, one coefficient slice per reduce iterate.
    """
    if plan.coeff_mode == "table":          # compile-time immediate (§4.8)
        return plan.coeffs[tap.coeff_id[-1]]
    if plan.coeff_mode == "dense":          # runtime filter, scalar element
        pre = (0,) * (plan.out_axes + plan.reduce_axes)
        return w_ref[pre + tap.coeff_id].astype(acc_dtype)
    if plan.coeff_mode == "perlane":        # runtime per-lane coefficient row
        return w_ref[tap.coeff_id[-1], :].astype(acc_dtype)
    raise ValueError(plan.coeff_mode)


def _accumulate_over_reduce(acc_ref, o_ref, contrib, rdims, o_idx,
                            epilogue_fn=None):
    """Grid-reduce epilogue shared by every accumulating kernel.

    The sweep over ``rdims`` (innermost, sequential grid dims) revisits
    the same output block: reset the scratch on the first reduce
    iterate, ⊕-accumulate the block's contribution, flush to the output
    ref on the last — the matmul-k pattern (DESIGN.md §9.2/§10.1).
    ``epilogue_fn`` (plan-IR output stages, DESIGN.md §11) applies at
    the flush, i.e. to the *summed* block, in VMEM — between the
    accumulator flush and the output store.
    """
    first = functools.reduce(
        jnp.logical_and, [pl.program_id(d) == 0 for d in rdims])
    last = functools.reduce(
        jnp.logical_and,
        [pl.program_id(d) == pl.num_programs(d) - 1 for d in rdims])

    @pl.when(first)
    def _reset():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contrib.astype(acc_ref.dtype)

    @pl.when(last)
    def _flush():
        out = acc_ref[...]
        if epilogue_fn is not None:
            out = epilogue_fn(out)
        o_ref[o_idx] = out.astype(o_ref.dtype)


def _tap_read(xb: jnp.ndarray, tap: Tap, valid: tuple[int, ...]) -> jnp.ndarray:
    """The vertical (in-lane, cheap-direction) register read of Fig. 1d."""
    if xb.ndim == 3:
        return xb[
            tap.z_offset : tap.z_offset + valid[0],
            tap.row_offset : tap.row_offset + valid[1],
            :,
        ]
    return xb[tap.row_offset : tap.row_offset + valid[0], :]


def _flat_taps(stage: SystolicPlan) -> list[tuple[int, Tap]]:
    """The tap set flattened to ``(cumulative_shift, tap)`` pairs.

    The cumulative lane shift is the tap's horizontal offset in
    shift_data coordinates: output lane ``l`` reads input lane
    ``l + cum`` (strided plans: ``l·stride + cum``).
    """
    out, cum = [], 0
    for step in stage.steps:
        cum += step.shift
        for tap in step.taps:
            out.append((cum, tap))
    return out


def _apply_plan_mxu(xb, stage: SystolicPlan, w_ref, acc_dtype):
    """One application of ``stage`` as an im2row matmul on the MXU.

    Instead of walking the tap set with per-tap FMAs (the VPU 'lanes'
    schedule), gather every tap's shifted view of the block into a
    ``(rows, taps, lanes)`` operand **in VMEM** — im2row over the tap
    set, never materialized in HBM — and contract the tap dimension in
    ONE row-batched ``jax.lax.dot_general`` with
    ``preferred_element_type=f32`` at ``HIGHEST`` precision (fp32
    contraction; Mosaic's default may round f32 operands to bf16), which
    Mosaic routes to the MXU (DESIGN.md §13). Each view keeps the block's
    full lane width: the lane shift is a roll (the shift_data
    association, so both strategies agree to fp32 tolerance) and the
    valid lanes are cropped after the contraction. Mosaic cannot flatten
    an unaligned view to 1-D, which is why the tap dimension sits between
    the row and lane dimensions.

    Every tap's coefficient — a compile-time immediate ('table'), a
    runtime filter scalar ('dense') or a per-lane row ('perlane') — is
    folded into its view, so all three modes contract against a ones
    vector. For NCHW reduce plans this runs once per ``C_in`` iterate of
    the reduce sweep into the same fp32 accumulator.
    """
    exts = stage.exts
    stride = stage.stride_per_axis()
    strided = any(v > 1 for v in stride)
    views = []
    if strided:
        sh, sw = stride
        out_sp = tuple((n - e) // v + 1
                       for n, e, v in zip(xb.shape, exts, stride))
        for cum, tap in _flat_taps(stage):
            views.append((tap, xb[
                tap.row_offset : tap.row_offset + out_sp[0] * sh : sh,
                cum : cum + out_sp[1] * sw : sw,
            ]))
    else:
        out_sp = tuple(n - (e - 1) for n, e in zip(xb.shape, exts))
        for cum, tap in _flat_taps(stage):
            xs = jnp.roll(xb, -cum, axis=-1) if cum else xb
            views.append((tap, _tap_read(xs, tap, out_sp)))
    A = jnp.stack([v * _coeff(stage, w_ref, tap, acc_dtype)
                   for tap, v in views], axis=-2)  # (..., rows, taps, lanes)
    lead = A.shape[:-3]
    A = A.reshape((-1,) + A.shape[-3:]) if lead else A[None]
    ones = jnp.ones((A.shape[1], 1, A.shape[2]), acc_dtype)
    out = jnp.stack([
        jax.lax.dot_general(
            ones, a, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:, 0, :]
        for a in A])                                # one batched dot per plane
    out = out.reshape(lead + out.shape[1:]) if lead else out[0]
    return out[..., : out_sp[-1]].astype(acc_dtype)


def _apply_plan_once(xb, stage: SystolicPlan, w_ref, variant: str, acc_dtype,
                     strategy: str = "lanes"):
    """One valid application of ``stage``'s schedule on the block ``xb``.

    Dense (stride-1) plans run either schedule variant (DESIGN.md §2).
    Output-strided plans use the data-stationary strided read directly —
    output lane ``l`` gathers input lane ``l·stride + cum`` per column
    step, so the kernel computes only the lanes the stride keeps instead
    of the dense result it would subsample. ``strategy='mxu'`` replaces
    the whole tap walk with the im2row matmul of
    :func:`_apply_plan_mxu`; the ``variant`` knob is then moot (there
    are no rolls to re-associate).
    """
    if strategy == "mxu":
        return _apply_plan_mxu(xb, stage, w_ref, acc_dtype)
    exts = stage.exts
    M = stage.M
    stride = stage.stride_per_axis()
    if any(v > 1 for v in stride):
        sh, sw = stride
        out_sp = tuple((n - e) // v + 1
                       for n, e, v in zip(xb.shape, exts, stride))
        s = jnp.zeros(out_sp, acc_dtype)
        cum = 0
        for step in stage.steps:
            cum += step.shift
            for tap in step.taps:
                patch = xb[
                    tap.row_offset : tap.row_offset + out_sp[0] * sh : sh,
                    cum : cum + out_sp[1] * sw : sw,
                ]
                s = s + patch * _coeff(stage, w_ref, tap, acc_dtype)
        return s
    valid = tuple(n - (e - 1) for n, e in zip(xb.shape, exts))
    # Partial sums keep the full lane width until the valid-lane crop.
    s = jnp.zeros(valid[:-1] + (xb.shape[-1],), acc_dtype)
    if variant == "shift_psum":
        # Paper Listing 1/2: shift the partial sums one lane per
        # column-step, then accumulate that column's vertical taps.
        for step in stage.steps:
            if step.shift:
                s = jnp.roll(s, step.shift, axis=-1)
            for tap in step.taps:
                s = s + _tap_read(xb, tap, valid) * _coeff(
                    stage, w_ref, tap, acc_dtype)
        return s[..., M - 1 : M - 1 + valid[-1]]
    if variant == "shift_data":
        # Stationary accumulator: roll the data by the cumulative
        # shift instead. Same per-lane sums in the same order.
        cum = 0
        for step in stage.steps:
            cum += step.shift
            xs = jnp.roll(xb, -cum, axis=-1) if cum else xb
            for tap in step.taps:
                s = s + _tap_read(xs, tap, valid) * _coeff(
                    stage, w_ref, tap, acc_dtype)
        return s[..., : valid[-1]]
    raise ValueError(variant)


def _apply_epilogue_val(st: EpilogueStage, val, epi_ref, plan: SystolicPlan,
                        acc_dtype, o_idx):
    """One elementwise epilogue stage on an in-VMEM block (DESIGN.md §11)."""
    if st.op == "gelu":
        return jax.nn.gelu(val, approximate=True)
    if st.op == "silu":
        return jax.nn.silu(val)
    if st.op == "relu":
        return jnp.maximum(val, 0)
    if st.op == "scale":
        return val * st.value
    if st.op == "bias":
        if plan.out_axes:                 # per-out-channel (NCHW): scalar
            return val + epi_ref[(0,) * plan.out_axes].astype(acc_dtype)
        if plan.coeff_mode == "perlane":  # per-lane (depthwise conv) row
            return val + epi_ref[...].astype(acc_dtype)
        return val + epi_ref[0].astype(acc_dtype)
    if st.op == "residual_add":
        return val + epi_ref[o_idx].astype(acc_dtype)
    raise ValueError(st.op)


def _window_kernel(*refs, plan: SystolicPlan, block: tuple[int, ...],
                   time_steps: int, variant: str, acc_dtype):
    """One overlapped block of any windowed plan.

    ``refs`` is ``(x_ref, *w_refs, *epilogue_refs, o_ref[, acc_ref])``.
    The block runs ``time_steps`` fused applications of the plan (§6.4)
    — or, for a fused pipeline, one application of each ``plan.stages``
    entry with the stage's own taps/coefficients and any mid-chain
    elementwise epilogues applied between stages, all in VMEM
    (DESIGN.md §11). Each application consumes one stage-footprint of
    halo per axis. The final epilogue applies between the accumulator
    flush and the output store. Reduce plans carry the block's partial
    sum in an fp32 VMEM scratch accumulator across the (innermost,
    sequential) reduce grid iterates and write the output on the last
    one — §2's shift-psum dataflow applied across channels instead of
    lanes.
    """
    nb, nr, no = plan.batch_axes, plan.reduce_axes, plan.out_axes
    n_w = pipeline_coeff_count(plan)
    epi_entries = chain_epilogue_operand_stages(plan)
    x_ref = refs[0]
    w_refs = refs[1:1 + n_w]
    epi_refs = refs[1 + n_w:1 + n_w + len(epi_entries)]
    o_ref = refs[1 + n_w + len(epi_entries)]
    acc_ref = refs[-1] if nr else None
    xb = (x_ref[(0,) * (nb + nr)] if nb + nr else x_ref[...]).astype(acc_dtype)
    ei0 = 0                 # epilogue-operand cursor, shared across the chain
    if plan.stages:
        wi = 0
        for si, stage in enumerate(plan.stages):
            w_ref = None
            if stage.coeff_mode == "dense":
                w_ref = w_refs[wi]
                wi += 1
            # A stage's own pinned strategy wins; otherwise it inherits
            # the chain's (the tuner pins the chain as ONE kernel).
            xb = _apply_plan_once(xb, stage, w_ref, variant, acc_dtype,
                                  strategy=stage.strategy or plan.strategy
                                  or "lanes")
            if si < len(plan.stages) - 1:
                # mid-chain epilogues fix zero or are a scalar bias
                # (fuse_plans); either way they apply to the whole
                # pad-once intermediate, so the trapezoidal boundary
                # stays shared with the unfused fallback.
                for st in stage.epilogue:
                    ref = None
                    if st.op in EPILOGUE_OPERANDS:
                        ref = epi_refs[ei0]
                        ei0 += 1
                    xb = _apply_epilogue_val(st, xb, ref, plan, acc_dtype,
                                             None)
    else:
        w_ref = w_refs[0] if n_w else None
        for _ in range(time_steps):
            xb = _apply_plan_once(xb, plan, w_ref, variant, acc_dtype,
                                  strategy=plan.strategy or "lanes")
    res = xb[tuple(slice(0, b) for b in block)]
    o_idx = (0,) * (nb + no) if nb + no else ...

    def epilogue_fn(val):
        ei = ei0
        for st in plan.final_epilogue():
            ref = None
            if st.op in EPILOGUE_OPERANDS:
                ref = epi_refs[ei]
                ei += 1
            val = _apply_epilogue_val(st, val, ref, plan, acc_dtype, o_idx)
        return val

    if nr:
        # Reduce grid dims are innermost: per output block the sweep is
        # sequential, so the scratch accumulator is exact fp32 ⊕ (§2).
        rdims = range(nb + no + plan.ndim_spatial,
                      nb + no + plan.ndim_spatial + nr)
        _accumulate_over_reduce(acc_ref, o_ref, res, tuple(rdims), o_idx,
                                epilogue_fn)
    else:
        o_ref[o_idx] = epilogue_fn(res).astype(o_ref.dtype)


def tpu_tile(dtype) -> tuple[int, int]:
    """The (sublane, lane) tile Mosaic lays a VMEM block of ``dtype`` in:
    (8, 128) for 32-bit, (16, 128) for 16-bit, (32, 128) for 8-bit."""
    return (8 * 4 // jnp.dtype(dtype).itemsize, 128)


def _round_block(shape, out_block, grid, tile) -> tuple[int, ...]:
    """Round the last two dims of an input block up to ``tile`` multiples.

    Mosaic accepts a block whose last two dims are tile multiples or span
    the whole array. A halo-widened input block such as 10×130 for an
    8×128 output block is neither, so it reads 16×256 instead. A dim
    needs no round-up when it carries no halo (it is then as legal as the
    output block) or when its grid has one step (the caller sizes the
    operand to exactly that block).
    """
    if tile is None:
        return tuple(shape)
    lead = tuple(shape[:-2])
    return lead + tuple(
        n if n == b or g == 1 else -(-n // t) * t
        for n, b, g, t in zip(shape[-2:], out_block[-2:], grid[-2:], tile))


def _window_call(
    x: jax.Array,
    w,
    *,
    plan: SystolicPlan,
    block: tuple[int, ...],
    time_steps: int,
    variant: str,
    interpret: bool,
    acc_dtype,
    epilogue_args: tuple,
    make_kernel,
    make_scratch,
    in_tile: tuple[int, int] | None = None,
) -> jax.Array:
    """Backend-shared windowed-family driver (DESIGN.md §14).

    Everything about a windowed lowering that is backend-*independent*
    lives here: plan validation, the t-widened origin/halo padding, the
    overlapped element-indexed (``pl.Element``) input BlockSpecs,
    coefficient/epilogue operand layout, the batch × out × spatial ×
    reduce grid, and the final valid crop. A backend contributes only its
    kernel body, scratch request and input tile — ``make_kernel(B)`` →
    kernel fn for output block ``B``, ``make_scratch(B, in_block)`` →
    ``scratch_shapes`` list, ``in_tile`` → the (sublane, lane) multiple
    the last two input block dims are rounded up to (None: unrounded) —
    so the TPU (sublane/lane) and GPU (warp-shuffle + SMEM skirt)
    lowerings share one geometry and can only differ in how a block is
    computed.
    """
    nb, nr, no, nd = (plan.batch_axes, plan.reduce_axes, plan.out_axes,
                      plan.ndim_spatial)
    assert x.ndim == nb + nr + nd, (x.shape, nb, nr, nd)
    assert len(block) == nd, (block, nd)
    for p in (plan,) + plan.stages:
        if p.strategy not in (None, "lanes", "mxu"):
            raise ValueError(
                f"unknown lowering strategy {p.strategy!r} on {p.kind!r}: "
                "expected None (auto), 'lanes' (VPU shift schedule) or "
                "'mxu' (im2row dot_general, DESIGN.md §13)")
    if nr or no:
        assert plan.coeff_mode == "dense" and w is not None, (
            "reduce/out axes need a dense runtime coefficient array")
        assert w.ndim == no + nr + 2, (w.shape, no, nr)
        assert time_steps == 1, (
            "temporal blocking does not commute with a channel reduction: "
            "iterate t must see the *summed* output of iterate t-1, which "
            "only exists after the full reduce sweep")
    if plan.stages:
        assert time_steps == 1, "a fused pipeline already is the fusion"
        assert isinstance(w, tuple) and len(w) == len(plan.stages), (
            "fused plans take one coefficient entry per stage (None for "
            "'table' stages)", plan.kind)
    if any(v > 1 for v in plan.stride_per_axis()):
        assert nd == 2 and time_steps == 1 and not plan.stages, (
            "output strides support single 2-D plan applications")
    epi_entries = chain_epilogue_operand_stages(plan)
    assert len(epilogue_args) == len(epi_entries), (
        "epilogue_args must match the chain's operand-bearing epilogue "
        "stages, in application order", [s.op for s in epi_entries])
    t = time_steps
    spatial_in = x.shape[nb + nr:]
    out_sp = plan.out_shape(spatial_in, t)
    assert all(o >= 1 for o in out_sp), (spatial_in, out_sp)

    B = tuple(min(b, o) for b, o in zip(block, out_sp))
    g = tuple(pl.cdiv(o, b) for o, b in zip(out_sp, B))
    stride = plan.stride_per_axis()
    # Origin + round-up padding (core.halo): t·lead zeros ahead of the
    # origin, then enough behind so every (including the last) overlapped
    # input block is in-bounds.
    in_block = _round_block(plan.block_in_shape(B, t), B, g, in_tile)
    # The tiling reads (g−1)·b·stride + in_block rows per axis, which
    # covers origin_pads' round-up plus any tile round-up of in_block.
    extent = [(gi - 1) * b * v + ib
              for gi, b, v, ib in zip(g, B, stride, in_block)]
    pads = [(0, 0)] * (nb + nr) + [
        (lo, max(0, e - lo - s)) for (lo, _), e, s in zip(
            origin_pads(plan, spatial_in, g, B, t), extent, spatial_in)]
    # Crop what a strided tiling never reads, so a one-step grid's input
    # block spans exactly the whole operand.
    with jax.named_scope(scopes.ENGINE_PAD):
        xp = jnp.pad(x, pads)[(slice(None),) * (nb + nr)
                              + tuple(slice(0, e) for e in extent)]

    # Grid layout: batch × out × spatial × reduce — reduce innermost so
    # the sweep over it is sequential per output block and the scratch
    # accumulator carries (the matmul-k pattern of the TPU grid).
    batch_dims = x.shape[:nb]
    out_dims = w.shape[:no] if no else ()
    red_dims = x.shape[nb:nb + nr]
    grid = batch_dims + out_dims + g + red_dims
    sp0 = nb + no                      # first spatial grid dim
    rd0 = sp0 + nd                     # first reduce grid dim

    # Overlapped input blocks (§4.5): element-indexed specs — output tiles
    # are disjoint, input tiles overlap by the halo, so grid steps never
    # communicate (the TPU analogue of the paper's branch-free warp blocks).
    # An output-strided grid reads input tiles at stride-scaled origins.
    # The kernel crops its result to the output block, so the rows and
    # lanes the tile round-up adds are read but never stored. A one-step
    # axis reads at a literal 0: Mosaic cannot prove that i·b is a tile
    # multiple when b is an unaligned whole-axis block (a halo frame).
    x_spec = pl.BlockSpec(
        tuple(pl.Element(n) for n in (1,) * (nb + nr) + in_block),
        lambda *ids: ids[:nb] + ids[rd0:rd0 + nr] + tuple(
            i * b * v if gi > 1 else 0
            for i, b, v, gi in zip(ids[sp0:sp0 + nd], B, stride, g)),
    )
    in_specs = [x_spec]
    operands = [xp]
    if plan.stages:
        for stage, w_s in zip(plan.stages, w):
            if stage.coeff_mode == "table":
                assert w_s is None, (stage.kind, "table stage took a w")
                continue
            fil = w_s.shape
            in_specs.append(pl.BlockSpec(
                fil, lambda *ids, _n=len(fil): (0,) * _n))
            operands.append(w_s)
    elif plan.coeff_mode == "dense":
        fil = w.shape[no + nr:]
        in_specs.append(pl.BlockSpec(
            (1,) * (no + nr) + fil,
            lambda *ids: ids[nb:nb + no] + ids[rd0:rd0 + nr]
            + (0,) * len(fil)))
        operands.append(w)
    elif plan.coeff_mode == "perlane":
        assert w.shape[-1] == spatial_in[-1], (w.shape, spatial_in)
        with jax.named_scope(scopes.ENGINE_PAD):
            wp = jnp.pad(w, ((0, 0), (0, g[-1] * B[-1] - w.shape[-1])))
        in_specs.append(
            pl.BlockSpec((w.shape[0], B[-1]),
                         lambda *ids: (0, ids[sp0 + nd - 1])))
        operands.append(wp)

    # Epilogue operands (DESIGN.md §11): bias rides per-channel/lane/
    # scalar, a residual rides blocked exactly like the output.
    for st, arr in zip(epi_entries, epilogue_args):
        if st.op == "bias":
            if no:
                assert arr.shape == out_dims, (arr.shape, out_dims)
                in_specs.append(pl.BlockSpec(
                    (1,) * no, lambda *ids: ids[nb:nb + no]))
                operands.append(arr)
            elif plan.coeff_mode == "perlane" and not plan.stages:
                assert arr.shape == (spatial_in[-1],), (arr.shape, spatial_in)
                with jax.named_scope(scopes.ENGINE_PAD):
                    bp = jnp.pad(arr, (0, g[-1] * B[-1] - arr.shape[-1]))
                in_specs.append(pl.BlockSpec(
                    (B[-1],), lambda *ids: (ids[sp0 + nd - 1],)))
                operands.append(bp)
            else:
                assert arr.size == 1, ("scalar bias expected for "
                                       f"{plan.kind!r}", arr.shape)
                in_specs.append(pl.BlockSpec((1,), lambda *ids: (0,)))
                with jax.named_scope(scopes.ENGINE_PAD):
                    operands.append(jnp.reshape(arr, (1,)))
        else:                           # residual_add: output layout
            assert arr.shape == batch_dims + out_dims + out_sp, (
                arr.shape, batch_dims + out_dims + out_sp)
            with jax.named_scope(scopes.ENGINE_PAD):
                rp = jnp.pad(arr, [(0, 0)] * (nb + no) + [
                    (0, gi * bi - o) for gi, bi, o in zip(g, B, out_sp)])
            in_specs.append(pl.BlockSpec(
                (1,) * (nb + no) + B, lambda *ids: ids[:rd0]))
            operands.append(rp)

    with jax.named_scope(scopes.ENGINE_KERNEL):
        out = pl.pallas_call(
            make_kernel(B),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1,) * (nb + no) + B,
                                   lambda *ids: ids[:rd0]),
            out_shape=jax.ShapeDtypeStruct(
                batch_dims + out_dims
                + tuple(gi * bi for gi, bi in zip(g, B)), x.dtype),
            scratch_shapes=make_scratch(B, in_block),
            interpret=interpret,
            name=scopes.WINDOW_KERNEL,
        )(*operands)
    with jax.named_scope(scopes.ENGINE_CROP):
        return out[(slice(None),) * (nb + no)
                   + tuple(slice(0, o) for o in out_sp)]


@functools.partial(
    jax.jit,
    static_argnames=("plan", "block", "time_steps", "variant", "interpret",
                     "acc_dtype", "strategy"),
)
def _run_window_plan_tpu(
    x: jax.Array,
    w=None,
    *,
    plan: SystolicPlan,
    block: tuple[int, ...],
    time_steps: int = 1,
    variant: str = "shift_psum",
    interpret: bool = True,
    acc_dtype=jnp.float32,
    epilogue_args: tuple = (),
    strategy: str | None = None,
) -> jax.Array:
    """The TPU lowering: 8×128 sublane/lane tiles, VPU lane rolls for
    ``shift_psum``, fp32 VMEM scratch for the reduce accumulator."""
    if strategy is not None:
        # kwarg convenience for the thin family wrappers + tuner replay:
        # the strategy still lives on the plan IR (adjoints/fusion
        # inherit it from there), this just pins it at the call site.
        plan = dataclasses.replace(plan, strategy=strategy)

    def make_kernel(B):
        return functools.partial(
            _window_kernel, plan=plan, block=B, time_steps=time_steps,
            variant=variant, acc_dtype=acc_dtype)

    def make_scratch(B, in_block):
        return [pltpu.VMEM(B, acc_dtype)] if plan.reduce_axes else []

    with _obs_lowering(plan=plan, block=block, backend="tpu",
                       time_steps=time_steps, variant=variant):
        return _window_call(
            x, w, plan=plan, block=block, time_steps=time_steps,
            variant=variant, interpret=interpret, acc_dtype=acc_dtype,
            epilogue_args=epilogue_args, make_kernel=make_kernel,
            make_scratch=make_scratch, in_tile=tpu_tile(x.dtype))


def run_window_plan(
    x: jax.Array,
    w=None,
    *,
    plan: SystolicPlan,
    block: tuple[int, ...],
    time_steps: int = 1,
    variant: str = "shift_psum",
    interpret: bool = True,
    acc_dtype=jnp.float32,
    epilogue_args: tuple = (),
    strategy: str | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Lower a windowed plan to a Pallas call and run it.

    Args:
      x: ``batch_axes + reduce_axes + ndim_spatial``-dim input, lane axis
        last.
      w: runtime coefficients for ``coeff_mode`` 'dense' (full filter,
        prefixed by ``out_axes + reduce_axes`` channel axes for reduce
        plans) or 'perlane' (``(K, lanes)`` rows); None for 'table' plans.
        For a fused pipeline (``plan.stages``), a tuple with one entry
        per stage — an array for 'dense' stages, None for 'table' ones.
      plan: the systolic schedule + geometry (lead/trail, footprint).
      block: output block size per windowed axis, lane axis last.
      time_steps: fused plan applications per block (§6.4).
      epilogue_args: runtime operands of the chain's operand-bearing
        epilogue stages, in application order (mid-chain ``bias``
        entries first for fused pipelines, the final stage's last) —
        ``bias`` (per-C_out for out-axes plans, per-lane for perlane
        plans, scalar otherwise; always scalar mid-chain) and/or
        ``residual_add`` (shaped like the output, final stage only).
      strategy: pin the lowering strategy for this call ('lanes' or
        'mxu', DESIGN.md §13); None keeps whatever the plan carries.
      backend: which lowering of the plan IR to emit — 'tpu'
        (:func:`_run_window_plan_tpu`), 'gpu'
        (:func:`repro.core.engine_gpu.run_window_plan_gpu`: warp-shuffle
        psum shifts + SMEM halo skirt, DESIGN.md §14) or 'auto'; None
        defers to :func:`repro.config.engine_backend`. Both backends run
        under ``interpret=True`` on any host, which is how CI proves
        their equivalence.

    Returns:
      The plan's output, ``batch + out_axes + spatial``-shaped: per
      windowed axis, ``out = (in + t·(lead+trail) − t·(ext−1) − 1) //
      stride + 1``; reduce axes are contracted away (fp32 grid
      accumulator).
    """
    from repro.config import engine_backend, resolve_engine_backend

    backend = (resolve_engine_backend(backend) if backend is not None
               else engine_backend())
    kw = dict(plan=plan, block=block, time_steps=time_steps, variant=variant,
              interpret=interpret, acc_dtype=acc_dtype,
              epilogue_args=epilogue_args, strategy=strategy)
    eff = dataclasses.replace(plan, strategy=strategy) if strategy else plan
    strat = (eff.strategy or "lanes") if eff.combine == "fma" else eff.combine
    rfaults.check("engine.window")
    # Under a jit trace this body runs once per compilation and launches
    # nothing; only an eager call dispatches the kernel.
    eager = not isinstance(x, jax.core.Tracer)
    if eager:
        obs.metrics.inc("engine.launch", f"{backend}:{strat}")
    t0 = time.perf_counter()
    with obs.span("engine.run_window_plan", cat="engine", kind=plan.kind,
                  backend=backend, strategy=strat):
        if backend == "gpu":
            from . import engine_gpu

            out = engine_gpu.run_window_plan_gpu(x, w, **kw)
        else:
            out = _run_window_plan_tpu(x, w, **kw)
    if eager and obs.drift.per_call():
        _obs_call_drift(eff, block, backend, time_steps, variant, out, t0,
                        x.shape)
    return out


def run_window_plan_mxu(x: jax.Array, w=None, *, plan: SystolicPlan, **kw):
    """:func:`run_window_plan` with the tap-set contraction forced onto
    the MXU: im2row over the tap set in VMEM + one ``dot_general`` per
    block application (DESIGN.md §13). Equivalent to pinning
    ``strategy='mxu'`` on the plan (and on every fused stage, via
    inheritance); same signature, same output to fp32 tolerance as the
    lanes schedule.
    """
    return run_window_plan(
        x, w, plan=dataclasses.replace(plan, strategy="mxu"), **kw)


# ---------------------------------------------------------------------------
# Windowed family: backward-weight (the adjoint correlation, DESIGN.md §10)
# ---------------------------------------------------------------------------

def _wgrad_dense_kernel(x_ref, g_ref, o_ref, acc_ref, *, exts, block,
                        acc_dtype):
    """One reduce iterate of ``∂L/∂w[n,m] = Σ_{b,o} g[b,o]·xp[b,o+(n,m)]``.

    The filter footprint is the *output* here; every grid step over
    batch × cotangent tiles is a reduce iterate contributing one
    filter-shaped partial to the fp32 scratch accumulator — the same
    accumulator pattern as the NCHW channel reduction, with batch and
    the spatial tiles playing the reduction.
    """
    N, M = exts
    bh, bw = block
    xb = x_ref[0, 0].astype(acc_dtype)
    gb = g_ref[0, 0].astype(acc_dtype)
    contrib = jnp.stack([
        jnp.stack([jnp.sum(xb[n:n + bh, m:m + bw] * gb) for m in range(M)])
        for n in range(N)])
    _accumulate_over_reduce(acc_ref, o_ref, contrib, (2, 3, 4), (0, 0))


def _wgrad_perlane_kernel(x_ref, g_ref, o_ref, acc_ref, *, K, block,
                          acc_dtype):
    """Per-lane backward-weight: ``∂L/∂w[k,d] = Σ_{b,t} g[b,t,d]·xp[b,t+k,d]``.

    Lanes (channels) are an *output* grid axis; batch and the time tiles
    are the reduce sweep.
    """
    bt, _ = block
    xb = x_ref[0].astype(acc_dtype)
    gb = g_ref[0].astype(acc_dtype)
    contrib = jnp.stack([
        jnp.sum(xb[k:k + bt, :] * gb, axis=0) for k in range(K)])
    _accumulate_over_reduce(acc_ref, o_ref, contrib, (1, 2), ...)


@functools.partial(
    jax.jit,
    static_argnames=("plan", "block", "interpret", "acc_dtype", "pre_padded"),
)
def run_weight_grad_plan(
    x: jax.Array,
    g: jax.Array,
    *,
    plan: SystolicPlan,
    block: tuple[int, ...] = (8, 128),
    interpret: bool = True,
    acc_dtype=jnp.float32,
    pre_padded: bool = False,
) -> jax.Array:
    """Backward-weight of a windowed plan: ``∂L/∂w`` of
    ``y = run_window_plan(x, w, plan=plan)`` given the cotangent ``g``.

    This is the adjoint *correlation* expressed through the engine's
    reduce machinery (DESIGN.md §10): batch and the cotangent's spatial
    tiles become block-1 grid **reduce** iterates, each accumulating a
    filter-shaped partial (``Σ`` over the tile of ``g · shifted x``) in
    an fp32 VMEM scratch block that is flushed once at the end of the
    sweep. The output is the coefficient array's own shape — tiny — so
    the whole gradient is one ``pallas_call`` with no Python loop over
    batch, channels or tiles.

    Args:
      x: the forward input (same layout run_window_plan consumed).
      g: the cotangent, shaped like the forward output.
      block: tile of ``g``'s spatial axes per reduce iterate (clamped).
      pre_padded: the sharded path passes ``x`` already halo-extended by
        the plan's lead/trail (neighbor rows via ppermute); skip the
        origin padding then.

    Returns:
      ``∂L/∂w`` in ``acc_dtype`` with the forward coefficient layout:
      ``(N, M)`` dense, ``(C_out, C_in, N, M)`` NCHW (out+reduce
      leading), ``(K, D)`` perlane.
    """
    if plan.combine != "fma" or plan.coeff_mode == "table":
        raise ValueError(
            f"no weight gradient for {plan.kind!r} "
            f"(combine={plan.combine!r}, coeff_mode={plan.coeff_mode!r})")
    # Jitted directly: fires once per compilation (recompile count).
    obs.metrics.inc("engine.lowering", f"tpu:wgrad-{plan.kind}")
    nb, nr, no = plan.batch_axes, plan.reduce_axes, plan.out_axes

    if plan.coeff_mode == "perlane":
        K = plan.N
        B, T, D = x.shape
        assert g.shape[0] == B and g.shape[2] == D, (x.shape, g.shape)
        lead = 0 if pre_padded else (plan.lead or (0, 0))[0]
        Tg = g.shape[1]
        assert Tg == T + lead + (0 if pre_padded else
                                 (plan.trail or (0, 0))[0]) - (K - 1), \
            (x.shape, g.shape)
        bt, bd = min(block[0], Tg), min(block[1], D)
        gt, gd = pl.cdiv(Tg, bt), pl.cdiv(D, bd)
        # The lane dim is unhaloed (bd is a lane multiple or all of D);
        # only the time dim's K−1 halo needs the tile round-up.
        it = _round_block((bt + K - 1, bd), (bt, bd), (gt, gd),
                          tpu_tile(x.dtype))[0]
        with jax.named_scope(scopes.ENGINE_PAD):
            gp = jnp.pad(g, ((0, 0), (0, gt * bt - Tg), (0, gd * bd - D)))
            xp = jnp.pad(x, ((0, 0), (lead, (gt - 1) * bt + it - lead - T),
                             (0, gd * bd - D)))
        kern = functools.partial(_wgrad_perlane_kernel, K=K, block=(bt, bd),
                                 acc_dtype=acc_dtype)
        with jax.named_scope(scopes.ENGINE_KERNEL):
            out = pl.pallas_call(
                kern,
                grid=(gd, B, gt),           # lanes out; batch × time reduce
                in_specs=[
                    pl.BlockSpec(
                        (pl.Element(1), pl.Element(it), pl.Element(bd)),
                        lambda d, b, i: (b, i * bt if gt > 1 else 0,
                                         d * bd)),
                    pl.BlockSpec((1, bt, bd), lambda d, b, i: (b, i, d)),
                ],
                out_specs=pl.BlockSpec((K, bd), lambda d, b, i: (0, d)),
                out_shape=jax.ShapeDtypeStruct((K, gd * bd), acc_dtype),
                scratch_shapes=[pltpu.VMEM((K, bd), acc_dtype)],
                interpret=interpret,
                name=scopes.WGRAD_KERNEL,
            )(xp, gp)
        with jax.named_scope(scopes.ENGINE_CROP):
            return out[:, :D]

    assert plan.coeff_mode == "dense" and plan.ndim_spatial == 2, plan.kind
    assert no == nr, (no, nr)            # plain dense (0,0) or NCHW (1,1)
    N, M = plan.exts
    x4 = x if nb else x[None]
    x4 = x4 if nr else x4[:, None]       # (B, C_in, H, W)
    g4 = g if nb else g[None]
    g4 = g4 if no else g4[:, None]       # (B, C_out, H', W')
    B, C_in, H, W = x4.shape
    _, C_out, Ho, Wo = g4.shape
    lead, trail = ((0, 0), (0, 0)) if pre_padded else plan.lead_trail()
    assert Ho == H + lead[0] + trail[0] - (N - 1), (x.shape, g.shape)
    assert Wo == W + lead[1] + trail[1] - (M - 1), (x.shape, g.shape)
    bh, bw = min(block[0], Ho), min(block[1], Wo)
    gh, gw = pl.cdiv(Ho, bh), pl.cdiv(Wo, bw)
    ih, iw = _round_block((bh + N - 1, bw + M - 1), (bh, bw), (gh, gw),
                          tpu_tile(x.dtype))
    with jax.named_scope(scopes.ENGINE_PAD):
        gp = jnp.pad(g4, ((0, 0), (0, 0), (0, gh * bh - Ho),
                          (0, gw * bw - Wo)))
        xp = jnp.pad(x4, ((0, 0), (0, 0),
                          (lead[0], (gh - 1) * bh + ih - lead[0] - H),
                          (lead[1], (gw - 1) * bw + iw - lead[1] - W)))
    kern = functools.partial(_wgrad_dense_kernel, exts=(N, M),
                             block=(bh, bw), acc_dtype=acc_dtype)
    with jax.named_scope(scopes.ENGINE_KERNEL):
        out = pl.pallas_call(
            kern,
            grid=(C_out, C_in, B, gh, gw),  # channels out; batch×tiles reduce
            in_specs=[
                pl.BlockSpec(tuple(pl.Element(n) for n in (1, 1, ih, iw)),
                             lambda co, ci, b, i, j: (
                                 b, ci, i * bh if gh > 1 else 0,
                                 j * bw if gw > 1 else 0)),
                pl.BlockSpec((1, 1, bh, bw),
                             lambda co, ci, b, i, j: (b, co, i, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, N, M),
                                   lambda co, ci, b, i, j: (co, ci, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((C_out, C_in, N, M), acc_dtype),
            scratch_shapes=[pltpu.VMEM((N, M), acc_dtype)],
            interpret=interpret,
            name=scopes.WGRAD_KERNEL,
        )(xp, gp)
    with jax.named_scope(scopes.ENGINE_CROP):
        return out if no else out[0, 0]


# ---------------------------------------------------------------------------
# Scan family: cumsum / linear recurrence (§3.6, Fig. 1e)
# ---------------------------------------------------------------------------

def _scan_kernel(*refs, plan: SystolicPlan, acc_dtype, has_carry: bool,
                 want_carry: bool):
    """Kogge–Stone over one ``(BR, BT)`` tile, carry across grid steps.

    Ref layout: ``(*data_ins, [c_ref], o_ref, [co_ref], scratch)`` — the
    optional ``c_ref`` seeds the VMEM carry at the first sequential tile
    (inter-chunk carry-in), the optional ``co_ref`` publishes the final
    carry (its block index ignores the sequential axis, so the last grid
    step's write wins).
    """
    carry = refs[-1]
    idx = len(refs) - 1
    co_ref = None
    if want_carry:
        idx -= 1
        co_ref = refs[idx]
    idx -= 1
    o_ref = refs[idx]
    c_ref = None
    if has_carry:
        idx -= 1
        c_ref = refs[idx]
    ins = refs[:idx]

    @pl.when(pl.program_id(1) == 0)
    def _reset():
        if has_carry:
            carry[:] = c_ref[:].astype(carry.dtype)   # h₋₁ = carry-in
        else:
            carry[:] = jnp.zeros_like(carry)   # h₋₁ = 0 for both combines

    def store(s):
        # The epilogue applies to the *stored* copy only (DESIGN.md §11);
        # the inter-block carry keeps the raw scan state — fusing an
        # activation must not corrupt the recurrence.
        out = s
        for st in plan.epilogue:
            out = _apply_epilogue_val(st, out, None, plan, acc_dtype, None)
        o_ref[:] = out.astype(o_ref.dtype)

    lane = jax.lax.broadcasted_iota(jnp.int32, ins[0].shape, 1)
    if plan.combine == "add":
        s = ins[0][:].astype(acc_dtype)
        for step in plan.steps:           # ctrl() of Eq. 1 gates each arrow
            shifted = jnp.roll(s, step.shift, axis=1)
            s = s + jnp.where(lane >= step.shift, shifted, jnp.zeros_like(s))
        s = s + carry[:]                  # inter-block carry (scratchpad)
        carry[:] = s[:, -1:]
        store(s)
    elif plan.combine == "linrec":
        A = ins[0][:].astype(acc_dtype)   # transfer pairs (a, b)
        B = ins[1][:].astype(acc_dtype)
        for step in plan.steps:
            As = jnp.roll(A, step.shift, axis=1)
            Bs = jnp.roll(B, step.shift, axis=1)
            ctrl = lane >= step.shift
            As = jnp.where(ctrl, As, jnp.ones_like(As))   # identity (1, 0)
            Bs = jnp.where(ctrl, Bs, jnp.zeros_like(Bs))
            A, B = A * As, A * Bs + B     # f_t ∘ f_{t−d}
        h = A * carry[:] + B              # prefix applied to the carry
        carry[:] = h[:, -1:]
        store(h)
    else:
        raise ValueError(plan.combine)
    if want_carry:
        co_ref[:] = carry[:].astype(co_ref.dtype)


def _scan_call(
    *operands: jax.Array,
    plan: SystolicPlan,
    block_r: int,
    interpret: bool,
    acc_dtype,
    carry: jax.Array | None,
    return_carry: bool,
    make_kernel,
    make_scratch,
):
    """Backend-shared scan-family driver (DESIGN.md §14): identity-element
    padding, the ``(R, T)`` tiling with T sequential, carry-in/-out spec
    plumbing. The backend contributes the Kogge–Stone kernel body
    (``make_kernel()``) and its carry scratch (``make_scratch(BR)``)."""
    if epilogue_operand_stages(plan.epilogue):
        raise ValueError(
            f"scan plans take operand-free epilogue stages only, got "
            f"{[s.op for s in plan.epilogue]}: bias/residual operands "
            "have no blocked layout along the sequential carry")
    R, T = operands[0].shape
    BT = plan.S
    BR = min(block_r, R)
    gr, gt = pl.cdiv(R, BR), pl.cdiv(T, BT)
    pad = ((0, gr * BR - R), (0, gt * BT - T))
    has_carry = carry is not None
    with jax.named_scope(scopes.ENGINE_PAD):
        if plan.combine == "linrec":
            a, b = operands
            assert a.shape == b.shape
            padded = [jnp.pad(a, pad, constant_values=1), jnp.pad(b, pad)]
        else:
            padded = [jnp.pad(operands[0], pad)]
        if has_carry:
            c = carry.reshape(R, 1).astype(operands[0].dtype)
            padded.append(jnp.pad(c, ((0, gr * BR - R), (0, 0))))

    kern = make_kernel(has_carry)
    in_specs = [pl.BlockSpec((BR, BT), lambda i, j: (i, j))] * (len(padded)
                                                                - has_carry)
    if has_carry:
        in_specs.append(pl.BlockSpec((BR, 1), lambda i, j: (i, 0)))
    out_specs = pl.BlockSpec((BR, BT), lambda i, j: (i, j))
    out_shape = jax.ShapeDtypeStruct((gr * BR, gt * BT), operands[0].dtype)
    if return_carry:
        # carry-out block ignores j: each sequential step overwrites it,
        # so the value left behind is the final state of the row tile.
        out_specs = (out_specs, pl.BlockSpec((BR, 1), lambda i, j: (i, 0)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((gr * BR, 1), operands[0].dtype))
    with jax.named_scope(scopes.ENGINE_KERNEL):
        res = pl.pallas_call(
            kern,
            grid=(gr, gt),                # T sequential per row-tile
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=make_scratch(BR),
            interpret=interpret,
            name=scopes.SCAN_KERNEL,
        )(*padded)
    with jax.named_scope(scopes.ENGINE_CROP):
        if return_carry:
            out, co = res
            return out[:R, :T], co[:R]
        return res[:R, :T]


@functools.partial(
    jax.jit, static_argnames=("plan", "block_r", "interpret", "acc_dtype",
                              "return_carry")
)
def _run_scan_plan_tpu(
    *operands: jax.Array,
    plan: SystolicPlan,
    block_r: int = 8,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    carry: jax.Array | None = None,
    return_carry: bool = False,
):
    """The TPU scan lowering: VPU lane rolls for the Kogge–Stone arrows,
    inter-tile carry in VMEM scratch."""

    def make_kernel(has_carry):
        return functools.partial(_scan_kernel, plan=plan,
                                 acc_dtype=acc_dtype, has_carry=has_carry,
                                 want_carry=return_carry)

    def make_scratch(BR):
        return [pltpu.VMEM((BR, 1), acc_dtype)]

    with _obs_lowering(plan=plan, block=(block_r, plan.S), backend="tpu"):
        return _scan_call(
            *operands, plan=plan, block_r=block_r, interpret=interpret,
            acc_dtype=acc_dtype, carry=carry, return_carry=return_carry,
            make_kernel=make_kernel, make_scratch=make_scratch)


def run_scan_plan(
    *operands: jax.Array,
    plan: SystolicPlan,
    block_r: int = 8,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    carry: jax.Array | None = None,
    return_carry: bool = False,
    backend: str | None = None,
):
    """Lower a scan/recurrence plan over ``(R, T)`` operands.

    ``plan.S`` is the lane-tile width BT (a power of two); T is tiled into
    sequential grid steps whose carries ride in VMEM scratch. Padding uses
    the combine's identity element ('add': 0; 'linrec': (1, 0)) so padded
    tail lanes are no-ops. ``plan.epilogue`` may carry *operand-free*
    elementwise stages (gelu/silu/relu/scale), applied to the stored
    output only — the carry keeps the raw scan state.

    ``carry`` (``(R,)`` or ``(R, 1)``) seeds the VMEM carry — the state
    h₋₁ entering the first tile — and ``return_carry=True`` additionally
    returns the final raw state ``(R, 1)``; together they promote the
    intra-kernel VMEM carry to an inter-chunk carry (DESIGN.md §12).

    ``backend`` picks the lowering ('tpu'/'gpu'/'auto', DESIGN.md §14);
    None defers to :func:`repro.config.engine_backend`. The GPU lowering
    runs Kogge–Stone arrows shorter than a warp as intra-warp shuffles
    and warp-crossing arrows through the shared-memory hand-off.
    """
    from repro.config import engine_backend, resolve_engine_backend

    backend = (resolve_engine_backend(backend) if backend is not None
               else engine_backend())
    kw = dict(plan=plan, block_r=block_r, interpret=interpret,
              acc_dtype=acc_dtype, carry=carry, return_carry=return_carry)
    rfaults.check("engine.scan")
    eager = not isinstance(operands[0], jax.core.Tracer)
    if eager:                # a jit trace lowers; only an eager call runs
        obs.metrics.inc("engine.launch", f"{backend}:{plan.combine}")
    t0 = time.perf_counter()
    with obs.span("engine.run_scan_plan", cat="engine", kind=plan.kind,
                  backend=backend, strategy=plan.combine):
        if backend == "gpu":
            from . import engine_gpu

            out = engine_gpu.run_scan_plan_gpu(*operands, **kw)
        else:
            out = _run_scan_plan_tpu(*operands, **kw)
    if eager and obs.drift.per_call():
        _obs_call_drift(plan, (block_r, plan.S), backend, 1, "shift_psum",
                        out, t0, operands[0].shape)
    return out


def check_chunk_geometry(plan: SystolicPlan, chunk: int) -> None:
    """Pre-pallas guards for the chunk-streamed scan schedule.

    Named errors (PR 4/5 pattern) so bad geometry fails before tracing a
    kernel: the chunk must hold a whole number of lane tiles, and the
    streamed path keeps the raw state in the ``lax.scan`` carry — fused
    epilogues would make the recomputed backward state disagree with the
    stored forward copy, so they are rejected here.
    """
    if plan.epilogue_op_count():
        raise ValueError(
            f"{plan.kind}: epilogue stages are illegal under chunking — the "
            "chunk-streamed schedule carries the raw scan state between "
            "chunks and recomputes it on backward; apply activations to "
            "the streamed output instead")
    if chunk < plan.S:
        raise ValueError(
            f"{plan.kind}: chunk={chunk} is smaller than the lane tile "
            f"S={plan.S}; a chunk must hold at least one Kogge–Stone tile")
    if chunk % plan.S:
        raise ValueError(
            f"{plan.kind}: chunk={chunk} is not a multiple of the lane "
            f"tile S={plan.S}; partial tiles would shift the carry "
            "hand-off off the tile boundary")


def run_scan_plan_chunked(
    *operands: jax.Array,
    plan: SystolicPlan,
    chunk: int,
    block_r: int = 8,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    carry: jax.Array | None = None,
    return_carry: bool = False,
    backend: str | None = None,
):
    """Stream a scan/recurrence plan over ``(R, chunk)`` slabs (§12).

    Runs :func:`run_scan_plan` inside a ``lax.scan`` whose carry is the
    per-row state, so peak live state is O(R·chunk) instead of O(R·T):
    the transfer-pair algebra that already composes across lane shifts
    composes identically across chunks. The body is ``jax.checkpoint``-
    wrapped — reverse-mode through this runner saves only the O(T/chunk)
    chunk-boundary carries and recomputes in-chunk state.
    """
    check_chunk_geometry(plan, chunk)
    R, T = operands[0].shape
    nc = pl.cdiv(T, chunk)
    pad_t = ((0, 0), (0, nc * chunk - T))
    if plan.combine == "linrec":
        a, b = operands
        padded = (jnp.pad(a, pad_t, constant_values=1), jnp.pad(b, pad_t))
    else:
        padded = (jnp.pad(operands[0], pad_t),)
    c0 = (jnp.zeros((R, 1), operands[0].dtype) if carry is None
          else carry.reshape(R, 1).astype(operands[0].dtype))

    def body(c, i):
        slabs = tuple(jax.lax.dynamic_slice_in_dim(o, i * chunk, chunk, 1)
                      for o in padded)
        out, c_new = run_scan_plan(
            *slabs, plan=plan, block_r=block_r, interpret=interpret,
            acc_dtype=acc_dtype, carry=c, return_carry=True,
            backend=backend)
        return c_new, out

    c_fin, outs = jax.lax.scan(jax.checkpoint(body), c0, jnp.arange(nc))
    out = jnp.moveaxis(outs, 0, 1).reshape(R, nc * chunk)[:, :T]
    return (out, c_fin) if return_carry else out
