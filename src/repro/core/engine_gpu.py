"""Plan→Pallas GPU lowering — the paper's own target (DESIGN.md §14).

The source paper is a *GPU* execution model: partial sums hop between
CUDA threads via ``__shfl_up_sync``, the register file is the cache, and
shared memory holds only what registers cannot. This module lowers the
unchanged :class:`repro.core.plan.SystolicPlan` IR onto that shape. The
lowering map (§14):

==============================  =======================================
plan-IR construct               GPU primitive
==============================  =======================================
``shift_psum`` lane roll        ``__shfl_up_sync`` within each 32-lane
                                warp + a shared-memory hand-off for the
                                lane that crosses the warp boundary
halo lead/trail geometry        shared-memory staging of the block
                                skirt (interior + halo loaded once)
accumulator / valid-lane crop   per-thread register accumulator arrays
``strategy='mxu'``              tensor-core im2row (the same
                                dialect-neutral ``dot_general`` as §13)
==============================  =======================================

**Emulation caveat (documented, by design):** the current JAX Pallas
GPU dialects (Triton, Mosaic-GPU) expose block-level array ops, not a
per-thread ``shfl_up`` intrinsic. :func:`warp_shift` therefore *models*
the shuffle as its exact semantic decomposition — an intra-warp roll
(the ``__shfl_up_sync`` picture) stitched to a previous-warp tail
hand-off (the SMEM picture), which composes to precisely
``jnp.roll(v, shift, axis=-1)``. That makes the GPU lowering **bitwise
equal** to the TPU lane roll for the same block geometry, which is what
lets interpret-mode CI prove backend equivalence on any host; on a real
CUDA build the same decomposition is what a Mosaic-GPU warpgroup
executes natively. Lane extents that are not a whole number of warps
fall back to the plain roll (same values, no warp decomposition).

Geometry (padding, overlapped BlockSpecs, grids, crops) is shared with
the TPU path through :func:`repro.core.engine._window_call` /
:func:`repro.core.engine._scan_call`, so the two backends cannot drift:
a backend contributes only its kernel body and scratch request.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
# Mosaic-GPU's SMEM memory space; interpret mode on CPU hosts runs it as a
# faithful stand-in.
from jax.experimental.pallas import mosaic_gpu as plgpu

from repro.robust import faults as rfaults

from . import engine
from .plan import (EPILOGUE_OPERANDS, GPU_WARP_LANES, SystolicPlan,
                   chain_epilogue_operand_stages)
from .fuse import pipeline_coeff_count

GPU_BLOCK_WARPS = 4      # CUDA-guide default block: 128 threads / 4 warps


def warp_shift(v: jnp.ndarray, shift: int,
               warp: int = GPU_WARP_LANES) -> jnp.ndarray:
    """Shift ``v`` along the lane (last) axis the way a GPU warp would.

    Decomposes ``shift = q·warp + r`` (``0 ≤ r < warp``): the
    ``q``-warp part is a whole-warp hand-off (warp *i*'s registers go to
    warp ``i+q`` — on hardware, a shared-memory exchange), and the
    ``r``-lane part is ``__shfl_up_sync(0xffffffff, x, r)`` inside each
    warp, with the ``r`` boundary lanes taking the previous warp's tail
    through shared memory. The composition is exactly
    ``jnp.roll(v, shift, axis=-1)`` — bitwise, it is a pure reindexing —
    which is the equivalence interpret-mode CI asserts
    (``tests/test_engine_gpu.py::TestWarpShift``).

    Negative ``shift`` (the shift_data variant pulls data *down*) maps
    to ``__shfl_down_sync`` the same way via Python's floor divmod.
    """
    if shift == 0:
        return v
    S = v.shape[-1]
    if S % warp:
        # No clean warp decomposition for a fractional-warp lane extent:
        # fall back to the plain roll (documented emulation, same values).
        return jnp.roll(v, shift, axis=-1)
    q, r = divmod(shift, warp)
    if q:
        v = jnp.roll(v, q * warp, axis=-1)      # whole-warp SMEM hand-off
    if r:
        w = v.reshape(v.shape[:-1] + (S // warp, warp))
        intra = jnp.roll(w, r, axis=-1)         # __shfl_up_sync(…, r)
        tail = jnp.roll(jnp.roll(w, 1, axis=-2), r, axis=-1)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, w.ndim - 1)
        # Lanes [0, r) fell off the shuffle's low edge: they take the
        # previous warp's top r registers (the SMEM boundary hand-off).
        v = jnp.where(lane < r, tail, intra).reshape(v.shape)
    return v


def _apply_plan_once_gpu(xb, stage: SystolicPlan, w_ref, variant: str,
                         acc_dtype, strategy: str = "lanes"):
    """One application of ``stage`` with GPU-shaped data movement.

    Same tap walk and accumulation *order* as
    :func:`repro.core.engine._apply_plan_once` — hence the same fp
    results — but every lane roll goes through :func:`warp_shift`
    (shuffle + warp-boundary hand-off) and the partial sums live in the
    per-thread register accumulator ``s``. ``strategy='mxu'`` routes to
    the tensor core via the dialect-neutral im2row ``dot_general``
    (§13's :func:`~repro.core.engine._apply_plan_mxu` — on CUDA that
    contraction is an ``mma.sync``).
    """
    if strategy == "mxu":
        return engine._apply_plan_mxu(xb, stage, w_ref, acc_dtype)
    if any(v > 1 for v in stage.stride_per_axis()):
        # Output-strided plans are data-stationary static gathers — no
        # shuffles on either backend; share the schedule verbatim.
        return engine._apply_plan_once(xb, stage, w_ref, variant, acc_dtype)
    exts = stage.exts
    M = stage.M
    valid = tuple(n - (e - 1) for n, e in zip(xb.shape, exts))
    # Register accumulator: full lane width until the valid-lane crop.
    s = jnp.zeros(valid[:-1] + (xb.shape[-1],), acc_dtype)
    if variant == "shift_psum":
        # Paper Listing 1/2 verbatim: shuffle the partial sums one
        # column-step up, then FMA that column's vertical register taps.
        for step in stage.steps:
            if step.shift:
                s = warp_shift(s, step.shift)
            for tap in step.taps:
                s = s + engine._tap_read(xb, tap, valid) * engine._coeff(
                    stage, w_ref, tap, acc_dtype)
        return s[..., M - 1 : M - 1 + valid[-1]]
    if variant == "shift_data":
        # Stationary accumulator: shuffle the *data* down by the
        # cumulative shift (shfl_down) instead. Same per-lane sums.
        cum = 0
        for step in stage.steps:
            cum += step.shift
            xs = warp_shift(xb, -cum) if cum else xb
            for tap in step.taps:
                s = s + engine._tap_read(xs, tap, valid) * engine._coeff(
                    stage, w_ref, tap, acc_dtype)
        return s[..., : valid[-1]]
    raise ValueError(variant)


def _gpu_window_kernel(*refs, plan: SystolicPlan, block: tuple[int, ...],
                       time_steps: int, variant: str, acc_dtype):
    """One overlapped block of a windowed plan, GPU-shaped.

    Ref layout matches the TPU kernel —
    ``(x_ref, *w_refs, *epi_refs, o_ref, smem_ref[, acc_ref])`` — plus
    the SMEM staging scratch: the halo-extended input block (interior +
    lead/trail skirt) is written to shared memory **once**, and every
    tap read below hits SMEM/registers, never HBM — the paper's §4.5
    branch-free block with its skirt staged, rather than re-reading the
    global overlap per tap. The reduce accumulator (NCHW channel sweep)
    is the per-thread register array discipline; Pallas scratch models
    it (on real hardware it is register-resident until the flush).
    """
    nb, nr, no = plan.batch_axes, plan.reduce_axes, plan.out_axes
    n_w = pipeline_coeff_count(plan)
    epi_entries = chain_epilogue_operand_stages(plan)
    x_ref = refs[0]
    w_refs = refs[1:1 + n_w]
    epi_refs = refs[1 + n_w:1 + n_w + len(epi_entries)]
    o_pos = 1 + n_w + len(epi_entries)
    o_ref = refs[o_pos]
    smem_ref = refs[o_pos + 1]
    acc_ref = refs[o_pos + 2] if nr else None
    # §14: stage the block skirt through shared memory, one coalesced
    # global read per element of interior+halo.
    smem_ref[...] = (x_ref[(0,) * (nb + nr)] if nb + nr
                     else x_ref[...]).astype(acc_dtype)
    xb = smem_ref[...]
    ei0 = 0                 # epilogue-operand cursor, shared across the chain
    if plan.stages:
        wi = 0
        for si, stage in enumerate(plan.stages):
            w_ref = None
            if stage.coeff_mode == "dense":
                w_ref = w_refs[wi]
                wi += 1
            xb = _apply_plan_once_gpu(xb, stage, w_ref, variant, acc_dtype,
                                      strategy=stage.strategy or plan.strategy
                                      or "lanes")
            if si < len(plan.stages) - 1:
                for st in stage.epilogue:
                    ref = None
                    if st.op in EPILOGUE_OPERANDS:
                        ref = epi_refs[ei0]
                        ei0 += 1
                    xb = engine._apply_epilogue_val(st, xb, ref, plan,
                                                    acc_dtype, None)
    else:
        w_ref = w_refs[0] if n_w else None
        for _ in range(time_steps):
            xb = _apply_plan_once_gpu(xb, plan, w_ref, variant, acc_dtype,
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
            val = engine._apply_epilogue_val(st, val, ref, plan, acc_dtype,
                                             o_idx)
        return val

    if nr:
        rdims = range(nb + no + plan.ndim_spatial,
                      nb + no + plan.ndim_spatial + nr)
        engine._accumulate_over_reduce(acc_ref, o_ref, res, tuple(rdims),
                                       o_idx, epilogue_fn)
    else:
        o_ref[o_idx] = epilogue_fn(res).astype(o_ref.dtype)


def run_window_plan_gpu(x, w=None, **kw):
    """Fault-checked entry: ``engine.gpu.window`` fires per *call*, not
    per trace — the jitted lowering below would only run its Python body
    once per compilation, so an armed site would miss warm-cache calls."""
    rfaults.check("engine.gpu.window")
    return _run_window_plan_gpu_jit(x, w, **kw)


@functools.partial(
    jax.jit,
    static_argnames=("plan", "block", "time_steps", "variant", "interpret",
                     "acc_dtype", "strategy"),
)
def _run_window_plan_gpu_jit(
    x: jax.Array,
    w=None,
    *,
    plan: SystolicPlan,
    block: tuple[int, ...] | None,
    time_steps: int = 1,
    variant: str = "shift_psum",
    interpret: bool = True,
    acc_dtype=jnp.float32,
    epilogue_args: tuple = (),
    strategy: str | None = None,
) -> jax.Array:
    """The GPU lowering of :func:`repro.core.engine.run_window_plan`.

    Same signature, same results (bitwise vs the TPU path for identical
    blocks when the lane extent is warp-aligned, fp32-tolerance
    otherwise only through XLA contraction choices): warp-shuffle psum
    shifts, SMEM skirt staging, per-thread register accumulators.
    Callers normally reach this through ``run_window_plan(backend=
    'gpu')``; calling it directly skips the config default.
    """
    if strategy is not None:
        plan = dataclasses.replace(plan, strategy=strategy)

    def make_kernel(B):
        return functools.partial(
            _gpu_window_kernel, plan=plan, block=B, time_steps=time_steps,
            variant=variant, acc_dtype=acc_dtype)

    def make_scratch(B, in_block):
        scratch = [plgpu.SMEM(in_block, acc_dtype)]  # halo-skirt staging
        if plan.reduce_axes:
            scratch.append(plgpu.SMEM(B, acc_dtype))  # register accumulator
        return scratch

    block, _ = engine.window_block(
        plan, block, x.shape[plan.batch_axes + plan.reduce_axes:], x.dtype,
        time_steps, variant, "gpu", acc_dtype)
    with engine._obs_lowering(plan=plan, block=block, backend="gpu",
                              time_steps=time_steps, variant=variant):
        return engine._window_call(
            x, w, plan=plan, block=block, time_steps=time_steps,
            variant=variant, interpret=interpret, acc_dtype=acc_dtype,
            epilogue_args=epilogue_args, make_kernel=make_kernel,
            make_scratch=make_scratch)


def _gpu_scan_kernel(*refs, plan: SystolicPlan, acc_dtype, has_carry: bool,
                     want_carry: bool):
    """Kogge–Stone over one ``(BR, BT)`` tile with warp-shaped arrows.

    Identical masked shift-accumulate math to the TPU kernel (§3.6,
    Fig. 1e) with each arrow routed per its span: shifts shorter than a
    warp are intra-warp shuffles, warp-crossing shifts go through the
    shared-memory hand-off of :func:`warp_shift`. The inter-tile carry
    lives in the SMEM scratch — scratchpad used only *between* systolic
    blocks, exactly as SSAM prescribes (§1).
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
            carry[:] = jnp.zeros_like(carry)

    def store(s):
        out = s
        for st in plan.epilogue:
            out = engine._apply_epilogue_val(st, out, None, plan, acc_dtype,
                                             None)
        o_ref[:] = out.astype(o_ref.dtype)

    lane = jax.lax.broadcasted_iota(jnp.int32, ins[0].shape, 1)
    if plan.combine == "add":
        s = ins[0][:].astype(acc_dtype)
        for step in plan.steps:           # ctrl() of Eq. 1 gates each arrow
            shifted = warp_shift(s, step.shift)
            s = s + jnp.where(lane >= step.shift, shifted, jnp.zeros_like(s))
        s = s + carry[:]
        carry[:] = s[:, -1:]
        store(s)
    elif plan.combine == "linrec":
        A = ins[0][:].astype(acc_dtype)   # transfer pairs (a, b)
        B = ins[1][:].astype(acc_dtype)
        for step in plan.steps:
            As = warp_shift(A, step.shift)
            Bs = warp_shift(B, step.shift)
            ctrl = lane >= step.shift
            As = jnp.where(ctrl, As, jnp.ones_like(As))   # identity (1, 0)
            Bs = jnp.where(ctrl, Bs, jnp.zeros_like(Bs))
            A, B = A * As, A * Bs + B     # f_t ∘ f_{t−d}
        h = A * carry[:] + B
        carry[:] = h[:, -1:]
        store(h)
    else:
        raise ValueError(plan.combine)
    if want_carry:
        co_ref[:] = carry[:].astype(co_ref.dtype)


def run_scan_plan_gpu(*operands, **kw):
    """Fault-checked entry for the scan lowering (site ``engine.gpu.scan``)."""
    rfaults.check("engine.gpu.scan")
    return _run_scan_plan_gpu_jit(*operands, **kw)


@functools.partial(
    jax.jit, static_argnames=("plan", "block_r", "interpret", "acc_dtype",
                              "return_carry")
)
def _run_scan_plan_gpu_jit(
    *operands: jax.Array,
    plan: SystolicPlan,
    block_r: int = 8,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    carry: jax.Array | None = None,
    return_carry: bool = False,
):
    """The GPU lowering of :func:`repro.core.engine.run_scan_plan` —
    warp-shaped Kogge–Stone arrows, SMEM inter-tile carry. Same
    signature; cumsum results are bitwise for warp-aligned ``plan.S``,
    linrec results agree to ≤1 ulp (XLA may contract the per-step
    ``A·Bs + B`` FMA differently between the two kernel bodies)."""

    def make_kernel(has_carry):
        return functools.partial(_gpu_scan_kernel, plan=plan,
                                 acc_dtype=acc_dtype, has_carry=has_carry,
                                 want_carry=return_carry)

    def make_scratch(BR):
        return [plgpu.SMEM((BR, 1), acc_dtype)]

    with engine._obs_lowering(plan=plan, block=(block_r, plan.S),
                              backend="gpu"):
        return engine._scan_call(
            *operands, plan=plan, block_r=block_r, interpret=interpret,
            acc_dtype=acc_dtype, carry=carry, return_carry=return_carry,
            make_kernel=make_kernel, make_scratch=make_scratch)
