"""SSAM scan kernels — Kogge–Stone plans over the engine (paper §3.6).

Two memory-bound primitives built from the same masked shift-accumulate
schedule (Fig. 1e — the ``ctrl()`` of Eq. 1 gates each arrow):

* :func:`cumsum` — inclusive prefix sum along time
  (:func:`repro.core.plan.scan_plan`, combine='add').
* :func:`linear_recurrence` — ``h_t = a_t · h_{t−1} + b_t`` via
  Kogge–Stone over the affine transfer pairs ``(a, b)``
  (:func:`repro.core.plan.linear_recurrence_plan`, combine='linrec').
  This is the execution engine for the RWKV6 WKV recurrence and the
  Hymba/Mamba selective scan (DESIGN.md §3).

Layout: time on the lane axis (the systolic "warp"), independent
channels on sublanes. Inter-block carries ride in a VMEM scratch
accumulator across sequential grid steps — the TPU analogue of the
paper's inter-warp scratchpad accumulation (§4.9), used only *between*
systolic blocks exactly as SSAM prescribes (§1). The lowering is
:func:`repro.core.engine.run_scan_plan`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.engine import run_scan_plan
from repro.core.plan import linear_recurrence_plan, scan_plan


def _lane_tile(block_t: int, T: int) -> int:
    """Largest power-of-two lane tile ≤ block_t, capped at T's next power
    of two. A sequence shorter than ``block_t`` then runs as one padded
    tile that spans the whole operand, never as several tiles narrower
    than the TPU's 128-lane layout."""
    return min(1 << (block_t.bit_length() - 1),
               1 << max(T - 1, 0).bit_length())


def cumsum(
    x: jax.Array,
    *,
    block_r: int = 8,
    block_t: int = 128,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    carry: jax.Array | None = None,
    return_carry: bool = False,
    backend: str | None = None,
):
    """Inclusive prefix sum along the last axis of ``(R, T)``.

    ``carry``/``return_carry`` thread the running total across chunks
    (DESIGN.md §12)."""
    plan = scan_plan(_lane_tile(block_t, x.shape[-1]))
    return run_scan_plan(x, plan=plan, block_r=block_r, interpret=interpret,
                         acc_dtype=acc_dtype, carry=carry,
                         return_carry=return_carry, backend=backend)


def linear_recurrence(
    a: jax.Array,
    b: jax.Array,
    *,
    block_r: int = 8,
    block_t: int = 128,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    carry: jax.Array | None = None,
    return_carry: bool = False,
    backend: str | None = None,
):
    """Solve ``h_t = a_t · h_{t−1} + b_t`` along the last axis of (R, T).

    ``carry`` seeds h₋₁ (default 0); ``return_carry=True`` additionally
    returns the final state ``(R, 1)`` — together they let the caller
    stream chunks through the inter-chunk carry (DESIGN.md §12).

    Padding note (engine): ``a`` pads with ones and ``b`` with zeros so
    padded tail steps are identity transfers.
    """
    assert a.shape == b.shape
    plan = linear_recurrence_plan(_lane_tile(block_t, a.shape[-1]))
    return run_scan_plan(a, b, plan=plan, block_r=block_r,
                         interpret=interpret, acc_dtype=acc_dtype,
                         carry=carry, return_carry=return_carry,
                         backend=backend)
