"""Sharded systolic execution: any windowed plan on a device mesh.

This is the paper's execution model lifted one level up the memory
hierarchy. Within a device, partial sums shift through VREG lanes while
block halos ride in from neighboring grid blocks (engine, §4.5); across
devices, the *same plan geometry* (:mod:`repro.core.halo`) decides how
many rows each shard must import from its mesh neighbors, and
``lax.ppermute`` plays the role the overlapped BlockSpecs play on-chip.

Schedule per call (DESIGN.md §8):

1. **Exchange** — for every sharded domain axis, each shard pushes its
   trailing ``t·lead`` rows to its high-side neighbor and its leading
   ``t·trail`` rows to its low-side neighbor (two ``ppermute``\\ s per
   axis). Exchanging the ``time_steps``-fold widened halo once per call
   — exactly one engine-halo per temporal step, batched into a single
   push — keeps the ``t`` fused plan applications communication-free
   and reproduces the single-device pad-once semantics (bit-for-bit
   under the monolithic schedule; the overlapped schedule's frame
   recompute can differ by ≤ 1 ulp of XLA FMA contraction).
2. **Interior compute, overlapped** — the shard's interior output block
   (everything ≥ halo-width away from a sharded edge) is lowered from
   the *resident* block alone, so it has no data dependence on the
   in-flight ``ppermute``\\ s and XLA's latency-hiding scheduler can run
   exchange and interior concurrently (the double-buffer: the interior
   output fills while the halo buffers land).
3. **Frame compute** — once the halos land, the boundary frame is
   recomputed from halo-extended slabs and spliced over the interior
   result. Domain edges fall out of the collective's semantics: a
   non-circular ``ppermute`` fills unsourced shards with zeros — which
   IS the engine's own origin padding (``boundary='zero'``); circular
   links give wraparound; ``'replicate'`` clamps the edge row.

Only *shape-preserving* plan axes (``lead+trail = ext−1``: stencils,
'same'-mode convs) can be sharded — each shard then owns equal slices
of input and output and the ``shard_map`` out-spec mirrors the in-spec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.obs import scopes
from repro.core.engine import run_weight_grad_plan, run_window_plan
from repro.robust import faults as rfaults
from repro.core.halo import (check_shard_geometry, extended_crop,
                             is_shape_preserving, shard_halo)
from repro.core.plan import SystolicPlan
from .sharding import mesh_axis_sizes, pspec_for_axes

BOUNDARIES = ("zero", "replicate", "wrap")

# Logical names of windowed-domain axes (lane axis last), resolved
# against the sharding rule tables when the caller passes no in_specs.
DOMAIN_AXES_2D = ("rows", "cols")
DOMAIN_AXES_3D = ("depth", "rows", "cols")


def default_domain_spec(shape, mesh: Mesh, rules=None) -> P:
    """Default PartitionSpec for a 2-D/3-D domain via the rule tables.

    Reuses :func:`repro.distributed.sharding.pspec_for_axes`, so the
    usual divisibility fallback applies: a mesh axis that does not
    divide the domain axis is skipped (replicated) rather than raising —
    explicit ``in_specs`` get the strict :class:`ValueError` treatment.
    """
    names = DOMAIN_AXES_3D if len(shape) == 3 else DOMAIN_AXES_2D
    return pspec_for_axes(names, shape, mesh, rules)


def default_plan_spec(plan: SystolicPlan, shape, mesh: Mesh, rules=None) -> P:
    """Default PartitionSpec for a plan's full input layout.

    Batch axes resolve through the rule tables' ``"batch"`` entry
    (→ the fast ``data`` axis), reduce axes stay replicated (sharding a
    contraction would need a cross-device psum), and the windowed axes
    get the usual ``rows``/``cols``/``depth`` resolution. Because
    ``pspec_for_axes`` never reuses a mesh axis, a sharded batch axis
    automatically leaves ``rows`` unsharded — batch parallelism first,
    halo exchange only where axes remain.
    """
    nb, nr = plan.batch_axes, plan.reduce_axes
    spatial = DOMAIN_AXES_3D if plan.ndim_spatial == 3 else DOMAIN_AXES_2D
    names = ("batch",) * nb + (None,) * nr + spatial
    return pspec_for_axes(names, shape, mesh, rules)


def _axis_assignments(
    spec, mesh: Mesh, ndim: int
) -> tuple[tuple[str, int] | None, ...]:
    """Resolve a PartitionSpec into per-domain-axis (mesh_axis, size)."""
    sizes = mesh_axis_sizes(mesh)
    entries = list(spec) + [None] * (ndim - len(tuple(spec)))
    if len(entries) > ndim:
        raise ValueError(
            f"in_specs {tuple(spec)} has more entries than the domain has "
            f"axes ({ndim})")
    out: list[tuple[str, int] | None] = []
    for a, e in enumerate(entries):
        if e is None:
            out.append(None)
            continue
        if isinstance(e, (tuple, list)):
            if len(e) != 1:
                raise ValueError(
                    f"domain axis {a} requests mesh axes {e}: halo exchange "
                    "shards each domain axis over at most one mesh axis")
            e = e[0]
        if e not in sizes:
            raise ValueError(
                f"in_specs names mesh axis {e!r} but the mesh has axes "
                f"{tuple(sizes)}")
        out.append((e, sizes[e]))
    return tuple(out)


def _edge_slab(x, axis: int, width: int, *, front: bool):
    """``width`` copies of the domain-edge row — the clamp boundary."""
    n = x.shape[axis]
    sl = lax.slice_in_dim(x, 0, 1, axis=axis) if front else \
        lax.slice_in_dim(x, n - 1, n, axis=axis)
    return jnp.concatenate([sl] * width, axis=axis)


def _multihop_slab(x, axis: int, width: int, name: str, size: int,
                   boundary: str, *, front: bool):
    """Halo slab spanning SEVERAL neighbor shards: chained ppermute hops.

    When the t-widened halo is wider than one shard's resident rows, no
    single neighbor owns the whole slab. Hop ``d`` ships each shard's
    *full* resident block ``d`` shards toward the consumer (one
    ``ppermute`` per hop — a chain of ``ceil(width/shard)`` collectives,
    not a raise); the stacked blocks then crop to the halo width.
    Out-of-domain rows resolve per boundary exactly as in the single-hop
    path: a non-circular ``ppermute`` fills them with zeros (the
    engine's origin padding), ``'wrap'`` uses circular links (mod-size
    sources), and ``'replicate'`` overwrites them with the *global*
    edge row — psum-broadcast from the shard that owns it, then masked
    in per slab row, since with a multi-shard halo several shards clamp
    and only partially.
    """
    n = x.shape[axis]
    hops = -(-width // n)
    blocks = []
    for d in range(1, hops + 1):
        if boundary == "wrap":
            pairs = ([(i, (i + d) % size) for i in range(size)] if front
                     else [((i + d) % size, i) for i in range(size)])
        else:
            pairs = ([(i, i + d) for i in range(size - d)] if front
                     else [(i + d, i) for i in range(size - d)])
        blocks.append(lax.ppermute(x, name, pairs))
    if front:
        # farthest neighbor's rows sit earliest in the global order
        stack = jnp.concatenate(blocks[::-1], axis=axis)
        slab = lax.slice_in_dim(stack, hops * n - width, hops * n, axis=axis)
    else:
        stack = jnp.concatenate(blocks, axis=axis)
        slab = lax.slice_in_dim(stack, 0, width, axis=axis)
    if boundary == "replicate":
        idx = lax.axis_index(name)
        edge_shard = 0 if front else size - 1
        one = _edge_slab(x, axis, 1, front=front)
        edge = lax.psum(jnp.where(idx == edge_shard, one,
                                  jnp.zeros_like(one)), name)
        tiled = jnp.concatenate([edge] * width, axis=axis)
        # slab row j of shard i holds global row i·n − width + j (front)
        # or (i+1)·n + j (back); rows beyond the domain edge clamp.
        iota = lax.broadcasted_iota(jnp.int32, slab.shape, axis)
        oob = (iota < width - idx * n) if front else \
            (iota >= (size - 1 - idx) * n)
        slab = jnp.where(oob, tiled, slab)
    return slab


def _halo_slab(x, axis: int, width: int, assign, boundary: str, *,
               front: bool):
    """One side's halo slab for one axis, or None when nothing to add.

    ``front=True`` is the low-side halo: each shard *pushes* its
    trailing ``width`` rows to its high-side neighbor (and receives
    symmetrically), so the slab this shard prepends is what its low
    neighbor pushed. On a domain edge a non-circular ``ppermute``
    delivers zeros — the engine's own origin padding — unless the
    boundary wraps (circular link) or clamps (edge-row replication).
    Halos wider than one shard chain ppermute hops
    (:func:`_multihop_slab`). Unsharded axes synthesize the same slab
    locally; for ``'zero'`` that is a no-op because the engine already
    zero-pads.
    """
    if width == 0:
        return None
    name, size = assign if assign is not None else (None, 1)
    n = x.shape[axis]
    if size > 1 and width > n:
        return _multihop_slab(x, axis, width, name, size, boundary,
                              front=front)
    if front:
        src = lax.slice_in_dim(x, n - width, n, axis=axis)
    else:
        src = lax.slice_in_dim(x, 0, width, axis=axis)
    if size > 1:
        if front:
            pairs = [(i, i + 1) for i in range(size - 1)]
        else:
            pairs = [(i + 1, i) for i in range(size - 1)]
        if boundary == "wrap":
            pairs.append((size - 1, 0) if front else (0, size - 1))
        slab = lax.ppermute(src, name, pairs)
        if boundary == "replicate":
            edge = 0 if front else size - 1
            slab = jnp.where(lax.axis_index(name) == edge,
                             _edge_slab(x, axis, width, front=front), slab)
        return slab
    if boundary == "wrap":
        return src
    if boundary == "replicate":
        return _edge_slab(x, axis, width, front=front)
    return None      # zero boundary, unsharded: engine origin pad covers it


def _extend_axis(x, axis: int, lo: int, hi: int, assign, boundary: str):
    """Halo-extend ``x`` along one axis (no-op when nothing to add)."""
    with jax.named_scope(scopes.HALO_EXCHANGE):
        front = _halo_slab(x, axis, lo, assign, boundary, front=True)
        back = _halo_slab(x, axis, hi, assign, boundary, front=False)
        parts = [p for p in (front, x, back) if p is not None]
        return x if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


# ---------------------------------------------------------------------------
# The sharded lowering
# ---------------------------------------------------------------------------

def _frame_regions(
    local_out: tuple[int, ...],
    halos: tuple[tuple[int, int], ...],
    exchanged: tuple[int, ...],
) -> list[tuple[tuple[int, int], ...]]:
    """Decompose the boundary frame into slabs, one list entry per slab.

    Axis-``a`` slabs span the full extent of later axes and are
    restricted to the interior of earlier exchanged axes, so every
    frame cell (corners included) is covered exactly by the first
    exchanged axis that owns it.
    """
    regions = []
    for k, a in enumerate(exchanged):
        lo, hi = halos[a]
        base = []
        for ax, n in enumerate(local_out):
            if ax in exchanged[:k]:
                l2, h2 = halos[ax]
                base.append((l2, n - h2))
            else:
                base.append((0, n))
        if any(b[0] >= b[1] for b in base):
            continue        # earlier axes' full-width slabs already cover it
        if lo:
            regions.append(tuple(
                (0, lo) if ax == a else b for ax, b in enumerate(base)))
        if hi:
            regions.append(tuple(
                (local_out[a] - hi, local_out[a]) if ax == a else b
                for ax, b in enumerate(base)))
    return [r for r in regions if all(b[0] < b[1] for b in r)]


def _local_lowering(
    xl, wl, epi, *, plan, block, time_steps, variant, boundary, interpret,
    acc_dtype, assigns, halos, overlap, backend=None,
):
    """The per-shard program: exchange → interior compute → frame splice.

    Batched plans pass through transparently: batch/reduce axes sit
    ahead of the windowed axes on the input (``in_off``) and batch/out
    axes ahead of them on the output (``out_off``); halo extension,
    cropping and the frame splice all index relative to those offsets,
    while the batch entries themselves were already scattered by
    ``shard_map`` (no exchange — batch items are independent).
    """
    nd = plan.ndim_spatial
    in_off = plan.batch_axes + plan.reduce_axes
    out_off = plan.batch_axes + plan.out_axes
    pre_in = (slice(None),) * in_off
    pre_out = (slice(None),) * out_off
    local = xl.shape[in_off:]
    ext = xl
    for a in range(nd):
        lo, hi = halos[a]
        assign = assigns[a]
        if (lo or hi) and assign is not None and assign[1] > 1:
            # A cross-device exchange on this axis. This runs inside the
            # shard_map trace, so the span and the halo.exchanges /
            # halo.bytes counters fire once per compilation, not per
            # call, with *static* accounting: per-shard slab bytes (both
            # sides) and the ppermute hop count (halos wider than a
            # shard chain ceil(width/n) hops, _multihop_slab).
            n = ext.shape[in_off + a]
            slab_bytes = ((lo + hi) * (ext.size // max(n, 1))
                          * ext.dtype.itemsize)
            hops = sum(-(-width // n) for width in (lo, hi) if width)
            obs.metrics.inc("halo.exchanges", f"axis{a}")
            obs.metrics.inc("halo.bytes", f"axis{a}", n=slab_bytes)
            with obs.span("halo.exchange", cat="halo", kind=plan.kind,
                          axis=a, lo=lo, hi=hi, mesh_axis=assign[0],
                          shards=assign[1], slab_bytes=slab_bytes,
                          hops=hops, boundary=boundary):
                ext = _extend_axis(ext, in_off + a, lo, hi, assign,
                                   boundary)
        else:
            ext = _extend_axis(ext, in_off + a, lo, hi, assign, boundary)
    exchanged = tuple(
        a for a in range(nd) if ext.shape[in_off + a] != local[a])

    # Epilogue operands replicate to every shard (per-channel bias /
    # scalars — residuals are refused upstream); the epilogue itself is
    # elementwise, so applying it per engine call (interior and frame
    # strips alike) matches the single-device fused store.
    engine = functools.partial(
        run_window_plan, plan=plan, block=block, time_steps=time_steps,
        variant=variant, interpret=interpret, acc_dtype=acc_dtype,
        epilogue_args=epi, backend=backend)

    def cropped(e):
        """Engine output on a (partially) extended slab, mapped back to
        the rows the slab's un-extended origin owns."""
        with jax.named_scope(scopes.HALO_INTERIOR):
            out = engine(e, wl) if wl is not None else engine(e)
            sl = tuple(
                extended_crop(plan, time_steps, a, local[a])
                if a in exchanged else slice(0, local[a])
                for a in range(nd))
            return out[pre_out + sl]

    if not exchanged:
        return cropped(ext)
    if not overlap or any(halos[a][0] + halos[a][1] >= local[a]
                          for a in exchanged):
        # A halo as wide as the shard leaves no interior to overlap with
        # the exchange (the multi-hop regime) — lower the extended block
        # monolithically instead of splicing an empty frame.
        return cropped(ext)

    # Overlapped schedule: the interior lowers from the *resident* block
    # (no data dependence on the in-flight ppermutes), the frame lowers
    # from halo-extended slabs once they land.
    with jax.named_scope(scopes.HALO_INTERIOR):
        out = engine(xl, wl) if wl is not None else engine(xl)
    for region in _frame_regions(local, halos, exchanged):
        slab_sl, out_sl, strip_crop = [], [], []
        for a, (lo_r, hi_r) in enumerate(region):
            out_sl.append(slice(lo_r, hi_r))
            if a in exchanged:
                # Output row i reads extended rows [i, i + lo + hi], so
                # the slab for out rows [lo_r, hi_r) is that union and
                # the strip sits ``lo`` rows into the slab's output.
                lo_h, hi_h = halos[a]
                slab_sl.append(slice(lo_r, hi_r + lo_h + hi_h))
                strip_crop.append(slice(lo_h, lo_h + (hi_r - lo_r)))
            else:
                slab_sl.append(slice(None))
                strip_crop.append(slice(lo_r, hi_r))
        with jax.named_scope(scopes.HALO_FRAME):
            strip = ext[pre_in + tuple(slab_sl)]
            s_out = engine(strip, wl) if wl is not None else engine(strip)
        with jax.named_scope(scopes.HALO_SPLICE):
            out = out.at[pre_out + tuple(out_sl)].set(
                s_out[pre_out + tuple(strip_crop)])
    return out


def validate_sharded_call(x, plan: SystolicPlan, mesh: Mesh,
                          in_spec: P | None = None, *, time_steps: int = 1,
                          boundary: str = "zero", rules=None):
    """Every pre-``pallas_call`` check of :func:`sharded_window_plan`.

    Factored out so the §16 guard can run it *before* entering the
    degradation lattice: these are configuration errors (a sharded
    reduce axis, a non-shape-preserving plan, halo-vs-shard geometry),
    and a lattice level that drops the mesh would otherwise "recover"
    from user misuse by silently computing something else. Returns the
    resolved ``(in_spec, batch_assigns, spatial_assigns, halos,
    local_shape)`` for the caller to lower with.
    """
    if boundary not in BOUNDARIES:
        raise ValueError(f"boundary must be one of {BOUNDARIES}, "
                         f"got {boundary!r}")
    if boundary == "replicate" and time_steps != 1:
        raise ValueError(
            "boundary='replicate' supports time_steps=1 only: a clamped "
            "halo is static while the true clamped boundary evolves under "
            "temporal fusion")
    nb, nr, nd = plan.batch_axes, plan.reduce_axes, plan.ndim_spatial
    if x.ndim != nb + nr + nd:
        raise ValueError(f"{plan.kind!r} plan wants a "
                         f"{nb + nr + nd}-D input, got shape {x.shape}")
    for a in range(nd):
        if not is_shape_preserving(plan, a):
            raise ValueError(
                f"sharded execution needs a shape-preserving plan "
                f"(lead+trail = ext−1 on every axis) so shards own equal "
                f"input and output slices; {plan.kind!r} violates this on "
                f"axis {a}. For conv2d use mode='same' "
                "(core.plan.conv2d_same_plan).")
    if in_spec is None:
        in_spec = default_plan_spec(plan, x.shape, mesh, rules)
    all_assigns = _axis_assignments(in_spec, mesh, nb + nr + nd)
    batch_assigns = all_assigns[:nb]
    for a, assign in enumerate(all_assigns[nb:nb + nr]):
        if assign is not None:
            raise ValueError(
                f"reduce axis {a} of a {plan.kind!r} plan cannot be "
                f"sharded (mesh axis {assign[0]!r}): the channel "
                "reduction is carried in the engine's accumulator, not a "
                "cross-device psum; shard the batch or spatial axes")
    for a, (n, assign) in enumerate(zip(x.shape[:nb], batch_assigns)):
        if assign is not None and n % assign[1] != 0:
            raise ValueError(
                f"mesh axis {assign[0]!r} (size {assign[1]}) does not "
                f"divide batch axis {a} (size {n}) for {plan.kind!r}")
    assigns = all_assigns[nb + nr:]
    local = check_shard_geometry(plan, x.shape[nb + nr:], assigns,
                                 time_steps)
    halos = shard_halo(plan, time_steps)
    if boundary != "zero":
        # wrap/replicate also extend UNSHARDED axes, locally — the
        # resident block must cover the halo it lends itself. Sharded
        # axes are exempt: halos wider than a shard chain ppermute hops
        # (:func:`_multihop_slab`) instead of slicing the resident rows.
        for a, ((lo, hi), n) in enumerate(zip(halos, local)):
            if (assigns[a] is None or assigns[a][1] == 1) \
                    and max(lo, hi) > n:
                raise ValueError(
                    f"boundary={boundary!r} needs the local block to cover "
                    f"its own axis-{a} halo: {n} rows per shard < "
                    f"({lo}, {hi}) halo")
    from repro.core.plan import epilogue_operand_stages
    for st in epilogue_operand_stages(plan.final_epilogue()):
        if st.op == "residual_add":
            raise ValueError(
                "a residual_add epilogue cannot ride a sharded call: the "
                "residual operand is output-shaped and would need the "
                "same sharding; add the residual outside the mesh call")
    return in_spec, batch_assigns, assigns, halos, local


def sharded_window_plan(
    x: jax.Array,
    w: jax.Array | None = None,
    *,
    plan: SystolicPlan,
    mesh: Mesh,
    in_spec: P | None = None,
    block: tuple[int, ...],
    time_steps: int = 1,
    variant: str = "shift_psum",
    boundary: str = "zero",
    overlap: bool = True,
    interpret: bool = True,
    acc_dtype=jnp.float32,
    rules=None,
    epilogue_args: tuple = (),
    backend: str | None = None,
) -> jax.Array:
    """Run a windowed plan on a domain sharded over a device mesh.

    Args:
      x: the global domain, lane axis last, with the plan's batch and
        reduce axes (if any) leading. May be host-global; ``shard_map``
        scatters it per ``in_spec``.
      w: runtime coefficients (replicated to every shard), or None.
      plan: any windowed :class:`SystolicPlan` whose sharded *spatial*
        axes are shape-preserving. Batch axes shard without any halo
        exchange (items are independent); reduce axes must stay
        replicated (a sharded contraction would need a psum).
      mesh: a 1-D/2-D device mesh (e.g. ``launch.mesh.make_domain_mesh``).
      in_spec: PartitionSpec mapping input axes (batch + reduce +
        domain) to mesh axes; at most one mesh axis per axis. Defaults
        to the rule-table resolution of :func:`default_plan_spec`.
      block / time_steps / variant / interpret / acc_dtype: forwarded to
        the engine, per shard.
      boundary: 'zero' (the engine's semantics — domain-edge shards
        receive the origin padding from the collective itself), 'wrap'
        (torus), or 'replicate' (edge clamp; ``time_steps == 1`` only,
        a static clamped halo does not commute with temporal fusion).
      overlap: lower the interior from the resident block concurrently
        with the exchange, then splice the frame (DESIGN.md §8); with
        False, one monolithic engine call on the extended block. The two
        schedules run the same per-output math and agree to ≤ 1 ulp
        (XLA may contract FMAs differently in the recomputed frame).

    Returns:
      The plan's output (batch + out + spatial axes), batch and spatial
      axes sharded exactly like the input.
    """
    in_spec, batch_assigns, assigns, halos, local = validate_sharded_call(
        x, plan, mesh, in_spec, time_steps=time_steps, boundary=boundary,
        rules=rules)
    nb, nr, no, nd = (plan.batch_axes, plan.reduce_axes, plan.out_axes,
                      plan.ndim_spatial)

    b_names = tuple(a[0] if a else None for a in batch_assigns)
    s_names = tuple(a[0] if a else None for a in assigns)
    spec_in = P(*b_names, *((None,) * nr), *s_names)
    spec_out = P(*b_names, *((None,) * no), *s_names)
    n_w = 1 if w is not None else 0
    # fused plans pass a tuple of per-stage filters — replicate each leaf
    w_args = (w,) if n_w else ()
    w_specs = (jax.tree.map(lambda _: P(), w),) if n_w else ()
    epi = tuple(epilogue_args)
    epi_specs = tuple(P() for _ in epi)

    fn = functools.partial(
        _local_lowering, plan=plan, block=block, time_steps=time_steps,
        variant=variant, boundary=boundary, interpret=interpret,
        acc_dtype=acc_dtype, assigns=assigns, halos=halos, overlap=overlap,
        backend=backend)

    sharded = jax.shard_map(
        lambda xs, *rest: fn(xs, rest[0] if n_w else None,
                             tuple(rest[n_w:])),
        mesh=mesh,
        in_specs=(spec_in,) + w_specs + epi_specs,
        out_specs=spec_out,
        check_vma=False,
    )
    rfaults.check("halo.exchange")
    if not isinstance(x, jax.core.Tracer):   # under jit: a trace, no launch
        obs.metrics.inc("halo.launch", plan.kind)
    with obs.span("halo.sharded_window_plan", cat="halo", kind=plan.kind,
                  devices=mesh.size, overlap=overlap, boundary=boundary):
        return sharded(x, *w_args, *epi)


# ---------------------------------------------------------------------------
# Sharded adjoint: backward-weight (DESIGN.md §10)
#
# The backward-*input* of a sharded plan needs no code here at all: the
# adjoint plan (core.adjoint.input_adjoint_plan) swaps lead and trail,
# so running it through sharded_window_plan with the same mesh/in_spec
# reverses the direction of every ppermute halo push automatically —
# the transposed dataflow falls out of the unchanged geometry machinery.
# ---------------------------------------------------------------------------

def sharded_weight_grad(
    x: jax.Array,
    g: jax.Array,
    *,
    plan: SystolicPlan,
    mesh: Mesh,
    in_spec: P | None = None,
    block: tuple[int, ...] = (8, 128),
    boundary: str = "zero",
    interpret: bool = True,
    acc_dtype=jnp.float32,
    rules=None,
) -> jax.Array:
    """``∂L/∂w`` of a sharded windowed-plan call, replicated to all shards.

    A shard's cotangent rows ``o`` pair with forward-input rows
    ``[o − lead, o + trail]`` — exactly the forward's shard halo — so
    the same single-hop ppermute pushes materialize the needed context
    (zeros beyond the domain edge under ``boundary='zero'``, the wrapped
    image under ``'wrap'``). Each shard then runs
    :func:`repro.core.engine.run_weight_grad_plan` on its halo-extended
    local block (batch + local spatial tiles as the grid's reduce
    sweep), and the partial filter gradients ``psum`` over every mesh
    axis the ``in_spec`` actually shards — batch axes included, since
    batch items are independent forward but *summed* in the weight
    gradient.
    """
    nb, nr, no, nd = (plan.batch_axes, plan.reduce_axes, plan.out_axes,
                      plan.ndim_spatial)
    if in_spec is None:
        in_spec = default_plan_spec(plan, x.shape, mesh, rules)
    all_assigns = _axis_assignments(in_spec, mesh, nb + nr + nd)
    batch_assigns, assigns = all_assigns[:nb], all_assigns[nb + nr:]
    check_shard_geometry(plan, x.shape[nb + nr:], assigns, 1)
    halos = shard_halo(plan, 1)
    in_off = nb + nr
    psum_axes = tuple(dict.fromkeys(
        a[0] for a in batch_assigns + assigns if a is not None))

    def local(xl, gl):
        ext = xl
        for a in range(nd):
            lo, hi = halos[a]
            front = _halo_slab(ext, in_off + a, lo, assigns[a], boundary,
                               front=True)
            back = _halo_slab(ext, in_off + a, hi, assigns[a], boundary,
                              front=False)
            # unsharded zero-boundary axes get no slab from the
            # collective — materialize the origin padding locally so the
            # engine sees a uniformly pre-padded block.
            def zeros(width):
                shape = list(ext.shape)
                shape[in_off + a] = width
                return jnp.zeros(shape, ext.dtype)
            parts = [front if front is not None else (zeros(lo) if lo else None),
                     ext,
                     back if back is not None else (zeros(hi) if hi else None)]
            parts = [p for p in parts if p is not None]
            ext = parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=in_off + a)
        dw = run_weight_grad_plan(ext, gl, plan=plan, block=block,
                                  interpret=interpret, acc_dtype=acc_dtype,
                                  pre_padded=True)
        return lax.psum(dw, psum_axes) if psum_axes else dw

    b_names = tuple(a[0] if a else None for a in batch_assigns)
    s_names = tuple(a[0] if a else None for a in assigns)
    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(*b_names, *((None,) * nr), *s_names),
                  P(*b_names, *((None,) * no), *s_names)),
        out_specs=P(),
        check_vma=False,
    )
    rfaults.check("halo.exchange")
    return sharded(x, g)
