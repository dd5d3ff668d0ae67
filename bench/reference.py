"""The plain reference of a stencil sweep, and the comparison that decides
``correct``.

It imports nothing of the program. A call of ``steps`` fused steps has
pad-once semantics: the whole domain is zero-padded once by ``steps``
footprints and ``steps`` valid applications follow, so intermediate
iterates outside the domain are not reset to zero. A sweep of ``calls``
such calls repeats that, padding the domain again at each call.

The comparison runs in blocks: for each block of output rows on each
device it gathers the input rows the block depends on (its cone of
``calls * steps`` footprints, clipped to the domain) onto that device,
runs the reference there, and reduces the gap to three numbers. So it
fits beside the program's last input and output at any size the program
runs, and never holds a whole field twice.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

BAND_ROWS = 2048


def extents(offsets) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Per axis, how far the stencil reaches below (lead) and above
    (trail) the point it updates."""
    nd = len(offsets[0])
    return (tuple(-min(o[a] for o in offsets) for a in range(nd)),
            tuple(max(o[a] for o in offsets) for a in range(nd)))


def axis_plan(o0: int, o1: int, n: int, lead: int, trail: int, steps: int,
              calls: int):
    """Input interval and per-call zero pads that give output ``[o0, o1)``
    of an axis of length ``n`` after ``calls`` calls of ``steps`` steps.

    After call ``k`` the rows held are the output's cone of the remaining
    ``calls - k`` calls, clipped to the domain; each call pads with zeros
    what its cone reaches beyond the domain."""
    def held(k):
        return (max(0, o0 - (calls - k) * steps * lead),
                min(n, o1 + (calls - k) * steps * trail))

    pads = []
    for k in range(1, calls + 1):
        (a, b), (a2, b2) = held(k - 1), held(k)
        pads.append((a - (a2 - steps * lead), b2 + steps * trail - b))
    return held(0), tuple(pads)


@functools.partial(jax.jit, static_argnames=(
    "offsets", "coeffs", "steps", "pads", "dtype"))
def sweep_region(x, *, offsets, coeffs, steps, pads, dtype):
    """``len(pads)`` pad-once calls of ``steps`` valid steps on a region,
    in ``dtype``; ``pads[k]`` holds the call's ((top, bottom), (left,
    right)) zero rows and columns."""
    lead, trail = extents(offsets)
    dt = jnp.dtype(dtype)
    cs = [jnp.asarray(c, dt) for c in coeffs]
    x = x.astype(dt)
    for pad in pads:
        x = jnp.pad(x, pad)
        for _ in range(steps):
            h = x.shape[0] - lead[0] - trail[0]
            w = x.shape[1] - lead[1] - trail[1]
            acc = None
            for (dy, dx), c in zip(offsets, cs):
                term = c * x[lead[0] + dy:lead[0] + dy + h,
                             lead[1] + dx:lead[1] + dx + w]
                acc = term if acc is None else acc + term
            x = acc
    return x.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("size",))
def _take(x, r, c, *, size):
    return lax.dynamic_slice(x, (r, c), size)


@jax.jit
def _gap(got, want):
    got = got.astype(jnp.float32)
    finite = jnp.isfinite(got)
    diff = jnp.where(finite, jnp.abs(got - want), 0.0)
    return (jnp.max(diff), jnp.max(jnp.abs(want)),
            jnp.sum(~finite, dtype=jnp.int32))


def _bounds(index, shape):
    return tuple(s.indices(n)[:2] for s, n in zip(index, shape))


def _shards(arr):
    """(device, ((r0, r1), (c0, c1)), data) per distinct shard."""
    seen = {}
    for s in arr.addressable_shards:
        b = _bounds(s.index, arr.shape)
        seen.setdefault(b, (s.device, b, s.data))
    return list(seen.values())


def gather_region(arr, rows, cols, device):
    """Rows ``[rows)`` and columns ``[cols)`` of ``arr`` as one array on
    ``device``, assembled from the shards that hold them."""
    pieces = {}
    for _, ((r0, r1), (c0, c1)), data in _shards(arr):
        a, b = max(rows[0], r0), min(rows[1], r1)
        c, d = max(cols[0], c0), min(cols[1], c1)
        if a < b and c < d:
            piece = _take(data, a - r0, c - c0, size=(b - a, d - c))
            pieces[(a, c)] = jax.device_put(piece, device)
    row_keys = sorted({a for a, _ in pieces})
    rows_out = [jnp.concatenate([pieces[k] for k in sorted(pieces)
                                 if k[0] == a], axis=1) for a in row_keys]
    return rows_out[0] if len(rows_out) == 1 else jnp.concatenate(
        rows_out, axis=0)


@dataclasses.dataclass
class Gap:
    max_abs_gap: float      # max |got - reference| over finite cells
    max_abs_ref: float      # max |reference|
    nonfinite: int          # cells of got that are NaN or infinite

    @property
    def rel_gap(self) -> float:
        return self.max_abs_gap / max(self.max_abs_ref, 1e-30)


def compare_sweep(x_in, x_out, *, offsets, coeffs, steps: int, calls: int,
                  dtype: str = "float32",
                  got_dtype: str | None = None) -> Gap:
    """Gap between ``x_out`` and the reference's ``calls`` calls of
    ``steps`` steps from ``x_in``, block by block on the devices that hold
    ``x_out``. With ``got_dtype`` the reference computed in that dtype
    takes the place of ``x_out`` (the control)."""
    offsets = tuple(tuple(int(v) for v in o) for o in offsets)
    coeffs = tuple(float(c) for c in coeffs)
    lead, trail = extents(offsets)
    n, m = x_in.shape
    worst, scale, bad = 0.0, 0.0, 0
    for device, ((R0, R1), (C0, C1)), out_data in _shards(x_out):
        cols, col_pads = axis_plan(C0, C1, m, lead[1], trail[1], steps, calls)
        for r in range(R0, R1, BAND_ROWS):
            r1 = min(r + BAND_ROWS, R1)
            rows, row_pads = axis_plan(r, r1, n, lead[0], trail[0], steps,
                                       calls)
            xin = gather_region(x_in, rows, cols, device)
            pads = tuple(zip(row_pads, col_pads))

            def ref(dt):
                return sweep_region(xin, offsets=offsets, coeffs=coeffs,
                                    steps=steps, pads=pads, dtype=dt)
            want = ref(dtype)
            got = ref(got_dtype) if got_dtype else _take(
                out_data, r - R0, 0, size=(r1 - r, C1 - C0))
            g, s, nf = _gap(got, want)
            worst, scale, bad = (max(worst, float(g)), max(scale, float(s)),
                                 bad + int(nf))
            del xin, want, got
    return Gap(worst, scale, bad)
