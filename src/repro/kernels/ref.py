"""Pure-jnp oracles for every SSAM kernel — the ground truth in tests.

Each function is a direct, obviously-correct statement of the math with
no systolic structure. Kernel unit tests sweep shapes/dtypes and
``assert_allclose`` the Pallas kernels (interpret mode) and the
:mod:`repro.core.executor` model against these.

Single-field windowed oracles sum their taps in a loop, one shifted
slice at a time, so they also check chip-sized fields: an unrolled sum
of 121 slices, or a one-channel ``conv_general_dilated``, asks the TPU
compiler for tens of GiB at 8192².
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .stencils import StencilDef


def _tap_sum(xp: jax.Array, offsets, coeffs, out_shape) -> jax.Array:
    """``Σ_k coeffs[k] · xp[offsets[k] : offsets[k] + out_shape]`` in f32,
    one tap per loop iteration."""
    offsets = jnp.asarray(offsets, jnp.int32).reshape(-1, xp.ndim)
    coeffs = jnp.asarray(coeffs, jnp.float32).reshape(-1)
    xp = xp.astype(jnp.float32)

    def tap(k, acc):
        return acc + coeffs[k] * jax.lax.dynamic_slice(
            xp, tuple(offsets[k]), out_shape)

    return jax.lax.fori_loop(0, coeffs.shape[0], tap,
                             jnp.zeros(out_shape, jnp.float32))


# ---------------------------------------------------------------------------
# Convolution
# ---------------------------------------------------------------------------

def conv2d_valid(x: jax.Array, w: jax.Array) -> jax.Array:
    """Valid cross-correlation: out[y,x] = Σ_{n,m} x[y+n, x+m]·w[n,m]."""
    N, M = w.shape
    offsets = [(n, m) for n in range(N) for m in range(M)]
    out_shape = (x.shape[0] - N + 1, x.shape[1] - M + 1)
    return _tap_sum(x, offsets, w, out_shape).astype(x.dtype)


def conv2d_same(x: jax.Array, w: jax.Array) -> jax.Array:
    """'Same' zero-boundary cross-correlation, anchor at filter centre."""
    N, M = w.shape
    top, left = (N - 1) // 2, (M - 1) // 2
    xp = jnp.pad(x, ((top, N - 1 - top), (left, M - 1 - left)))
    return conv2d_valid(xp, w)


def conv2d_batched(x: jax.Array, w: jax.Array, mode: str = "valid") -> jax.Array:
    """Minibatch of single-channel images against one filter: (B, H, W)."""
    fn = conv2d_same if mode == "same" else conv2d_valid
    return jax.vmap(lambda xi: fn(xi, w))(x)


def conv2d_nchw(x: jax.Array, w: jax.Array, mode: str = "valid",
                groups: int = 1) -> jax.Array:
    """Batched multi-channel cross-correlation.

    x: (B, C_in, H, W); w: (C_out, C_in/groups, N, M) → (B, C_out, H', W').
    'same' mode anchors at the filter centre (top = (N−1)//2), matching
    :func:`conv2d_same` per channel. ``groups`` maps straight to
    ``feature_group_count`` — the oracle the grouped engine path
    validates against.
    """
    N, M = w.shape[2:]
    if mode == "same":
        top, left = (N - 1) // 2, (M - 1) // 2
        padding = [(top, N - 1 - top), (left, M - 1 - left)]
    else:
        padding = "VALID"
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (1, 1), padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups).astype(x.dtype)


def conv1d_causal(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv: y[b,t,d] = Σ_k x[b, t−K+1+k, d]·w[k,d]."""
    B, T, D = x.shape
    K, _ = w.shape
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros((B, T, D), jnp.promote_types(x.dtype, jnp.float32))
    for k in range(K):
        out = out + xp[:, k : k + T, :] * w[k, :]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Stencils
# ---------------------------------------------------------------------------

def stencil_apply(x: jax.Array, sdef: StencilDef) -> jax.Array:
    """One same-shape stencil application with zeros outside the domain."""
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for off, c in zip(sdef.offsets, sdef.coeffs):
        shifted = x.astype(jnp.float32)
        for axis, d in enumerate(off):
            shifted = jnp.roll(shifted, -d, axis=axis)
            # zero the wrapped region
            idx = jnp.arange(x.shape[axis])
            if d > 0:
                mask = idx < (x.shape[axis] - d)
            elif d < 0:
                mask = idx >= (-d)
            else:
                continue
            shape = [1] * x.ndim
            shape[axis] = x.shape[axis]
            shifted = shifted * mask.reshape(shape)
        out = out + shifted * c
    return out.astype(x.dtype)


def stencil_iterate(x: jax.Array, sdef: StencilDef, steps: int) -> jax.Array:
    """``steps`` applications with the *pad-once* (trapezoidal) semantics.

    The domain is zero-padded once by ``steps`` footprints, then ``steps``
    valid applications follow. For ``steps == 1`` this equals
    :func:`stencil_apply`. This is the semantics the temporally-blocked
    SSAM kernels implement (see ``ssam_stencil2d`` docstring); it agrees
    with classic zero-Dirichlet iteration (:func:`stencil_iterate_dirichlet`)
    on the interior at distance > steps·radius from the boundary.
    """
    los = [min(o[a] for o in sdef.offsets) for a in range(sdef.ndim)]
    his = [max(o[a] for o in sdef.offsets) for a in range(sdef.ndim)]
    pad = [(steps * -lo, steps * hi) for lo, hi in zip(los, his)]
    xp = jnp.pad(x, pad).astype(jnp.float32)
    starts = [[d - lo for d, lo in zip(off, los)] for off in sdef.offsets]
    for _ in range(steps):
        new_shape = tuple(s - (hi - lo)
                          for s, lo, hi in zip(xp.shape, los, his))
        xp = _tap_sum(xp, starts, sdef.coeffs, new_shape)
    return xp.astype(x.dtype)


def stencil_iterate_dirichlet(x: jax.Array, sdef: StencilDef, steps: int) -> jax.Array:
    """Classic iteration: re-apply zero boundary conditions every step."""
    for _ in range(steps):
        x = stencil_apply(x, sdef)
    return x


# ---------------------------------------------------------------------------
# Scans
# ---------------------------------------------------------------------------

def cumsum(x: jax.Array) -> jax.Array:
    return jnp.cumsum(x.astype(jnp.float32), axis=-1).astype(x.dtype)


def sat(x: jax.Array) -> jax.Array:
    """Summed-area table: SAT[y,x] = Σ_{i≤y,j≤x} X[i,j]."""
    s = jnp.cumsum(x.astype(jnp.float32), axis=-1)
    return jnp.cumsum(s, axis=-2).astype(x.dtype)


def linear_recurrence(a: jax.Array, b: jax.Array) -> jax.Array:
    """Sequential gold: h_t = a_t·h_{t−1} + b_t along the last axis."""

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    h0 = jnp.zeros(a.shape[:-1], jnp.float32)
    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(a32, -1, 0), jnp.moveaxis(b32, -1, 0)))
    return jnp.moveaxis(hs, 0, -1).astype(a.dtype)
