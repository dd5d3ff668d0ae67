"""Plain reference of RWKV-6 "Finch" (arXiv:2404.05892), and the weights
the benchmark makes for it from a seed.

:func:`forward` follows the paper's equations for one sequence, in
straightforward ``jax.numpy``, with no kernel, cache or batching: token
shift with the data-dependent lerp (ddlerp, its low-rank mixes), the
low-rank data-dependent decay ``w_t = exp(-exp(w0 + tanh(x_w A) B))``,
the WKV recurrence run one token after another with the bonus ``u``
(``o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)``, ``S_t = diag(w_t) S_{t-1}
+ k_t^T v_t``), a group norm per head, the SiLU gate and the output
projection; then the channel mix ``sigmoid(x_r W_r) * (relu(x_k W_k)^2
W_v)``. Its matrix products run at ``highest`` precision unless it is
told otherwise (:func:`einsum_at`).

Where this departs from the paper it follows the parameter layout the
benchmark serves (the one ``repro.models.rwkv6`` reads): RMS norms with a
scale where RWKV-6 has layer norms with a scale and a bias (before the
first layer, before each mix, at the end); a group norm with a scale and
no bias; the output head tied to the embedding table. It imports nothing
of the program and reads only the parameter tree.

:func:`make_params` draws that tree from a key, on the device, in one
jitted call. The same key gives the same weights, so the check draws
them again after the program's have been freed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

GROUP_NORM_EPS = 64e-5     # RWKV-6: 1e-5 x head_size_divisor (8) ** 2
RMS_EPS = 1e-6
# With the head tied to the embedding, a token's own embedding at full
# scale in the residual stream outweighs what random layers add to it,
# and greedy decoding repeats the last token whatever the layers compute.
NORM_IN_SCALE = 0.1


def _shapes(s: dict) -> dict:
    """The parameter tree's shapes for the sizes ``s`` of a configuration
    file (layers stacked on a leading axis)."""
    L, d, H = s["n_layers"], s["d_model"], s["n_heads"]
    K, V, F = s["head_k"], s["head_v"], s["d_ff"]
    sl, dl = s["shift_lora"], s["decay_lora"]
    return {
        "embed": {"table": (s["vocab"], d)},
        "norm_in": {"scale": (d,)},
        "layers": {
            "norm_tm": {"scale": (L, d)},
            "norm_cm": {"scale": (L, d)},
            "tm": {
                "mu_x": (L, d), "mu": (L, 5, d),
                "shift_w1": (L, d, 5 * sl), "shift_w2": (L, 5, sl, d),
                "w0": (L, H, K), "decay_w1": (L, d, dl),
                "decay_w2": (L, dl, H, K), "u": (L, H, K),
                "wr": (L, d, H, K), "wk": (L, d, H, K),
                "wv": (L, d, H, V), "wg": (L, d, H, V),
                "ln_x": (L, H, V), "wo": (L, H, V, d),
            },
            "cm": {"mu_k": (L, d), "mu_r": (L, d), "wk": (L, d, F),
                   "wv": (L, F, d), "wr": (L, d, d)},
        },
        "norm_f": {"scale": (d,)},
    }


def _ramps(L: int, H: int, K: int):
    """RWKV-6's initial decay base and bonus per layer and channel (its
    reference code's initialisation): decays from exp(-exp(-6)) (a memory
    of some hundreds of tokens) to exp(-exp(-1)), slower in later layers."""
    n = jnp.arange(H * K, dtype=jnp.float32) / (H * K - 1)
    ratio = jnp.arange(L, dtype=jnp.float32)[:, None] / max(L - 1, 1)
    w0 = -6.0 + 5.0 * n[None] ** (0.7 + 1.3 * ratio)
    zigzag = ((jnp.arange(H * K) + 1) % 3 - 1) * 0.1
    u = ratio * (1.0 - n[None]) + zigzag[None]
    return w0.reshape(L, H, K), u.reshape(L, H, K)


def _leaf(path: str, shape, key, s: dict, dtype):
    """One leaf: norms at 1, but for the one before the first layer
    (:data:`NORM_IN_SCALE`); lerp coefficients uniform on [0, 1]; the
    decay base and bonus from :func:`_ramps`; the low-rank second factors
    normal with std 0.1; every other matrix normal with std one over the
    square root of the width it contracts."""
    name = path.rsplit("/", 1)[-1]
    if path == "norm_in/scale":
        return jnp.full(shape, NORM_IN_SCALE, dtype)
    if name in ("scale", "ln_x"):
        return jnp.ones(shape, dtype)
    if name.startswith("mu"):
        return jax.random.uniform(key, shape, dtype)
    if name in ("w0", "u"):
        w0, u = _ramps(s["n_layers"], s["n_heads"], s["head_k"])
        return (w0 if name == "w0" else u).astype(dtype)
    if name in ("shift_w2", "decay_w2"):
        std = 0.1
    elif path.startswith("embed"):
        std = 0.02
    elif name == "wo":
        std = 1.0 / math.sqrt(s["n_heads"] * s["head_v"])
    elif path.endswith("cm/wv"):
        std = 1.0 / math.sqrt(s["d_ff"])
    else:
        std = 1.0 / math.sqrt(s["d_model"])
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the
    rest is folded in, so seeds past 2**32 stay distinct."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(sizes: dict, seed: int, *, dtype="float32", device=None):
    """The weights of one seed, made on ``device`` in one jitted call."""
    shapes = _shapes(sizes)
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    paths = ["/".join(k.key for k in p) for p, _ in flat]
    treedef = jax.tree_util.tree_structure(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def gen(key):
        leaves = [_leaf(p, shape, jax.random.fold_in(key, i), sizes,
                        jnp.dtype(dtype))
                  for i, (p, (_, shape)) in enumerate(zip(paths, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    if device is not None:
        from jax.sharding import SingleDeviceSharding
        fn = jax.jit(gen, out_shardings=SingleDeviceSharding(device))
    else:
        fn = jax.jit(gen)
    return fn(seed_key(seed))


# ---- the forward pass ----------------------------------------------------

def _rms(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * scale


def _shift(x):
    """x_{t-1}, zero before the first token."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def einsum_at(precision: str):
    """``jnp.einsum`` with its products at ``precision``: ``"highest"``
    (float32 products), ``"default"``, or ``"high"``, spelled out so that
    every backend computes it as a TPU does: each float32 operand split
    into a high and a low bfloat16 part, and three bfloat16 products (high
    by high, high by low, low by high) summed in float32."""
    if precision != "high":
        return functools.partial(jnp.einsum, precision=precision)

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(x.dtype)
        return hi, (x - hi).astype(jnp.bfloat16).astype(x.dtype)

    def einsum(spec, a, b):
        # a product of two bfloat16 values is exact in float32
        (ah, al), (bh, bl) = split(a), split(b)
        prod = functools.partial(jnp.einsum, spec, precision="highest")
        return prod(ah, bh) + (prod(ah, bl) + prod(al, bh))
    return einsum


def _wkv(ein, r, k, v, w, u):
    """o_t = r_t (S_{t-1} + diag(u) k_t^T v_t); S_t = diag(w_t) S_{t-1} +
    k_t^T v_t, one token after another. r, k, w: (T, H, K); v: (T, H, V);
    u: (H, K). The state is kept in the inputs' dtype."""
    H, K, V = r.shape[1], r.shape[2], v.shape[2]

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[:, :, None] * v_t[:, None, :]                   # (H, K, V)
        o = ein("hk,hkv->hv", r_t, S + u[:, :, None] * kv)
        return w_t[:, :, None] * S + kv, o.astype(r.dtype)

    S0 = jnp.zeros((H, K, V), r.dtype)
    _, o = jax.lax.scan(step, S0, (r, k, v, w))
    return o


def _time_mix(ein, p, x):
    T, d = x.shape
    dx = _shift(x) - x
    xxx = x + dx * p["mu_x"]
    lora = jnp.tanh(ein("td,dl->tl", xxx, p["shift_w1"])).reshape(T, 5, -1)
    mix = ein("tfl,fld->tfd", lora, p["shift_w2"]) + p["mu"]
    xw, xk, xv, xr, xg = (x + dx * mix[:, i] for i in range(5))
    r = ein("td,dhk->thk", xr, p["wr"])
    k = ein("td,dhk->thk", xk, p["wk"])
    v = ein("td,dhv->thv", xv, p["wv"])
    g = ein("td,dhv->thv", xg, p["wg"])
    w = p["w0"] + ein("tl,lhk->thk",
                      jnp.tanh(ein("td,dl->tl", xw, p["decay_w1"])),
                      p["decay_w2"])
    o = _wkv(ein, r, k, v, jnp.exp(-jnp.exp(w)), p["u"])
    mean = o.mean(-1, keepdims=True)
    var = ((o - mean) ** 2).mean(-1, keepdims=True)
    o = (o - mean) * jax.lax.rsqrt(var + GROUP_NORM_EPS) * p["ln_x"]
    return ein("thv,hvd->td", o * jax.nn.silu(g), p["wo"])


def _channel_mix(ein, p, x):
    dx = _shift(x) - x
    xk = x + dx * p["mu_k"]
    xr = x + dx * p["mu_r"]
    k = jnp.square(jax.nn.relu(ein("td,df->tf", xk, p["wk"])))
    return (jax.nn.sigmoid(ein("td,de->te", xr, p["wr"]))
            * ein("tf,fd->td", k, p["wv"]))


@functools.partial(jax.jit, static_argnames=("dtype", "precision"))
def forward(params, tokens, positions, *, dtype="float32",
            precision="highest"):
    """Logits (P, vocab), in float32, at ``positions`` (P,) of the
    sequence ``tokens`` (T,), computed in ``dtype`` throughout (the
    weights, the activations and the WKV state), every matrix product at
    ``precision`` (:func:`einsum_at`)."""
    dt = jnp.dtype(dtype)
    ein = einsum_at(precision)
    p = jax.tree.map(lambda a: a.astype(dt), params)
    x = _rms(p["embed"]["table"][tokens], p["norm_in"]["scale"])

    def layer(x, lp):
        h = _time_mix(ein, lp["tm"], _rms(x, lp["norm_tm"]["scale"]))
        x = x + h.astype(dt)
        h = _channel_mix(ein, lp["cm"], _rms(x, lp["norm_cm"]["scale"]))
        return x + h.astype(dt), None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = _rms(x[positions], p["norm_f"]["scale"])
    return ein("pd,vd->pv", x, p["embed"]["table"]).astype(jnp.float32)


def served_gaps(ref_logits, tokens):
    """For each position, how far the reference's logit of ``tokens``
    lies below its best logit (0 where the token is the reference's own
    greedy pick), in standard deviations of the reference's logits at
    that position; non-finite where the reference is."""
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return (ref_logits.max(-1) - got) / ref_logits.std(-1)
