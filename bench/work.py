"""The algorithmic work of one engine call or one token, from shapes alone.

These counts are what a call must do whatever implements it: the
stencil's FLOPs per point (the paper's Table 3 FPP) for every cell and
fused step, and one read plus one write of the field. What an
implementation adds on top (halo re-reads, tile round-up, padding
copies, recomputed trapezoid rows) is not work here: it shows as time.
For a served model they are the FLOPs of its matrix products and
recurrence per token, and the bytes a scan must move per token.
"""
from __future__ import annotations

import math


def stencil_call_flops(shape, fpp: int, steps: int) -> int:
    """FLOPs of ``steps`` fused stencil steps over a field of ``shape``."""
    return fpp * math.prod(shape) * steps


def stencil_call_bytes(shape, itemsize: int) -> int:
    """Bytes of one read and one write of a field of ``shape``."""
    return 2 * itemsize * math.prod(shape)


def least_time_s(flops: int, nbytes: int, peaks: dict) -> float:
    """The least time the chip could take for this work: the larger of
    FLOPs over peak FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def rwkv6_token_flops(s: dict, *, logits: bool) -> int:
    """FLOPs of one token through an RWKV-6 model of sizes ``s`` (all
    layers): two for each weight of every matrix product (the token
    shift's and the decay's low-rank factors, r, k, v, g and the output
    projection, the channel mix), the WKV term (:func:`wkv_token_flops`)
    and, with ``logits``, the tied output head."""
    d, H, K, V = s["d_model"], s["n_heads"], s["head_k"], s["head_v"]
    sl, dl, F = s["shift_lora"], s["decay_lora"], s["d_ff"]
    time_mix = (10 * d * sl + d * dl + dl * H * K      # low-rank factors
                + 2 * d * H * K + 2 * d * H * V         # r, k; v, g
                + H * V * d)                            # output
    channel_mix = 2 * d * F + d * d
    per_token = 2 * s["n_layers"] * (time_mix + channel_mix)
    head = 2 * d * s["vocab"] if logits else 0
    return per_token + wkv_token_flops(s) + head


def wkv_token_flops(s: dict) -> int:
    """The WKV recurrence's FLOPs for one token, all layers: per head the
    outer product k^T v, the decay and the add into the state, and r read
    against the state, about 4 K V."""
    return 4 * s["n_layers"] * s["n_heads"] * s["head_k"] * s["head_v"]


def wkv_token_bytes(s: dict, itemsize: int) -> int:
    """The WKV scan's bytes for one token, all layers: one read of r, k,
    the decay and v and one write of its output."""
    H, K, V = s["n_heads"], s["head_k"], s["head_v"]
    return s["n_layers"] * itemsize * (3 * H * K + 2 * H * V)
