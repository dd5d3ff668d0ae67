"""Driver: a stencil sweep through ``repro.kernels.ops.stencil``.

One dispatch is one jitted program of ``calls_per_dispatch`` engine calls
of ``steps_per_call`` fused steps each (``impl='pallas'``, untuned family
default blocks, no tuning sidecar). Dispatches chain: each takes the
field the last one produced, and the host waits only to keep
``in_flight`` of them queued. A configuration with a ``mesh`` shards the
field over a 2-D domain mesh and passes ``mesh=`` to the op, so each call
runs the halo exchange.

The check compares the window's last dispatch, its input and its output
at the timed size, with the plain reference (``bench/reference.py``).
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from bench import reference, work


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the
    rest is folded in, so seeds past 2**32 stay distinct."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class StencilSweep:
    def __init__(self, config, traffic, *, seed, impl, devices):
        self.config, self.traffic = config, traffic
        self.seed, self.impl, self.devices = seed, impl, devices
        self.shape = tuple(config["domain"])
        self.dtype = jnp.dtype(config["dtype"])
        self.steps = int(traffic["steps_per_call"])
        self.calls_per_dispatch = int(traffic["calls_per_dispatch"])
        self.in_flight = int(traffic["in_flight"])
        self.mesh = None
        self.dispatches = 0
        self.pending = collections.deque()
        self.x = self.prev = None

    def _sharding(self):
        if self.config.get("mesh") is None:
            return SingleDeviceSharding(self.devices[0])
        from repro.launch.mesh import make_domain_mesh
        self.mesh = make_domain_mesh(tuple(self.config["mesh"]))
        return NamedSharding(self.mesh, P(*self.mesh.axis_names))

    def _program(self, x):
        from repro.kernels import ops
        kw = {}
        if self.mesh is not None:
            kw = {"mesh": self.mesh, "boundary": self.config["boundary"]}
        for _ in range(self.calls_per_dispatch):
            x = ops.stencil(x, self.config["stencil"], time_steps=self.steps,
                            impl=self.impl, **kw)
        return x

    def setup(self):
        """The field from the seed, on the device in one jitted call, and
        the sweep program compiled and run twice (so an output that comes
        back with another layout would compile here, not in the window)."""
        sharding = self._sharding()
        gen = jax.jit(lambda key: jax.random.normal(key, self.shape,
                                                    self.dtype),
                      out_shardings=sharding)
        self.x = gen(seed_key(self.seed))
        self.fn = jax.jit(self._program)
        warm = self.fn(self.fn(self.x))
        jax.block_until_ready(warm)
        del warm
        jax.block_until_ready(self.x)

    def dispatch(self):
        self.prev = self.x
        self.x = self.fn(self.x)
        self.pending.append(self.x)
        self.dispatches += 1

    def throttle(self):
        while len(self.pending) > self.in_flight:
            self.pending.popleft().block_until_ready()

    def drain(self):
        self.x.block_until_ready()
        self.pending.clear()

    def completed(self) -> dict:
        calls = self.dispatches * self.calls_per_dispatch
        return {"calls": calls,
                "cell_updates": calls * self.steps * math.prod(self.shape)}

    def shard_shape(self):
        mesh = self.config.get("mesh") or [1] * len(self.shape)
        return tuple(n // k for n, k in zip(self.shape, mesh))

    def work_per_call(self) -> dict:
        shard = self.shard_shape()
        return {"flops": work.stencil_call_flops(shard, self.config["fpp"],
                                                 self.steps),
                "bytes": work.stencil_call_bytes(shard, self.dtype.itemsize)}

    def release(self):
        """Free all but the last dispatch's input and output."""
        self.pending.clear()
        self.fn = None

    def gap(self, got_dtype=None) -> reference.Gap:
        return reference.compare_sweep(
            self.prev, self.x, offsets=self.config["offsets"],
            coeffs=self.config["coeffs"], steps=self.steps,
            calls=self.calls_per_dispatch, dtype=self.config["dtype"],
            got_dtype=got_dtype)

    def check(self) -> list[dict]:
        g = self.gap()
        limit = self.config["limits"]["rel_gap"]
        return [
            {"name": "rel_gap", "value": g.rel_gap, "limit": limit,
             "ok": g.nonfinite == 0 and g.rel_gap <= limit},
            {"name": "nonfinite", "value": g.nonfinite, "limit": 0,
             "ok": g.nonfinite == 0},
        ]


def make(config, traffic, *, seed, impl, devices):
    return StencilSweep(config, traffic, seed=seed, impl=impl,
                        devices=devices)
