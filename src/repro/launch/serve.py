"""Batched serving driver with continuous batching.

A fixed pool of B decode slots advances in lock-step through one jitted
``serve_step`` per token; each slot carries its own write index, so a
finished request's slot is immediately refilled from the queue while the
other slots keep decoding (continuous batching — no batch-wide drain).
Per-slot indices flow through the whole cache machinery
(:func:`repro.nn.attention._cache_write` vmaps the cache write).

Prefill: recurrent archs (RWKV6) expose ``model.prefill`` — the whole
prompt runs through the chunk-streamed scan plans in one call
(DESIGN.md §12) and only the resulting O(1) state lands in the slot;
KV-cache archs feed the prompt token-by-token through ``serve_step``.

Greedy sampling by default; temperature optional. This driver doubles as
the end-to-end serving example (examples/serve_decode.py wraps it).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
      --slots 4 --max-new 32 --requests 12
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.robust import faults as rfaults
from repro.robust import guard as rguard

# consecutive serve_step failures tolerated before the server sheds load
# (evicts the oldest active request) to break a poison-request livelock
MAX_STEP_RETRIES = 3


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (L,) int32
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_assign: float = 0.0       # slot-assignment wall time (latency metric)
    deadline_s: float | None = None   # wall-clock budget from slot assignment
    error: str | None = None    # why the request failed (None = clean finish)


class DecodeServer:
    """Continuous-batching decode server over a fixed slot pool."""

    def __init__(self, model, params, *, slots: int, cache_len: int,
                 temperature: float = 0.0, seed: int = 0):
        from repro.nn.spec import init_params
        self.model = model
        self.params = params
        self.B = slots
        self.cache_len = cache_len
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.state = init_params(model.decode_state_specs(slots, cache_len),
                                 jax.random.PRNGKey(0))
        self.index = np.zeros((slots,), np.int32)     # per-slot positions
        self.slot_req: list[Request | None] = [None] * slots
        self.prompt_left: list[np.ndarray] = [np.zeros((0,), np.int32)] * slots
        self.step_fn = jax.jit(model.serve_step)
        # recurrent archs expose whole-prompt prefill through the chunked
        # scan plans (DESIGN.md §12); KV-cache archs fall back to feeding
        # the prompt token-by-token through serve_step.
        self.prefill_fn = (jax.jit(model.prefill)
                           if hasattr(model, "prefill") else None)
        self.tokens = np.zeros((slots, 1), np.int32)
        self.active_mask = np.zeros((slots,), bool)
        self.steps = 0
        self.step_failures = 0

    def assign(self, req: Request, slot: int):
        req.t_assign = time.perf_counter()
        self.slot_req[slot] = req
        self.index[slot] = 0
        self.active_mask[slot] = True
        # zero this slot's state so a stale cache cannot leak across requests
        self.state = jax.tree.map(
            lambda s: s.at[:, slot].set(0) if s.ndim >= 2 else s, self.state)
        if self.prefill_fn is not None and len(req.prompt) > 1:
            # one batched scan over prompt[:-1] replaces L−1 serve_step
            # calls; the last prompt token then rides the normal decode
            # step, so the slot's state trajectory is identical to the
            # token-by-token path (greedy outputs match exactly).
            _, st = self.prefill_fn(
                self.params, jnp.asarray(req.prompt[None, :-1]))
            self.state = jax.tree.map(
                lambda s, n: (s.at[:, slot].set(n[:, 0].astype(s.dtype))
                              if s.ndim >= 2 else s),
                self.state, st)
            self.index[slot] = len(req.prompt) - 1
            self.tokens[slot, 0] = req.prompt[-1]
            self.prompt_left[slot] = np.zeros((0,), np.int32)
        else:
            self.tokens[slot, 0] = req.prompt[0]
            self.prompt_left[slot] = req.prompt[1:]

    def step(self):
        """One lock-step decode across all slots."""
        rfaults.check("serve.step")
        t0 = time.perf_counter()
        logits, self.state = self.step_fn(
            self.params, self.state, jnp.asarray(self.tokens),
            jnp.asarray(self.index))
        self.steps += 1
        # Histogram of dispatch wall-time per batched step (the first
        # sample includes the jit compile; p50 is the steady state).
        obs.metrics.observe("serve.step_us",
                            (time.perf_counter() - t0) * 1e6)
        if self.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            nxt = jax.random.categorical(sub, logits / self.temperature, -1)
        else:
            nxt = jnp.argmax(logits, -1)
        nxt = np.asarray(nxt, np.int32)
        for b in range(self.B):
            if not self.active_mask[b]:
                continue
            req = self.slot_req[b]
            self.index[b] += 1
            if len(self.prompt_left[b]):               # still prefilling
                self.tokens[b, 0] = self.prompt_left[b][0]
                self.prompt_left[b] = self.prompt_left[b][1:]
            else:
                req.out.append(int(nxt[b]))
                self.tokens[b, 0] = nxt[b]
                if (len(req.out) >= req.max_new
                        or self.index[b] >= self.cache_len - 1):
                    req.done = True
                    self.active_mask[b] = False
                    self.slot_req[b] = None
                    # assignment→completion latency; p50/p99 come out of
                    # metrics.snapshot()["histograms"]["serve.request_us"]
                    obs.metrics.observe(
                        "serve.request_us",
                        (time.perf_counter() - req.t_assign) * 1e6)
                    obs.metrics.inc("serve.requests")

    def free_slots(self):
        return [b for b in range(self.B) if not self.active_mask[b]]

    def _fail_slot(self, b: int, reason: str):
        """Reclaim slot ``b``: mark its request failed-but-done so the
        driver returns it (with ``.error`` set) instead of hanging, and
        free the slot for the next queued request."""
        req = self.slot_req[b]
        if req is not None:
            req.error = reason
            req.done = True
            obs.metrics.inc("serve.request_error", reason.split(":")[0])
        self.active_mask[b] = False
        self.slot_req[b] = None
        self.prompt_left[b] = np.zeros((0,), np.int32)

    def _sweep_deadlines(self):
        now = time.perf_counter()
        for b in range(self.B):
            req = self.slot_req[b]
            if (req is not None and req.deadline_s is not None
                    and now - req.t_assign > req.deadline_s):
                obs.metrics.inc("serve.deadline_exceeded")
                self._fail_slot(b, "deadline")

    def health(self) -> dict:
        """Liveness snapshot for external monitors (and the chaos bench)."""
        return {
            "steps": self.steps,
            "step_failures": self.step_failures,
            "active_slots": int(self.active_mask.sum()),
            "slots": self.B,
            "requests_completed": obs.metrics.counter_total("serve.requests"),
            "requests_failed":
                obs.metrics.counter_total("serve.request_error"),
        }

    def run(self, requests: list[Request]) -> list[Request]:
        """Drain ``requests`` through the slot pool.

        A step failure no longer hangs the driver: under the session
        policy ``on_failure='raise'`` it propagates (injected faults as
        :class:`GuardedExecutionError` naming ``serve.step``); under
        ``'fallback'`` the step retries up to :data:`MAX_STEP_RETRIES`
        consecutive times, then the oldest active request is evicted
        (``.error`` set, slot freed) so the rest of the pool makes
        progress. Per-request ``deadline_s`` budgets are swept every
        iteration. Every request always comes back ``done`` — check
        ``.error`` to tell clean completions from failures.
        """
        queue = list(requests)
        done: list[Request] = []
        streak = 0
        while queue or self.active_mask.any():
            self._sweep_deadlines()
            for b in self.free_slots():
                if not queue:
                    break
                self.assign(queue.pop(0), b)
            if self.active_mask.any():
                try:
                    self.step()
                    streak = 0
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    self.step_failures += 1
                    obs.metrics.inc("serve.step_error", type(e).__name__)
                    if rguard.on_failure() == "raise":
                        if isinstance(e, rfaults.FaultInjected):
                            raise rguard.GuardedExecutionError(
                                "serve.step", [("step", e)]) from e
                        raise
                    streak += 1
                    if streak > MAX_STEP_RETRIES:
                        active = [b for b in range(self.B)
                                  if self.active_mask[b]]
                        if active:
                            oldest = min(
                                active,
                                key=lambda b: self.slot_req[b].t_assign)
                            self._fail_slot(oldest, "step_failure")
                        streak = 0
            for r in requests:
                if r.done and r not in done:
                    done.append(r)
        return done


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--scan-impl", default=None,
                    choices=("engine", "engine_unchunked", "chunked"),
                    help="recurrence schedule for scan-family archs: "
                         "chunk-streamed engine / monolithic engine / XLA "
                         "chunked scan (default: backend pick, DESIGN.md §12)")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()

    from repro.config import get_config
    from repro.models import build_model
    from repro.nn.spec import init_params

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.scan_impl:
        cfg = dataclasses.replace(cfg, scan_impl=args.scan_impl)
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    server = DecodeServer(model, params, slots=args.slots,
                          cache_len=args.cache_len)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab, args.prompt_len,
                                    dtype=np.int32), args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    done = server.run(reqs)
    dt = time.time() - t0
    tok = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok/dt:.1f} tok/s, {server.steps} batched steps)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}…")
    return done


if __name__ == "__main__":
    main()
