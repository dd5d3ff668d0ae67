"""Driver: a language model served through ``repro.launch.serve``.

The model is built by ``repro.models.build_model`` from the program's
configuration of ``arch``, with the sizes, the dtype and the scan path
of the configuration file; the weights are the benchmark's own, drawn
from the seed (``bench/reference_<family>.py``). Requests go through a
``DecodeServer`` as its own ``run`` loop sends them: each dispatch
assigns queued requests to the free slots (each prompt prefilled alone,
``prefill_fn`` at B=1) and then makes one lock-step decode step of all
slots (``step_fn``), which reads the sampled tokens back to the host.
So the loop is closed and synchronous: ``throttle`` and ``drain`` have
nothing to do. The queue never runs dry: it repeats the traffic's cycle
of prompt and answer lengths (:func:`request_cycle`), the same for every
seed, with token ids uniform over the vocabulary drawn from the seed and
greedy answers of the cycle's lengths.

Every call into the server runs under the matrix-product precision the
configuration states (``matmul_precision``). The decode step's logits
are kept as the window produces them (a reference to each step's
output, no copy and no extra device work), so the check reads what the
timed path computed.

The check compares, once the window has closed and the program's
weights and state are freed, a sample of the finished requests (drawn
from the seed, the longest among them) with the plain reference run
once over each prompt and its served tokens: the program's logits at
each served position against the reference's (``logit_rel_gap``), and
how far each served token's logit lies below the reference's best
(``token_gap``).
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from statistics import NormalDist

import jax
import numpy as np

from bench import work

WARM_UP = 2**40          # request ids of the warm-up round
REFERENCE_BUCKET = 256   # the reference's sequences are padded to this
SIZES = ("n_layers", "d_model", "n_heads", "head_k", "head_v", "d_ff",
         "vocab", "shift_lora", "decay_lora")


def lognormal_quantiles(mean: float, sigma: float, n: int) -> list[float]:
    """The quantiles (i + 1/2) / n, i < n, of the lognormal distribution
    of this mean and log-scale ``sigma``."""
    mu = math.log(mean) - sigma ** 2 / 2
    normal = NormalDist()
    return [math.exp(mu + sigma * normal.inv_cdf((i + 0.5) / n))
            for i in range(n)]


def request_cycle(traffic: dict) -> list[tuple[int, int]]:
    """The traffic's cycle of (prompt length, answer length): the
    ``cycle`` quantiles of each lognormal, a prompt put to the nearest of
    its ``buckets`` in log scale and an answer rounded, paired and ordered
    by two permutations drawn from ``order_seed``."""
    n, p, a = int(traffic["cycle"]), traffic["prompt"], traffic["answer"]
    prompts = [min(p["buckets"], key=lambda b: abs(math.log(b / x)))
               for x in lognormal_quantiles(p["mean"], p["sigma"], n)]
    answers = [max(1, round(x))
               for x in lognormal_quantiles(a["mean"], a["sigma"], n)]
    rng = np.random.default_rng(int(traffic["order_seed"]))
    return [(int(prompts[i]), int(answers[j]))
            for i, j in zip(rng.permutation(n), rng.permutation(n))]


class LMServe:
    def __init__(self, config, traffic, *, seed, impl, devices):
        self.config, self.traffic = config, traffic
        self.seed, self.devices = seed, devices
        self.sizes = {k: int(config[k]) for k in SIZES}
        self.reference = importlib.import_module(
            f"bench.reference_{config['reference']}")
        self.cycle = request_cycle(traffic)
        self.slots = int(traffic["slots"])
        self.precision = config["matmul_precision"]
        self.server = None
        self.issued = 0
        self.finished: list = []
        self.step_logits: list = []      # each decode step's (slots, vocab)
        self.produced: dict = {}         # rid -> [(step, slot)] of its tokens
        self.checked: list = []          # (request, its logits), on the host
        self.counts = {"calls": 0, "attempted": 0, "requests": 0,
                       "failed": 0, "prefill_tokens": 0, "decode_tokens": 0}

    # ---- the system under test -------------------------------------------
    def _arch(self):
        from repro.config import get_config
        c = self.config
        return dataclasses.replace(
            get_config(c["arch"]), n_layers=c["n_layers"],
            d_model=c["d_model"], n_heads=c["n_heads"], kv_heads=c["n_heads"],
            head_dim=c["head_k"], head_k=c["head_k"], head_v=c["head_v"],
            d_ff=c["d_ff"], vocab=c["vocab"], wkv_chunk=c["wkv_chunk"],
            dtype=c["dtype"], scan_impl=c["scan_impl"])

    def _params(self):
        return self.reference.make_params(self.sizes, self.seed,
                                          dtype=self.config["dtype"],
                                          device=self.devices[0])

    def _request(self, rid: int, length: int, max_new: int):
        from repro.launch.serve import Request
        rng = np.random.default_rng([self.seed & (2**64 - 1), rid])
        prompt = rng.integers(0, self.sizes["vocab"], length, dtype=np.int32)
        return Request(rid, prompt, max_new)

    def _next(self):
        length, max_new = self.cycle[self.issued % len(self.cycle)]
        req = self._request(self.issued, length, max_new)
        self.issued += 1
        return req

    def _build(self):
        from repro.launch.serve import DecodeServer
        from repro.models import build_model
        from repro.nn.spec import abstract_params

        model = build_model(self._arch())
        params = self._params()
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            abstract_params(model.specs()))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != got:
            from bench.harness import BenchError
            raise BenchError("the benchmark's weights do not match the "
                             f"model's parameters: {want} != {got}")
        server = DecodeServer(model, params, slots=self.slots,
                              cache_len=int(self.traffic["cache_len"]),
                              temperature=0.0)
        step_fn = server.step_fn

        def step_keeping_logits(*args):
            logits, state = step_fn(*args)
            self.step_logits.append(logits)
            return logits, state
        server.step_fn = step_keeping_logits
        return server

    def setup(self):
        """The model and its weights on the chip, then every program the
        window runs compiled and run: one warm-up request of each prompt
        length of the cycle (a prefill program each) in a slot of its
        own, answered with two tokens. The programs do not depend on the
        slot or the answer's length, so the other slots need no warm-up.
        The window's queue starts after the warm-up's."""
        lengths = sorted({length for length, _ in self.cycle})
        with jax.default_matmul_precision(self.precision):
            s = self.server = self._build()
            for i, length in enumerate(lengths):
                while not s.free_slots():
                    s.step()
                s.assign(self._request(WARM_UP + i, length, 2),
                         s.free_slots()[0])
            while s.active_mask.any():
                s.step()
        self.step_logits.clear()

    def dispatch(self):
        s = self.server
        with jax.default_matmul_precision(self.precision):
            with jax.profiler.TraceAnnotation("bench.admit"):
                for b in s.free_slots():
                    req = self._next()
                    s.assign(req, b)
                    self.counts["attempted"] += 1
                    self.counts["prefill_tokens"] += len(req.prompt) - 1
            active = [(b, s.slot_req[b]) for b in range(s.B)
                      if s.active_mask[b]]
            with jax.profiler.TraceAnnotation("bench.step"):
                s.step()
        step = len(self.step_logits) - 1
        self.counts["calls"] += 1
        self.counts["decode_tokens"] += len(active)
        for b, req in active:
            self.produced.setdefault(req.rid, []).append((step, b))
            if req.done:
                self.finished.append(req)
                self.counts["requests"] += 1
                self.counts["failed"] += req.error is not None

    def throttle(self):
        pass

    def drain(self):
        pass

    def completed(self) -> dict:
        c = dict(self.counts)
        c["tokens"] = c["prefill_tokens"] + c["decode_tokens"]
        return c

    def work_per_call(self) -> dict:
        """Algorithmic work per token, all layers: ``flops_prefill`` and
        ``flops_decode`` of the model (``bench/work.py``), and the WKV
        scan's ``scan_flops`` and ``scan_bytes``."""
        s = self.sizes
        return {"flops_prefill": work.rwkv6_token_flops(s, logits=False),
                "flops_decode": work.rwkv6_token_flops(s, logits=True),
                "scan_flops": work.wkv_token_flops(s),
                "scan_bytes": work.wkv_token_bytes(
                    s, np.dtype(self.config["dtype"]).itemsize)}

    def release(self):
        """Copy the sampled requests' logits to the host, then free the
        program's weights, state, kept logits and compiled programs."""
        host = {}
        for req in self.sample():
            at = self.produced[req.rid]
            for k in {k for k, _ in at} - host.keys():
                host[k] = np.asarray(self.step_logits[k])
            self.checked.append((req, np.stack([host[k][b] for k, b in at])))
        self.step_logits.clear()
        self.server = None

    # ---- the check -------------------------------------------------------
    def sample(self) -> list:
        """Up to ``check_requests`` finished requests drawn from the seed,
        the longest (prompt and answer; the last of them) always among
        them."""
        done = [r for r in self.finished if r.error is None]
        n = int(self.traffic["check_requests"])
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda i: (len(done[i].prompt) + len(done[i].out),
                                     i))
        rest = [i for i in range(len(done)) if i != longest]
        rng = np.random.default_rng([self.seed & (2**64 - 1), 2**31])
        pick = rng.permutation(rest)[:n - 1].tolist()
        return [done[i] for i in sorted([longest] + pick)]

    def compare(self, dtype="float32", precision="highest") -> dict:
        """The check's readings of the sampled requests: the program's kept
        logits against the reference's, run once over each prompt and its
        served tokens. With a ``dtype`` or ``precision`` below the
        configuration's, the reference computed so stands in the program's
        place (the control): its logits at the same positions, and the
        tokens it puts first there."""
        ref = self.reference
        control = (dtype, precision) != ("float32", "highest")
        params = self._params()
        rel, gaps, bad = [], [], 0
        for req, got in self.checked:
            served = np.asarray(req.out, np.int32)
            seq = np.concatenate([req.prompt, served[:-1]])
            pos = np.arange(len(req.prompt) - 1, len(seq))
            want = _forward(ref, params, seq, pos)
            if control:
                got = _forward(ref, params, seq, pos, dtype=dtype,
                               precision=precision)
                served = got.argmax(-1).astype(np.int32)
            bad += int((~np.isfinite(got)).sum())
            rel.append(float(np.abs(got - want).max() / np.abs(want).max()))
            gaps.append(np.asarray(ref.served_gaps(want, served)))
        del params
        g = np.concatenate(gaps) if gaps else np.zeros((0,), np.float32)
        return {"logit_rel_gap": max(rel, default=float("inf")),
                "token_gap": float(np.nanmax(g)) if g.size else float("inf"),
                "nonfinite": bad + int((~np.isfinite(g)).sum()),
                "positions": int(g.size)}

    def check(self) -> list[dict]:
        want = int(self.traffic["check_requests"])
        got = self.compare()
        limits = self.config["limits"]
        short = sum(len(r.out) != r.max_new for r, _ in self.checked)
        out = [{"name": name, "value": got[name], "limit": limit,
                "ok": got[name] <= limit} for name, limit in limits.items()]
        return out + [
            {"name": "nonfinite", "value": got["nonfinite"], "limit": 0,
             "ok": got["nonfinite"] == 0},
            {"name": "requests_missing", "value": want - len(self.checked),
             "limit": 0, "ok": len(self.checked) == want},
            {"name": "answers_short", "value": short, "limit": 0,
             "ok": short == 0},
        ]


def _forward(ref, params, seq, pos, **kw):
    """The reference's logits at ``pos`` of ``seq``, with the sequence
    and the positions padded to a multiple of :data:`REFERENCE_BUCKET`
    (the tokens with 0 after the end, which no earlier position sees; the
    positions by repeating the last), so that runs share few compiled
    shapes."""
    size = -(-len(seq) // REFERENCE_BUCKET) * REFERENCE_BUCKET
    logits = ref.forward(params, np.pad(seq, (0, size - len(seq))),
                         np.pad(pos, (0, size - len(pos)), mode="edge"), **kw)
    return np.asarray(logits)[:len(pos)]


def make(config, traffic, *, seed, impl, devices):
    """``impl`` is not passed on: the model's engine scan takes Mosaic on
    a TPU and the Pallas interpreter elsewhere (``ops.default_engine_impl``),
    as the serving path does."""
    return LMServe(config, traffic, seed=seed, impl=impl, devices=devices)
