"""The served-model cell at a size the CPU holds: the harness counts its
tokens, the plain reference agrees with the program, and ``correct`` holds
for the program and fails for the control and for each fault a served
cell can have (the look for a chip skipped, the Pallas interpreter)."""
import contextlib
import dataclasses
import io
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference_rwkv6 as ref, trace as tr

CELL = "rwkv6-1.6b.chat"
SMALL = dict(n_layers=2, d_model=64, n_heads=4, head_k=16, head_v=16,
             d_ff=224, vocab=512, wkv_chunk=16)
SEED = 2**31 + 13


def _cell(**traffic):
    cell = harness.resolve(CELL)
    mix = dict(cycle=8, prompt=dict(mean=14.0, sigma=1.0, buckets=[8, 16, 32]),
               answer=dict(mean=5.0, sigma=0.5), cache_len=256)
    mix.update(traffic)
    return dataclasses.replace(cell, config=dict(cell.config, **SMALL),
                               traffic=dict(cell.traffic, **mix))


def _run(cell, seed=SEED, seconds=1.0):
    err = io.StringIO()
    line = harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            t_start=time.perf_counter(), impl="interpret",
                            require_tpu=False, out=io.StringIO(), err=err)
    return line, err.getvalue()


def test_stencil_cells_report_what_they_did():
    per_layer = ["window_kernel_roofline", "engine_wrapper_share",
                 "device_idle_share", "sweep_mfu", "engine_pad_share"]
    halo = ["window_kernel_roofline", "engine_wrapper_share",
            "halo_exposed_share", "device_idle_share", "sweep_mfu",
            "engine_pad_share", "halo_copy_share"]
    for workload, want in [("jacobi2d-16k.t1", per_layer),
                           ("jacobi2d-16k.t4", per_layer),
                           ("jacobi2d-48k-x4.t4", halo)]:
        cell = harness.resolve(workload)
        assert [m["name"] for m in cell.end_to_end] == ["gcells_per_s",
                                                        "setup_s"]
        assert [m["name"] for m in cell.per_layer] == want


def test_serve_cell_reports_tokens_and_its_own_layers():
    cell = harness.resolve(CELL)
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "serve_mfu", "scan_kernel_roofline", "prefill_share",
        "serve_idle_share"]
    assert cell.config["scan_impl"] == "engine"


def test_serve_cell_is_correct():
    line, err = _run(_cell())
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(line)[-1] == "compared"
    assert line["attempted"] >= 8 and line["failed"] == 0
    assert line["compared"]["requests_missing"]["value"] == 0
    assert re.search(r"in the window 0 traces and 0 compiles", err)
    m = re.search(r"(\d+) prefill_tokens, (\d+) decode_tokens, (\d+) tokens",
                  err)
    assert int(m[1]) + int(m[2]) == int(m[3]) > 0


def _sequence(seed, length):
    return np.random.default_rng(seed).integers(0, SMALL["vocab"], length,
                                                dtype=np.int32)


def _program(seed):
    """The program built as the driver builds it, with the benchmark's
    weights of ``seed``."""
    d = harness.driver_module(harness.resolve(CELL)).make(
        _cell().config, _cell().traffic, seed=seed, impl="interpret",
        devices=jax.devices()[:1])
    server = d._build()
    return d, server


def test_reference_matches_forward_and_prefill_then_decode():
    d, server = _program(SEED)
    params, model = server.params, server.model
    seq = _sequence(1, 40)
    want = np.asarray(ref.forward(params, seq, np.arange(len(seq))))
    x, _ = model.forward(params, {"tokens": jnp.asarray(seq[None])})
    got = np.asarray(x[0] @ params["embed"]["table"].T)
    scale = want.std()
    assert np.abs(got - want).max() / scale < 1e-4

    # prefill of the first 24 tokens, then the server's decode step
    # through the cache for the rest, in slot 2 of the 8
    from repro.launch.serve import Request
    server.assign(Request(0, seq[:25], max_new=len(seq) - 24), 2)
    steps = []
    for t in range(24, len(seq)):
        logits, server.state = server.step_fn(
            server.params, server.state, jnp.asarray(server.tokens),
            jnp.asarray(server.index))
        steps.append(np.asarray(logits[2]))
        server.index[2] += 1
        if t + 1 < len(seq):
            server.tokens[2, 0] = seq[t + 1]
    assert np.abs(np.stack(steps) - want[24:]).max() / scale < 1e-4


def test_control_reads_far_above_the_program():
    """The control (the reference at the configuration's ``control``
    precision, 'high': three bfloat16 products for each float32 one) in
    the program's place reads a logit gap at least ten times the
    program's, whose logits are the ones its decode steps produced in the
    window. At this size 2 layers barely amplify rounding, so both read
    far under the limit; on the chip, at the published widths, the
    control reads above it (PERF.md)."""
    cell = _cell()
    d = harness.driver_module(cell).make(cell.config, cell.traffic,
                                         seed=SEED, impl="interpret",
                                         devices=jax.devices()[:1])
    d.setup()
    harness.measure(d, 1.0, lambda name: contextlib.nullcontext())
    d.release()
    program = d.compare()
    control = d.compare(**cell.config["control"])
    assert program["positions"] == control["positions"] > 0
    assert control["logit_rel_gap"] >= 10 * program["logit_rel_gap"] > 0


def _patch(monkeypatch, name, make):
    from repro.models import rwkv6
    monkeypatch.setattr(rwkv6.RWKV6, name, make(getattr(rwkv6.RWKV6, name)))


def _state_unchanged(real):
    def serve_step(self, params, state, tokens, index):
        logits, _ = real(self, params, state, tokens, index)
        return logits, state
    return serve_step


def _half_the_slots(real):
    """Each step leaves out the half of the slots at an even position:
    their logits read 0 and their state stays as it was."""
    def serve_step(self, params, state, tokens, index):
        logits, new = real(self, params, state, tokens, index)
        out = index % 2 == 0
        new = jax.tree.map(
            lambda n, o: jnp.where(out.reshape((1, -1) + (1,) * (n.ndim - 2)),
                                   o, n), new, state)
        return jnp.where(out[:, None], 0.0, logits), new
    return serve_step


def _token_altered(real):
    """Every request's token at one position of its answer becomes 7."""
    def serve_step(self, params, state, tokens, index):
        logits, new = real(self, params, state, tokens, index)
        due = index % 4 == 2
        return logits.at[:, 7].add(jnp.where(due, 1e3, 0.0)), new
    return serve_step


def _prefill_state_dropped(real):
    def prefill(self, params, tokens):
        logits, st = real(self, params, tokens)
        return logits, jax.tree.map(jnp.zeros_like, st)
    return prefill


@pytest.mark.parametrize("name, fault", [
    ("serve_step", _state_unchanged), ("serve_step", _half_the_slots),
    ("serve_step", _token_altered), ("prefill", _prefill_state_dropped)],
    ids=["state_unchanged", "half_the_slots_left_out", "token_altered",
         "prefill_state_dropped"])
def test_fault_fails(name, fault, monkeypatch):
    _patch(monkeypatch, name, fault)
    line, _ = _run(_cell())
    assert line["correct"] is False


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


SCAN = ('%repro_scan.3 = f32[1024,16]{1,0} custom-call(f32[1024,16]{1,0} '
        '%a, f32[1024,16]{1,0} %b), custom_call_target="tpu_custom_call"')
DOT = "%fusion.1 = f32[8,512]{1,0} fusion(f32[8,64]{1,0} %x), kind=kOutput"


def _profile():
    """One chip; the window is [1000, 2000): a prefill program [1000,
    1500) with two scan kernels and a matmul, a decode step [1600, 1800)."""
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=[
            _event("bench.window", 1000, 1000),
            _event("bench.dispatch", 1000, 1000),
            _event("bench.admit", 1000, 500),
            _event("bench.step", 1500, 500)])])
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            _event(SCAN, 1000, 100), _event(SCAN, 1200, 100),
            _event(DOT, 1300, 200), _event(DOT, 1600, 200)]),
        types.SimpleNamespace(name="XLA Modules", events=[
            _event("jit_prefill(123)", 1000, 500),
            _event("jit_serve_step(456)", 1600, 200)])])
    return types.SimpleNamespace(planes=[host, dev])


def test_serve_metric_readers_on_a_reduced_trace():
    r = tr.reduce_profile(_profile(), kernel="repro_scan")
    assert r.devices[0].modules[0] == (1000, 1500, "jit_prefill(123)")
    # the longest gap, [1800, 2000), lies in bench.step in bench.dispatch
    assert r.breakdown()["idle_gaps"][0] == ["bench.step (/device:TPU:0)",
                                             pytest.approx(200e-9)]
    cell = harness.resolve(CELL)
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    work = {"flops_prefill": 10, "flops_decode": 20, "scan_flops": 4,
            "scan_bytes": 50}
    run = harness.Run(cell=cell, peaks=peaks, setup_s=1.0, window_s=1e-6,
                      work=work, trace=r,
                      counts={"calls": 2, "prefill_tokens": 3,
                              "decode_tokens": 8, "tokens": 11})
    read = {m["name"]: harness.metric_reader(cell, m["name"]).read
            for m in cell.per_layer + cell.end_to_end}
    assert read["tokens_per_s"](run) == pytest.approx(11 / 1e-6)
    # busy [1000, 1100) [1200, 1500) [1600, 1800): 600 of 1000 ns
    assert read["serve_idle_share"](run) == pytest.approx(40.0)
    assert read["prefill_share"](run) == pytest.approx(100 * 400 / 600)
    # 3 tokens x 50 bytes at 1e12 B/s over 200 ns of scan kernels
    assert read["scan_kernel_roofline"](run) == pytest.approx(
        100 * 150e-12 / 200e-9)
    assert read["serve_mfu"](run) == pytest.approx(
        100 * (3 * 10 + 8 * 20) / (1e12 * 1e-6))
    # nothing to read: no trace, no prefill in the window
    assert all(read[m["name"]](dataclasses.replace(run, trace=None)) is None
               for m in cell.per_layer)
    idle = dataclasses.replace(run, counts=dict(run.counts,
                                                prefill_tokens=0))
    assert read["scan_kernel_roofline"](idle) is None


def test_serve_work_counts():
    from bench import work

    s = {"n_layers": 24, "d_model": 2048, "n_heads": 32, "head_k": 64,
         "head_v": 64, "d_ff": 7168, "vocab": 65536, "shift_lora": 32,
         "decay_lora": 64}
    # per layer: time-mix matrices 21,889,024 weights (token shift's
    # 2 x 5 x 2048 x 32, decay's 2 x 2048 x 64, r k v g o 5 x 2048^2);
    # channel mix 33,554,432 (2 x 2048 x 7168 + 2048^2)
    body = 2 * 24 * (21_889_024 + 33_554_432)
    wkv = 4 * 24 * 32 * 64 * 64
    assert work.rwkv6_token_flops(s, logits=False) == body + wkv
    assert work.rwkv6_token_flops(s, logits=True) == (body + wkv
                                                       + 2 * 2048 * 65536)
    assert work.wkv_token_bytes(s, 4) == 24 * 4 * 5 * 2048


def test_request_cycle_holds_both_distributions():
    """The chat cycle: each length's 16 quantiles once, the prompts in
    their buckets, the means near the source's, the same for every run."""
    from bench.drivers import lm_serve

    traffic = harness.resolve(CELL).traffic
    cycle = lm_serve.request_cycle(traffic)
    assert cycle == lm_serve.request_cycle(dict(traffic))
    prompts, answers = zip(*cycle)
    assert len(cycle) == traffic["cycle"] == 16
    quantiles = lm_serve.lognormal_quantiles(
        traffic["answer"]["mean"], traffic["answer"]["sigma"], 16)
    assert sorted(answers) == [round(x) for x in quantiles]
    assert set(prompts) <= set(traffic["prompt"]["buckets"])
    assert np.mean(answers) == pytest.approx(214.5, rel=0.05)
    assert np.mean(prompts) == pytest.approx(69.5, rel=0.05)
    # slots turn over mid-batch: no two neighbours end together
    assert len(set(answers)) == 16
    assert max(p + a for p, a in cycle) < traffic["cache_len"]
    # the first 8 slots' answers end at different steps, short and long
    assert min(answers[:8]) < 214 < max(answers[:8])
