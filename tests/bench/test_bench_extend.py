"""A configuration, a traffic mix and a metric added as new files, with
entries added to BENCHMARK.json, are picked up without an edit to any
existing file of the benchmark."""
import hashlib
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_make_a_new_cell(tmp_path, run_small):
    from bench import harness

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)

    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "jacobi2d-16k.json").read_text())
    cfg.update(name="jacobi2d-small", domain=[256, 256])
    (bench / "configs" / "jacobi2d-small.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "t2x2.json").write_text(json.dumps({
        "name": "t2x2", "steps_per_call": 2, "calls_per_dispatch": 2,
        "ahead_s": 1, "why": "two calls of two fused steps a dispatch"}))
    (bench / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return run.counts[\"calls\"] / run.window_s\n")

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "jacobi2d-small", "source": "x",
                            "file": "bench/configs/jacobi2d-small.json",
                            "reduced": ["domain"], "why": "test"})
    spec["workloads"].append({"name": "jacobi2d-small.t2x2",
                              "config": "jacobi2d-small", "traffic": "t2x2",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["jacobi2d-small.t2x2"]})
    gcells = next(m for m in spec["end_to_end"]
                  if m["name"] == "gcells_per_s")
    gcells["workloads"].append("jacobi2d-small.t2x2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before, "an existing file changed"

    cell = harness.resolve("jacobi2d-small.t2x2", tmp_path)
    assert cell.traffic["calls_per_dispatch"] == 2
    line = run_small(cell)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"gcells_per_s", "setup_s",
                                    "calls_per_s"}
    assert line["attempted"] % 2 == 0 and line["attempted"] > 0
    # the cells already there do not report the new metric
    old = harness.resolve("jacobi2d-16k.t1", tmp_path)
    assert "calls_per_s" not in {m["name"] for m in old.end_to_end}


TOY_DRIVER = '''
class Toy:
    def __init__(self, config, traffic, **_):
        self.per_call = traffic["tokens_per_call"]
        self.calls = 0

    def setup(self):
        pass

    def dispatch(self):
        self.calls += 1

    def throttle(self):
        pass

    def drain(self):
        pass

    def completed(self):
        return {"calls": self.calls, "tokens": self.per_call * self.calls}

    def work_per_call(self):
        return {}

    def release(self):
        pass

    def check(self):
        return [{"name": "answers", "value": 0, "limit": 0, "ok": True}]


def make(config, traffic, **kw):
    return Toy(config, traffic, **kw)
'''


def test_a_cell_that_counts_tokens(tmp_path, run_small):
    """A driver that counts tokens, its configuration and its mix, added as
    files, report tokens_per_s through the entries alone; the stencil
    cells keep what they report."""
    from bench import harness

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    names = {w: ([m["name"] for m in c.end_to_end],
                 [m["name"] for m in c.per_layer])
             for w in ("jacobi2d-16k.t1", "jacobi2d-48k-x4.t4")
             for c in [harness.resolve(w, tmp_path)]}

    bench = tmp_path / "bench"
    (bench / "drivers" / "toy_tokens.py").write_text(TOY_DRIVER)
    (bench / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "driver": "toy_tokens", "kernel": "none"}))
    (bench / "traffic" / "three.json").write_text(json.dumps(
        {"name": "three", "tokens_per_call": 3, "why": "test"}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "x",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "toy.three", "config": "toy",
                              "traffic": "three", "chips": 1, "why": "test"})
    tokens = next(m for m in spec["end_to_end"]
                  if m["name"] == "tokens_per_s")
    tokens["workloads"].append("toy.three")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before, "an existing file changed"

    cell = harness.resolve("toy.three", tmp_path)
    assert [m["name"] for m in cell.end_to_end] == ["tokens_per_s",
                                                    "setup_s"]
    assert "sweep_mfu" not in {m["name"] for m in cell.per_layer}
    line = run_small(cell)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    calls = line["attempted"]
    assert calls > 0 and line["metrics"]["tokens_per_s"]["value"] > 3 * calls
    for w, (e2e, per_layer) in names.items():
        c = harness.resolve(w, tmp_path)
        assert [m["name"] for m in c.end_to_end] == e2e
        assert [m["name"] for m in c.per_layer] == per_layer
