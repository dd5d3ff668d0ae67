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
        "in_flight": 1, "why": "two calls of two fused steps a dispatch"}))
    (bench / "metrics" / "calls_per_s.py").write_text(
        "def read(run):\n    return run.calls / run.window_s\n")

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
