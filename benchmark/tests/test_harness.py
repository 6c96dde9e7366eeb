"""The harness is driven by data, and a rehearsal is no measurement."""

import json
import os
import shutil
import subprocess
import sys

import harness
from helpers import BENCH, rehearse

REPO = BENCH.parent


def _run(cwd, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          cwd=cwd, env=e, capture_output=True, text=True,
                          timeout=300)


def test_rehearsal_names_the_cpu_and_is_refused_as_a_measurement(capsys):
    rc, line = rehearse(capsys, "array_gemm", trace=1)
    assert rc == harness.EXIT_REHEARSAL != 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}           # no device metric from a CPU
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert list(line)[-1] == "rehearsal"


def test_no_chip_means_no_result():
    p = _run(REPO, "--workload", "array_gemm", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_tiny_sizes_are_for_rehearsals_only():
    p = _run(REPO, "--workload", "array_gemm", "--seed", "1", "--seconds",
             "1", "--trace", "0", "--size", "tiny")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path, "--workload", "array_gemm", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--platform", "cpu",
             "--size", "tiny")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_cell_config_and_metric_are_found_with_no_edit(tmp_path):
    """A later PR adds a cell, a configuration, a traffic mix and a
    per-layer metric as new files and entries; no file that is there
    changes."""
    root = tmp_path / "repo"
    root.mkdir()
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "distributedarrays_tpu", root / "distributedarrays_tpu")
    for f in ("AUTOTUNE_SEED.json",):
        shutil.copy(REPO / f, root / f)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    cfg = json.loads((b / "configs" / "darray_dense_f32.json").read_text())
    cfg["deployment"] = "a second deployment, added as a file"
    (b / "configs" / "darray_other.json").write_text(json.dumps(cfg))
    traffic = json.loads((b / "traffic" / "gemm.json").read_text())
    traffic["tiny"] = {"N": 128, "block_rows": 64}
    (b / "traffic" / "gemm_small.json").write_text(json.dumps(traffic))
    shutil.copy(b / "limits" / "array_gemm.json",
                b / "limits" / "other_gemm.json")
    (b / "layer_metrics" / "steps_counted.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "darray_other", "source": "https://example.org/other",
        "file": "benchmark/configs/darray_other.json", "reduced": [],
        "why": "added by the test"})
    bench["workloads"].append({
        "name": "other_gemm", "config": "darray_other",
        "traffic": "gemm_small", "chips": 1, "why": "added by the test"})
    bench["per_layer"].append({
        "name": "steps_counted", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Entry points",
        "moves": "step_ms", "workloads": ["other_gemm"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run(root, "--workload", "other_gemm", "--seed", "9", "--seconds",
             "0.2", "--trace", "1", "--platform", "cpu", "--size", "tiny")
    assert p.returncode == harness.EXIT_REHEARSAL, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["rehearsal"]["cost"]["flops"] == 2 * 128 ** 3 + 2 * 128 ** 2
    assert line["rehearsal"]["readers_found"][-1] == "steps_counted"
    # the new reader was found by its name and asked for this cell only
    sys.path.insert(0, str(b))
    try:
        assert [m["name"] for m in harness.metrics_for(
            bench, "per_layer", "other_gemm")][-1] == "steps_counted"
        assert "steps_counted" not in [m["name"] for m in harness.metrics_for(
            bench, "per_layer", "array_gemm")]
    finally:
        sys.path.remove(str(b))
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_every_named_file_exists():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["source"] \
            == c["source"]
    for w in bench["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        assert (BENCH / "drivers" / f"{t['driver']}.py").is_file()
        lim = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        assert set(lim) >= {"limits", "tiny_limits"}
    for m in bench["per_layer"]:
        assert (BENCH / "layer_metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in [e["name"] for e in bench["end_to_end"]]


def test_percentile_is_nearest_rank_over_all_samples():
    vals = list(range(1, 101))
    assert harness.percentile(vals, 95) == 95
    assert harness.percentile([5.0], 95) == 5.0
    assert harness.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 100], 95) == 100
