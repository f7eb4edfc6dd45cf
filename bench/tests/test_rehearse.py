"""``bench/run.py`` as the command runs it: the last line, the exits, a new cell.

Each cell is rehearsed at its configuration's tiny size on the CPU
(``--rehearse``: Pallas kernels interpreted, every metric's value null).
Without a TPU, or without the program beside the benchmark, a run exits
non-zero and prints no result. A cell that comes with a new configuration,
a new traffic mix and its limits runs from new files and new entries of
``BENCHMARK.json`` alone.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def bench(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *SPEC["command"][1:], *args], cwd=root,
                          env=env, capture_output=True, text=True, timeout=600)


def last_json(stdout):
    lines = stdout.strip().splitlines()
    return json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None


def metrics_of(cell, trace):
    names = [m["name"] for m in SPEC["per_layer" if trace else "end_to_end"]
             if cell in m.get("workloads", [cell])]
    return sorted(names)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_rehearsal_last_line(cell, trace):
    p = bench(ROOT, "--workload", cell, "--seed", "2147483647", "--seconds", "1",
              "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    result = last_json(p.stdout)
    assert list(result)[:5] == KEYS and list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert sorted(result["metrics"]) == metrics_of(cell, trace)
    assert all(m["value"] is None for m in result["metrics"].values())  # no CPU numbers
    tail = p.stderr.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[1] for line in tail] == list(result["compared"])


def test_no_tpu_exits_without_a_result():
    cell = SPEC["workloads"][0]["name"]
    p = bench(ROOT, "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and last_json(p.stdout) is None
    assert "TPU" in p.stderr


def test_without_the_program_exits_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = SPEC["workloads"][0]["name"]
    p = bench(tmp_path, "--workload", cell, "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse")
    assert p.returncode != 0 and last_json(p.stdout) is None


def walker_cell(tmp_path):
    """A cell of the linreg pipeline: two tenants of a tiny configuration, merged per launch."""
    cfg = json.loads((ROOT / "bench/configs/higgs-linreg.json").read_text())
    cfg.update(name="linreg-tiny", rows=4096, tile=512, rehearse={})
    (tmp_path / "bench/traffic/tenants2.json").write_text(json.dumps(
        {"tenants": 2, "split_rows": True, "warmup_jobs": 1,
         "why": "two tenants of half the rows each"}))
    (tmp_path / "bench/limits/linreg-tiny.tenants2.json").write_text(
        (ROOT / "bench/limits/higgs-linreg.tenants8.json").read_text())
    return cfg, "tenants2", 2


def own_job_cell(tmp_path):
    """A cell whose pipeline brings its own job: four requests a call, one matmul."""
    shutil.copy(ROOT / "bench/tests/matmul_requests.py",
                tmp_path / "bench/pipelines/matmul_requests.py")
    cfg = {"name": "matmul-tiny", "source": "a test's configuration", "reduced": [],
           "pipeline": "matmul_requests", "width": 64}
    (tmp_path / "bench/traffic/requests4.json").write_text(json.dumps(
        {"requests": 4, "arrival_gap_ms": 0.5, "warmup_jobs": 1,
         "why": "four requests a call, arrived half a millisecond apart"}))
    (tmp_path / "bench/limits/matmul-tiny.requests4.json").write_text(
        json.dumps({"rel_err": 1e-4}))
    return cfg, "requests4", 4


@pytest.mark.parametrize("make_cell", [walker_cell, own_job_cell])
def test_a_new_cell_needs_only_new_files(tmp_path, make_cell):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}

    cfg, traffic, owed = make_cell(tmp_path)
    name = cfg["name"]
    cell = f"{name}.{traffic}"
    (tmp_path / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": cfg["source"],
                            "file": f"bench/configs/{name}.json", "reduced": cfg["reduced"],
                            "why": "a test's cell"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": traffic, "chips": 1, "why": "a test's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    p = bench(tmp_path, "--workload", cell, "--seed", "5",
              "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    result = last_json(p.stdout)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % owed == 0
    assert result["window"]["jobs"] + result["window"]["traced_jobs"] == result["attempted"]
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file the benchmark had was edited


def test_program_counters_over_the_window():
    """The window's deltas of the program's exported counters: two concat outputs
    of ``movielens-10m`` a job, each returned as the walker wrote it (one shard)."""
    from bench import run as harness

    result = harness.run(harness.parse_args(
        ["--workload", "movielens-10m.solo", "--seed", "12", "--seconds", "1",
         "--trace", "0", "--rehearse"]))
    window = result["window"]
    assert window["jobs"] > 0 and window["compiles"] == 0
    assert window["counters"]["sched_walker_combine_taken"] == 2 * window["jobs"]
    assert "sched_walker_combine_merged" not in window["counters"]  # a delta of 0
