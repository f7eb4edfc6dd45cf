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


def test_a_new_cell_needs_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}

    cfg = json.loads((ROOT / "bench/configs/higgs-linreg.json").read_text())
    cfg.update(name="linreg-tiny", rows=4096, tile=512, rehearse={})
    (tmp_path / "bench/configs/linreg-tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/tenants2.json").write_text(json.dumps(
        {"tenants": 2, "split_rows": True, "warmup_jobs": 1,
         "why": "two tenants of half the rows each"}))
    (tmp_path / "bench/limits/linreg-tiny.tenants2.json").write_text(
        (ROOT / "bench/limits/higgs-linreg.tenants8.json").read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "linreg-tiny", "source": cfg["source"],
                            "file": "bench/configs/linreg-tiny.json", "reduced": ["rows"],
                            "why": "a test's cell"})
    spec["workloads"].append({"name": "linreg-tiny.tenants2", "config": "linreg-tiny",
                              "traffic": "tenants2", "chips": 1, "why": "a test's cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    p = bench(tmp_path, "--workload", "linreg-tiny.tenants2", "--seed", "5",
              "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    result = last_json(p.stdout)
    assert result["correct"] is True and result["attempted"] % 2 == 0
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file the benchmark had was edited
