"""``BENCHMARK.json`` keeps the form every later check relies on.

Its keys and their limits, and that everything a cell names is there:
its configuration's file, its traffic mix, its limits and a reader for
each metric it reports.
"""

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
RAW = (ROOT / "BENCHMARK.json").read_bytes()
SPEC = json.loads(RAW)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def under_paths(f):
    return any(f.startswith(p.rstrip("/") + "/") for p in SPEC["paths"])


def test_top_level():
    assert len(RAW) <= 64 * 1024
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(text_ok(w) for w in cmd)
    assert under_paths(cmd[1]) and (ROOT / cmd[1]).is_file()
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in SPEC["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and text_ok(c["source"]) and text_ok(c["why"])
        assert c["name"] in used and under_paths(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / "bench/pipelines" / f"{cfg['pipeline']}.py").is_file()


def test_workloads():
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and text_ok(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "bench/traffic" / f"{w['traffic']}.json").is_file()
        limits = json.loads((ROOT / "bench/limits" / f"{w['name']}.json").read_text())
        assert limits and all(v > 0 for v in limits.values())


def test_metrics():
    e2e, layer = SPEC["end_to_end"], SPEC["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in e2e)
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("host_clock", "device_trace", "program_span", "program_counter")
        assert text_ok(m["layer"]) and m["moves"] in {e["name"] for e in e2e}
        moved = next(e for e in e2e if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for cell in cells:
        reported = [m["name"] for m in e2e if cell in m.get("workloads", [cell])]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", [cell]) for m in layer)
