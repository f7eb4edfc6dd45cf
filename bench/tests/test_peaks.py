"""The table of peaks and each configuration's count of its least work."""

import json
from pathlib import Path

import pytest

from bench import peaks
from bench.pipelines import linreg, recommend

BENCH = Path(__file__).resolve().parents[1]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_higgs_linreg_counts():
    cfg = config("higgs-linreg")
    work = linreg.work(cfg, cfg["rows"], traffic("solo"))
    operand = 30 * 10_993_664 * 4  # [X, 1, y]^T in float32
    assert operand == 1_319_239_680  # 1,319.2 MB
    assert work["bytes"] == operand + 29 * 4  # and beta written once
    assert work["flops"] == 2 * 29 * 30 * 10_993_664 == 19_128_975_360  # 19.1 GFLOP
    least, bound = peaks.least_seconds(work["flops"], work["bytes"], "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(1.6108e-3, rel=1e-4)


def test_movielens_10m_counts():
    cfg = config("movielens-10m")
    work = recommend.work(cfg, cfg["rows"], traffic("solo"))
    ratings = 71_552 * 10_681 * 4
    assert ratings == 3_056_987_648  # 3,057.0 MB
    assert work["bytes"] == ratings + 71_552 * 4  # and one item per user
    least, bound = peaks.least_seconds(work["flops"], work["bytes"], "TPU v5 lite")
    assert bound == "memory" and least == pytest.approx(3.7329e-3, rel=1e-4)


def test_tenants_split_the_work():
    cfg = config("higgs-linreg")
    eighth = linreg.work(cfg, cfg["rows"] // 8, traffic("tenants8"))
    assert eighth["flops"] * 8 == linreg.work(cfg, cfg["rows"], traffic("solo"))["flops"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "TPU v4")
