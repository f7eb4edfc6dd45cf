"""The plain references against the program's entry, and what they must refuse.

At a size a test run can hold, on the CPU (Pallas kernels interpreted):
each float64 reference agrees with what ``vee.apps.run_device_dag`` returns
within the written limits; the same pipeline on a bfloat16 copy of the
inputs, and the control (the reference one precision step below what the
configuration states), fall outside them.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as harness
from bench.pipelines import linreg, recommend
from bench.reference import linreg as linreg_ref
from bench.reference import recommend as recommend_ref

BENCH = Path(__file__).resolve().parents[1]
SEEDS = (1, 2, 3)


def tiny(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(cfg["rehearse"])
    return cfg


def limits(cell):
    return json.loads((BENCH / "limits" / f"{cell}.json").read_text())


def bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)


def within(numbers, lim):
    return all(numbers[n] <= lim[n] for n in lim)


def program_answer(pipeline, cfg, seed):
    from repro.vee.apps import run_device_dag

    low = pipeline.build(cfg, cfg["rows"], seed)
    values, _ = run_device_dag(low, cfg["techniques"])
    return pipeline.answer(low, values)


@pytest.mark.parametrize("seed", SEEDS)
def test_linreg_reference_agrees_with_the_program(seed):
    cfg, lim = tiny("higgs-linreg"), limits("higgs-linreg.solo")
    got = program_answer(linreg, cfg, seed)
    assert within(linreg.Checker(cfg, cfg["rows"], seed)(got), lim)


@pytest.mark.parametrize("seed", SEEDS)
def test_recommend_reference_agrees_with_the_program(seed):
    cfg, lim = tiny("movielens-10m"), limits("movielens-10m.solo")
    got = program_answer(recommend, cfg, seed)
    assert within(recommend.Checker(cfg, cfg["rows"], seed)(got), lim)


@pytest.mark.parametrize("seed", SEEDS)
def test_linreg_bf16_inputs_fall_outside(seed):
    cfg, lim = tiny("higgs-linreg"), limits("higgs-linreg.solo")
    XY = bf16(linreg_ref.data(cfg["rows"], cfg["cols"], seed))
    gram = linreg_ref.normal_equations_of(XY)
    got = {"beta": linreg_ref.solve(gram, cfg["lam"])}
    assert not within(linreg.Checker(cfg, cfg["rows"], seed)(got), lim)


@pytest.mark.parametrize("seed", SEEDS)
def test_recommend_bf16_inputs_fall_outside(seed):
    cfg, lim = tiny("movielens-10m"), limits("movielens-10m.solo")
    R = bf16(recommend_ref.ratings(cfg["rows"], cfg["items"], cfg["density"], seed))
    S = R / (np.sqrt((R ** 2).sum(axis=0)) + 1e-9)
    got = S.argmax(axis=1)
    assert not within(recommend.Checker(cfg, cfg["rows"], seed)(got), lim)


@pytest.mark.parametrize("cell", ["higgs-linreg.solo", "movielens-10m.solo",
                                  "higgs-linreg.tenants8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_falls_outside(cell, seed):
    spec = harness.load_cell(cell, rehearse=True)
    cfg, lim = spec["config"], spec["limits"]
    pipeline = harness.pipeline_of(cfg)
    rows, tenant_seeds = harness.tenants_of(cfg, spec["traffic"], seed)
    s = tenant_seeds[0]
    numbers = pipeline.Checker(cfg, rows, s)(pipeline.control(cfg, rows, s))
    assert not within(numbers, lim), numbers
