"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell at its tiny size (``--rehearse``
skips the look for a chip) with the program's walker wrapped to plant one
fault, and sees ``correct`` false: an answer altered where the walk
produces it, and half of the rows left out with the sums scaled up to
stand for the whole. A sound run of the same cell comes out correct. The
faults that these cells cannot have (a training step that returns its
state, an exchange between chips) have no test.

A pipeline that brings its own job (``matmul_requests``: four answers a
call, one tenant) is held to the same rules answer by answer: one answer
of four altered, an answer a call owes and does not return, one
request's answer given for all four, and a call that raises each fail
what they touch, and no more.
"""

import types
from pathlib import Path

import numpy as np
import pytest

from bench import run as harness

CELLS = ["higgs-linreg.solo", "movielens-10m.solo", "higgs-linreg.tenants8"]


def run_cell(cell, seed=11):
    return harness.run(harness.parse_args(
        ["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", "0",
         "--rehearse"]))


def wrap_walk(monkeypatch, before=None, after=None):
    from repro.kernels import dag_walk

    walk = dag_walk.dag_walk_sharded

    def broken(stages, operands, values, tables, tile, table_key=None):
        if before is not None:
            tables = before(stages, np.array(tables))
        out = walk(stages, operands, values, tables, tile, table_key=table_key)
        return after(stages, out) if after is not None else out

    monkeypatch.setattr(dag_walk, "dag_walk_sharded", broken)


def alter_answer(stages, out):
    """Change one element of the last stage's output: the answer's producer."""
    out = dict(out)
    last = np.array(out[stages[-1].name])
    flat = last.reshape(-1)
    flat[0] = flat[0] + 1 if flat.dtype.kind in "iu" else flat[0] * 1.01 + 1e-3
    out[stages[-1].name] = last
    return out


def half_the_rows(stages, tables):
    """Walk only the first half of every stage's rows; double what sums over them."""
    n_rows = np.array([s.n_rows for s in stages])
    tables[..., 2] = np.where(tables[..., 1] < n_rows[tables[..., 0]] // 2,
                              tables[..., 2], 0)
    return tables


def doubled_sums(stages, out):
    return {s.name: (np.asarray(out[s.name]) * 2 if s.combine == "sum" else out[s.name])
            for s in stages}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = run_cell(cell)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, monkeypatch):
    wrap_walk(monkeypatch, after=alter_answer)
    result = run_cell(cell)
    assert not result["correct"] and result["failed"] > 0
    assert any(c["value"] > c["limit"] for c in result["compared"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_rows_is_not_correct(cell, monkeypatch):
    wrap_walk(monkeypatch, before=half_the_rows, after=doubled_sums)
    result = run_cell(cell)
    assert not result["correct"] and result["failed"] > 0


OWN_JOB = harness.load_module(Path(__file__).parent / "matmul_requests.py")
OWED = 4


def run_own_job(monkeypatch, fault=None, every=False):
    """A whole run of a cell whose pipeline brings its own job; ``fault(out)``
    changes what the first call of the window returns, or every call of the
    windows with ``every``."""
    spec = {"cell": {"name": "matmul-tiny.requests4", "chips": 1},
            "config": {"name": "matmul-tiny", "pipeline": "matmul_requests", "width": 64},
            "traffic": {"requests": OWED, "arrival_gap_ms": 0.5, "warmup_jobs": 1},
            "limits": {"rel_err": 1e-4}, "end_to_end": [], "per_layer": []}

    def make_job(lowerings, cfg, traffic):
        job, calls = OWN_JOB.make_job(lowerings, cfg, traffic), [0]

        def faulty():
            calls[0] += 1
            out = job()
            hit = calls[0] > 1 if every else calls[0] == 2  # call 1 warms up
            return fault(out) if fault is not None and hit else out

        return faulty

    pipeline = types.SimpleNamespace(**{k: getattr(OWN_JOB, k) for k in
                                        ("build", "owed", "Checker", "work")},
                                     make_job=make_job)
    monkeypatch.setattr(harness, "load_cell", lambda name, rehearse=False: spec)
    monkeypatch.setattr(harness, "pipeline_of", lambda cfg: pipeline)
    return run_cell("matmul-tiny.requests4")


def test_own_job_sound_run_is_correct(monkeypatch):
    result = run_own_job(monkeypatch)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] == result["window"]["jobs"] > OWED


def test_own_job_one_altered_answer_of_four_fails_one(monkeypatch):
    def alter_third(out):
        key, answer, t, arrived = out[2]
        out[2] = (key, answer * 1.01, t, arrived)
        return out

    result = run_own_job(monkeypatch, alter_third)
    assert not result["correct"] and result["failed"] == 1
    assert result["compared"]["rel_err"]["value"] > result["compared"]["rel_err"]["limit"]


def first_answer_under_every_key(out):
    """Request 0's answer, sound, given as the answer to every request."""
    return [(key, out[0][1], t, arrived) for key, _, t, arrived in out]


def first_answer_four_times(out):
    """Request 0's answer, sound and under its own key, returned for all four."""
    return [out[0]] * len(out)


@pytest.mark.parametrize("fault, failed", [(first_answer_under_every_key, 3),
                                           (first_answer_four_times, OWED)])
def test_own_job_one_requests_answer_for_all_fails(monkeypatch, fault, failed):
    result = run_own_job(monkeypatch, fault)
    assert not result["correct"] and result["failed"] == failed


def test_own_job_answers_owed_and_not_returned_fail(monkeypatch):
    result = run_own_job(monkeypatch, lambda out: out[:OWED - 1], every=True)
    calls = result["attempted"] // OWED
    assert not result["correct"] and result["failed"] == calls > 0
    assert result["window"]["jobs"] + result["window"]["traced_jobs"] == 3 * calls


def test_own_job_raising_call_fails_all_it_owes(monkeypatch):
    def boom(out):
        raise RuntimeError("planted")

    result = run_own_job(monkeypatch, boom)
    assert not result["correct"] and result["failed"] == OWED
    assert result["attempted"] == result["window"]["jobs"] + OWED
    assert result["window"]["errors"] == ["RuntimeError: planted"]
