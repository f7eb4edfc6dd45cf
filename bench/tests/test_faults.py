"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole run of a cell at its tiny size (``--rehearse``
skips the look for a chip) with the program's walker wrapped to plant one
fault, and sees ``correct`` false: an answer altered where the walk
produces it, and half of the rows left out with the sums scaled up to
stand for the whole. A sound run of the same cell comes out correct. The
faults that these cells cannot have (a training step that returns its
state, an exchange between chips) have no test.
"""

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
