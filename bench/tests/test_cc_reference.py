"""The connected-components reference against the program's entry, and what it must refuse.

At the configuration's rehearse size (scale 9, n = 512), on the CPU with
Pallas kernels interpreted: the union-find reference agrees with what
``vee.apps.run_device_dag`` returns for a ``cc_device_lowering``, and
Listing 1's round count by numpy propagation equals the program's
launches; the control (labels stored in bfloat16) and a bfloat16 copy of
the exact labels fall outside ``label_mismatch``; the reference's copy of
the Kronecker draw gives the program's graph. The configuration's
``graph_seed`` is varied here to try several graphs; the run's seed does
not change the graph.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as harness
from bench.pipelines import cc
from bench.reference import cc as cc_ref

BENCH = Path(__file__).resolve().parents[1]
CELL = "cc-g500-s14.solo"
SEEDS = (1, 2, 3)


def tiny(graph_seed=None):
    spec = harness.load_cell(CELL, rehearse=True)
    cfg = spec["config"]
    if graph_seed is not None:
        cfg = dict(cfg, graph_seed=graph_seed)
    return cfg, spec["limits"]


def within(numbers, lim):
    return all(numbers[n] <= lim[n] for n in lim)


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_the_program(seed):
    from repro.core import loop_counts
    from repro.vee.apps import run_device_dag

    cfg, lim = tiny(seed)
    low = cc.build(cfg, cfg["rows"], seed)
    before = loop_counts()["launches"]
    values, _ = run_device_dag(low, cfg["techniques"])
    launches = loop_counts()["launches"] - before
    numbers = cc.Checker(cfg, cfg["rows"], seed)(cc.answer(low, values))
    assert numbers == {"label_mismatch": 0.0} and within(numbers, lim)
    src, dst = cc_ref.kronecker(cfg["scale"], cfg["edge_factor"], seed)
    assert cc_ref.rounds(cfg["rows"], src, dst) == launches


@pytest.mark.parametrize("seed", SEEDS)
def test_control_falls_outside(seed):
    cfg, lim = tiny(seed)
    numbers = cc.Checker(cfg, cfg["rows"], seed)(cc.control(cfg, cfg["rows"], seed))
    assert not within(numbers, lim), numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_labels_fall_outside(seed):
    cfg, lim = tiny(seed)
    checker = cc.Checker(cfg, cfg["rows"], seed)
    labels = np.asarray(jnp.asarray(checker.want, jnp.bfloat16), np.float64)
    assert not within(checker(labels.astype(np.int64)), lim)


def test_kronecker_copy_draws_the_programs_graph():
    from repro.vee.sparse import CSRMatrix, rmat_graph

    cfg = json.loads((BENCH / "configs" / "cc-g500-s14.json").read_text())
    scale, ef, seed = 10, cfg["edge_factor"], 3140000001
    src, dst = cc_ref.kronecker(scale, ef, seed)
    want = rmat_graph(scale, ef, seed=seed, relabel=True)
    got = CSRMatrix.from_edges(src, dst, 1 << scale)
    assert np.array_equal(got.indptr, want.indptr)
    assert np.array_equal(got.indices, want.indices)
    assert len(src) > ef * (1 << scale)  # both directions, self-loops dropped


def test_run_seed_keeps_the_graph():
    cfg, _ = tiny()
    rows = cfg["rows"]
    src, dst = cc_ref.kronecker(cfg["scale"], cfg["edge_factor"], cfg["graph_seed"])
    want = cc_ref.components(rows, src, dst)
    for seed in (7, 3150000041):
        assert np.array_equal(cc.Checker(cfg, rows, seed).want, want)
        assert np.array_equal(cc.control(cfg, rows, seed), cc.control(cfg, rows, 7))
