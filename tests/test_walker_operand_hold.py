"""The walker holds an inner-indexed operand's block at steps that do not read it.

``kernels.dag_walk.walk_call`` gives an operand with an ``inner`` axis that
one stage alone reads an index map that stays on that stage's latest
reading block (its current start, its last inner block) at every grid step
that does not read the operand: another stage's slot, an empty slot, or an
inner index at or past the stage's ``inner``. On a TPU an unchanged block
index costs no copy, so a connected-components round reads the adjacency
once. Here the index maps ``walk_call`` builds are replayed over real walker
tables on the CPU, and a block fetch counted wherever the block index moves
(and at the first step). Other operands keep their maps: the fetch counts of
the linear-regression, recommendation and eight-member merged lowerings are
those of the maps before the hold, hard-coded.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.admission import BATCH_SEP
from repro.core.device_schedule import build_dag_tables_cached
from repro.kernels import dag_walk as dw
from repro.vee.apps import (cc_device_lowering, linreg_device_lowering,
                            merge_device_lowerings,
                            recommendation_device_lowering)

CC_TECHNIQUES = {"propagate": "MFSC", "changed": "STATIC"}
LINREG_TECHNIQUES = {"moments": "GSS", "syrk_gemv": "FAC2"}


def _grid_and_maps(monkeypatch, stages, operands, shapes, n_slots, tile):
    """The grid and the operands' index maps as ``walk_call`` builds them."""
    seen = {}

    def capture(kernel, *, grid_spec, **kwargs):
        seen["grid_spec"] = grid_spec

    monkeypatch.setattr(dw, "pallas_call", capture)
    dw.walk_call(stages, operands, shapes, n_slots, tile)
    spec = seen["grid_spec"]
    return spec.grid, {op.name: s.index_map for op, s in zip(operands, spec.in_specs)}


def _block_indices(imap, grid, flat_table):
    """``(steps, rank)`` block indices of one map at every grid step, in order."""
    ii, jj = (a.reshape(-1) for a in np.meshgrid(np.arange(grid[0]),
                                                 np.arange(grid[1]),
                                                 indexing="ij"))
    tbl = jnp.asarray(flat_table)
    out = jax.vmap(lambda i, j: tuple(jnp.asarray(x, jnp.int32)
                                      for x in imap(i, j, tbl)))(ii, jj)
    return np.stack([np.broadcast_to(np.asarray(x), ii.shape) for x in out], axis=1)


def _fetches(blocks):
    """Block copies a pipeline makes: at the first step and wherever the index moves."""
    return 1 + int(np.any(blocks[1:] != blocks[:-1], axis=1).sum())


def _replay(monkeypatch, low, techniques):
    """``({operand: fetches per launch}, grid, walker table, index maps)``
    for ``low``'s one-shard table as ``run_device_dag`` builds it."""
    ddt = build_dag_tables_cached(low.dag, 1, techniques)
    rows = ddt.tables[0].copy()
    rows[:, 1:] *= low.tile
    table = dw._walk_table(rows, low.stages)
    shapes = {op.name: tuple(low.values[op.name].shape) for op in low.operands}
    grid, maps = _grid_and_maps(monkeypatch, low.stages, low.operands, shapes,
                                len(rows), low.tile)
    flat = table.reshape(-1)
    return ({name: _fetches(_block_indices(m, grid, flat)) for name, m in maps.items()},
            grid, table, maps)


def test_cc_round_reads_the_adjacency_once(monkeypatch):
    # scale 10, tiles 16 x 64: the 64 row tiles x 16 column tiles of scale 14
    low = cc_device_lowering(10, 16, tile=16, tile_c=64)
    fetches, grid, table, maps = _replay(monkeypatch, low, CC_TECHNIQUES)
    assert grid == (128, 16)
    # every propagate step reads a new block of G and of c_col, and only those
    reading = int(((table[:, 0] == 0) & (table[:, 2] > 0)).sum()) * 16
    assert reading == 1024
    assert fetches == {"G": 1024, "c_col": 1024, "c_row": 64}
    # the blocks a propagate step reads are the blocks before the hold
    blocks = _block_indices(maps["G"], grid, table.reshape(-1))
    for i in range(grid[0]):
        sid, start, size = table[i, :3]
        if sid == 0 and size > 0:
            want = [(start // 16, j) for j in range(16)]
            assert [tuple(b) for b in blocks[i * 16:(i + 1) * 16]] == want, i


@pytest.mark.parametrize("case", ["linreg", "recommendation", "linreg_tenants8"])
def test_other_lowerings_keep_their_fetch_counts(monkeypatch, case):
    """No operand of these lowerings has an inner axis: the maps are unchanged.

    Sizes are the benchmark configurations' rehearse sizes with their
    techniques; the counts are those of the maps before the hold."""
    if case == "linreg":
        low = linreg_device_lowering(8192, 29, tile=512, seed=1)
        techniques = LINREG_TECHNIQUES
        want = {"W": 32}
    elif case == "recommendation":
        low = recommendation_device_lowering(512, 331, tile=64, density=0.05,
                                             seed=1)
        techniques = "MFSC"
        want = {"R": 16}
    else:
        low = merge_device_lowerings([
            linreg_device_lowering(1024, 29, tile=512, seed=8 + k)
            for k in range(8)])
        techniques = {f"{stage}{BATCH_SEP}{j}": t
                      for stage, t in LINREG_TECHNIQUES.items() for j in range(8)}
        want = {f"W{BATCH_SEP}{j}": 4 for j in range(8)}
    assert not any("inner" in op.index for op in low.operands)
    fetches, _, _, _ = _replay(monkeypatch, low, techniques)
    assert fetches == want


# a hand-built two-stage walk: ``rowsum`` reads X over two column tiles, and
# ``total`` (three inner steps, so ``rowsum`` has a step past its inner) sums Y
TILE, N, COLS = 8, 32, 256


def _rowsum_body(ctx, ins, out):
    from jax.experimental import pallas as pl

    @pl.when(ctx.inner == 0)
    def _():
        out[...] = jnp.zeros_like(out)

    out[...] += ins["X"][...].sum(axis=1, keepdims=True)


def _total_body(ctx, ins, out):
    from jax.experimental import pallas as pl

    @pl.when(ctx.inner == 0)
    def _():
        out[...] += ins["Y"][...].sum(keepdims=True)


def _two_stage():
    stages = [
        dw.WalkStage("rowsum", N, (N, 1), jnp.float32, "concat", _rowsum_body,
                     operands=("X",), inner=2),
        dw.WalkStage("total", N, (1, 128), jnp.float32, "sum", _total_body,
                     operands=("Y",), inner=3),
    ]
    operands = [dw.WalkOperand("X", (TILE, 128), ("row", "inner")),
                dw.WalkOperand("Y", (TILE, 128), ("row", "zero"))]
    rng = np.random.default_rng(0)
    values = {"X": jnp.asarray(rng.uniform(size=(N, COLS)).astype(np.float32)),
              "Y": jnp.asarray(rng.uniform(size=(N, 128)).astype(np.float32))}
    # (stage, start, size) in rows: each ``total`` slot starts on another row
    # tile than ``rowsum``'s latest, and the last slot is empty
    table = np.array([(0, 0, 8), (1, 16, 8), (0, 8, 8), (1, 0, 8), (0, 16, 8),
                      (0, 24, 8), (1, 8, 8), (1, 24, 8), (0, 0, 0)], np.int32)
    return stages, operands, values, table


def test_held_block_follows_the_readers_current_start(monkeypatch):
    stages, operands, values, table = _two_stage()
    shapes = {k: tuple(v.shape) for k, v in values.items()}
    grid, maps = _grid_and_maps(monkeypatch, stages, operands, shapes,
                                len(table), TILE)
    assert grid == (9, 3)
    flat = dw._walk_table(table, stages).reshape(-1)
    x = [tuple(b) for b in _block_indices(maps["X"], grid, flat)]
    # rowsum's slots read (row tile, 0), (row tile, 1) and hold at j = 2; other
    # steps hold on rowsum's latest row tile, whatever the slot's own start
    want = [(0, 0), (0, 1), (0, 1)] + [(0, 1)] * 3 \
        + [(1, 0), (1, 1), (1, 1)] + [(1, 1)] * 3 \
        + [(2, 0), (2, 1), (2, 1)] + [(3, 0), (3, 1), (3, 1)] + [(3, 1)] * 9
    assert x == want
    assert _fetches(np.array(x)) == 8
    # Y keeps the plain map: each slot's own start, the empty slot's too
    y = [tuple(b) for b in _block_indices(maps["Y"], grid, flat)]
    assert y == [(r, 0) for r in (0, 2, 1, 0, 2, 3, 1, 3, 0) for _ in range(3)]


def test_held_walk_computes_the_stagewise_answer():
    """The walk with held blocks runs on the CPU, in Pallas's TPU interpret
    mode, and equals the one-launch-per-stage walk and numpy; it adds its
    steps to the hold counters."""
    stages, operands, values, table = _two_stage()
    before = dw.walker_hold_stats()
    fused = dw.dag_walk(stages, operands, values, table, TILE)
    after = dw.walker_hold_stats()
    # 9 slots x 3 inner steps of X, read at 2 steps of each of 4 live slots
    assert after["inner_steps"] - before["inner_steps"] == 27
    assert after["held_steps"] - before["held_steps"] == 27 - 8
    stagewise = dw.dag_walk_stagewise(stages, operands, values, table, TILE)
    for k in fused:
        assert np.array_equal(np.asarray(fused[k]), np.asarray(stagewise[k])), k
    X, Y = (np.asarray(values[k], np.float64) for k in ("X", "Y"))
    np.testing.assert_allclose(np.asarray(fused["rowsum"]),
                               X.sum(axis=1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fused["total"])[0, 0], Y.sum(), rtol=1e-5)
