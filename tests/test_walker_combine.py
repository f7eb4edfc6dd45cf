"""The walker's concat combine merges shards by row ownership (DESIGN.md §11).

``kernels.dag_walk.dag_walk_sharded`` combines each concat stage's
per-shard outputs from the rows each shard's table owns, counted once per
keyed table. With one shard that owns every row, the value is the shard's
host copy as the walker wrote it; otherwise a zeroed buffer takes each
shard's owned rows in ascending shard order. Both must equal, bit for bit
and in dtype, the per-slot copy loop the combine used to run
(``_loop_combine``, kept here as the reference): over ``build_dag_tables``
tables of one to four shards and several techniques, and over hand-made
tables with unowned rows and empty slots. The walker is replaced by
per-shard outputs that hold noise in every row, so a row taken from the
wrong shard or left unzeroed shows. The recommendation
pipeline (one shard: its barrier edge refuses two) and connected
components at one and two shards then run through ``run_device_dag``
against their references, and the combine counters
(``walker_combine_stats``) count what each took.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from test_cc_device import _lowering as _cc_lowering
from test_cc_device import _run as _cc_run
from test_cc_device import _start as _cc_start

from repro.core import PipelineDAG, Stage, StageDep, build_dag_tables
from repro.kernels import dag_walk as dw
from repro.kernels import ref
from repro.vee.apps import (recommendation_device_lowering,
                            recommendation_oracle, run_device_dag)

TILE = 8
N_ROWS = 96


def _loop_combine(stages, tables, host):
    """The per-slot combine ``dag_walk_sharded`` ran before the ownership
    combine: a zeroed buffer per concat stage, each owned slot copied."""
    combined = {}
    for k, s in enumerate(stages):
        if s.combine == "sum":
            acc = host[0][s.name]
            for h in host[1:]:
                acc = acc + h[s.name]
            combined[s.name] = acc
            continue
        buf = np.zeros(tuple(s.out_shape), host[0][s.name].dtype)
        for sh in range(len(tables)):
            out = host[sh][s.name]
            for sid, start, size in tables[sh]:
                if sid == k and size > 0:
                    buf[start:start + size] = out[start:start + size]
        combined[s.name] = buf
    return combined


def _no_body(ctx, ins, out):
    raise AssertionError("the walker is replaced in these tests")


def _stages(dtype, n_rows=N_ROWS, reads=(("a", "rows"),)):
    """Two concat stages, the second reading the first's rows (on a built
    table; a hand-made one need not order them), and a sum."""
    return [
        dw.WalkStage("a", n_rows, (n_rows, 3), dtype, "concat", _no_body),
        dw.WalkStage("b", n_rows, (1, 3), dtype, "sum", _no_body),
        dw.WalkStage("c", n_rows, (n_rows, 1), dtype, "concat", _no_body,
                     reads=reads),
    ]


def _dag():
    """The stages' host DAG in tiles, as a lowering's DAG counts them."""
    def op(inputs, s, z):
        return np.zeros(z)

    units = N_ROWS // TILE
    return PipelineDAG([
        Stage("a", units, op, combine="concat"),
        Stage("b", units, op, combine="sum"),
        Stage("c", units, op, combine="concat",
              deps=(StageDep("a", "elementwise"),)),
    ])


def _noise_walker(monkeypatch, dtype, seed):
    """Replace ``dag_walk`` with per-shard outputs of noise in every row;
    returns the list the host copies are appended to, shard by shard."""
    rng = np.random.default_rng(seed)
    outs = []

    def walk(stages, operands, values, table, tile, **kwargs):
        out = {s.name: (rng.standard_normal(s.out_shape) * 100).astype(dtype)
               for s in stages}
        outs.append(out)
        return out

    monkeypatch.setattr(dw, "dag_walk", walk)
    return outs


def _combine(monkeypatch, stages, tables, dtype, seed=0, table_key=None):
    """``dag_walk_sharded``'s values, the walker's outputs, the counters' delta."""
    outs = _noise_walker(monkeypatch, dtype, seed)
    before = dw.walker_combine_stats()
    got = dw.dag_walk_sharded(stages, [], {}, tables, TILE, table_key=table_key)
    after = dw.walker_combine_stats()
    return got, outs, {k: after[k] - before[k] for k in after}


def _assert_same(got, want):
    assert got.keys() == want.keys()
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        assert got[name].shape == want[name].shape, name
        assert np.array_equal(got[name], want[name]), name


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("technique", ["STATIC", "SS", "GSS", "FAC2", "MFSC"])
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_combine_equals_the_slot_loop_on_built_tables(monkeypatch, n_shards,
                                                      technique, dtype):
    stages = _stages(dtype)
    ddt = build_dag_tables(_dag(), 1, technique, n_shards=n_shards,
                           n_workers=4)
    rows = ddt.tables.copy()
    rows[:, :, 1:] *= TILE  # tile units -> row space, as run_device_dag does
    got, outs, delta = _combine(monkeypatch, stages, rows, dtype)
    _assert_same(got, _loop_combine(stages, rows, outs))
    if n_shards == 1:  # one table owns every row: the walker's rows as they are
        assert got["a"] is outs[0]["a"] and got["c"] is outs[0]["c"]
        assert delta == {"taken": 2, "merged": 0}
    else:
        assert delta == {"taken": 0, "merged": 2}


# (stage, start, size) rows; stage 1 is the sum. Rows 16-24 and 72-96 of
# stage 0 and rows 0-8 of stage 2 are owned by no slot, and slots of size
# 0 point at rows nothing else owns.
_GAPS = np.array([
    [0, 0, 16], [2, 8, 8], [1, 0, 8], [0, 24, 8], [0, 32, 40],
    [0, 80, 0], [2, 16, 80], [1, 8, 88], [2, 0, 0], [0, 72, 0],
], np.int32)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("table_key", [None, ("gaps",)])
def test_unowned_rows_stay_zero(monkeypatch, dtype, table_key):
    stages = _stages(dtype, reads=())
    got, outs, delta = _combine(monkeypatch, stages, _GAPS[None], dtype,
                                seed=1, table_key=table_key)
    _assert_same(got, _loop_combine(stages, _GAPS[None], outs))
    a, c = got["a"], got["c"]
    assert not a[16:24].any() and not a[72:].any() and not c[:8].any()
    assert np.array_equal(a[24:72], outs[0]["a"][24:72])
    assert delta == {"taken": 0, "merged": 2}


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_shards_with_gaps_and_overlaps_merge_as_the_loop(monkeypatch, dtype):
    """Shard 1 owns rows shard 0 owns too (the later shard wins) and leaves
    rows no shard owns; shard 2 holds only empty slots."""
    stages = _stages(dtype, reads=())
    tables = np.stack([
        _GAPS,
        np.array([[0, 8, 16], [2, 40, 8], [1, 0, 96], [0, 88, 8]]
                 + [[0, 0, 0]] * 6, np.int32),
        np.array([[0, 0, 0], [2, 0, 0]] * 5, np.int32),
    ])
    got, outs, delta = _combine(monkeypatch, stages, tables, dtype, seed=2)
    _assert_same(got, _loop_combine(stages, tables, outs))
    assert np.array_equal(got["a"][8:24], outs[1]["a"][8:24])
    assert not got["a"][72:88].any()
    assert delta == {"taken": 0, "merged": 2}


def test_slots_past_the_output_copy_what_the_rows_hold(monkeypatch):
    """A slot that runs past the output's last row copies up to it, as the
    loop's slice did; one past the end copies nothing."""
    stages = _stages(np.float32, n_rows=40, reads=())
    table = np.array([[[0, 0, 32], [0, 32, 16], [2, 0, 40], [0, 48, 8]]],
                     np.int32)
    got, outs, delta = _combine(monkeypatch, stages, table, np.float32)
    _assert_same(got, _loop_combine(stages, table, outs))
    assert got["a"] is outs[0]["a"] and delta == {"taken": 2, "merged": 0}


def test_row_spans_merge_overlapping_and_touching_slots():
    table = np.array([[0, 32, 8], [1, 0, 4], [0, 0, 8], [0, 8, 8],
                      [0, 4, 2], [0, 40, 0], [0, 48, 8]], np.int32)
    spans = dw._row_spans(table, 3)
    assert [s.tolist() for s in spans[0]] == [[0, 32, 48], [16, 40, 56]]
    assert [s.tolist() for s in spans[1]] == [[0], [4]]
    assert [s.tolist() for s in spans[2]] == [[], []]


def test_recommendation_through_the_combine_equals_the_oracle():
    """The recommendation pipeline's two concat stages are taken as the
    walker wrote them; the top items equal the float64 oracle's (a tie may
    differ only within the regret bound). Its barrier edge keeps it on one
    shard: a two-shard table is refused before any walk or combine."""
    from repro.vee.apps import recommendation_device, recommendation_regret

    before = dw.walker_combine_stats()
    items, vals, _ = recommendation_device(256, 32, tile=32,
                                           stage_techniques="MFSC")
    after = dw.walker_combine_stats()
    assert {k: after[k] - before[k] for k in after} == {"taken": 2,
                                                        "merged": 0}
    want = recommendation_oracle(256, 32, seed=0)
    assert recommendation_regret(items, 256, 32, seed=0).max() <= 1e-6
    assert (items == want).mean() >= 0.99
    low = recommendation_device_lowering(256, 32, tile=32, seed=0)
    R = np.asarray(low.values["R"], dtype=np.float64)
    np.testing.assert_allclose(vals["user_bias"].reshape(-1), R.mean(axis=1),
                               rtol=1e-5)
    with pytest.raises(ValueError, match="barrier edges need n_shards=1"):
        run_device_dag(low, "MFSC", n_shards=2, n_workers=4)
    assert dw.walker_combine_stats() == after


@pytest.mark.parametrize("n_shards", [1, 2])
def test_cc_rounds_through_the_combine_equal_the_reference(n_shards):
    """Each CC round's labels (one concat stage) are taken as the walker
    wrote them at one shard and merged at two, once per round; the loop's
    labels and rounds equal the reference's."""
    low = _cc_lowering("scale8", 4)
    before = dw.walker_combine_stats()
    labels, launches = _cc_run(low, n_shards)
    after = dw.walker_combine_stats()
    G, c = _cc_start(low)
    want, rounds = ref.connected_components_ref(jnp.asarray(G), c)
    assert np.array_equal(labels, np.asarray(want).astype(np.int64))
    assert launches == rounds * n_shards
    key = "taken" if n_shards == 1 else "merged"
    assert {k: after[k] - before[k] for k in after} == {
        "taken": 0, "merged": 0, key: rounds}
