"""The walker's compiled call is built once per structure and reused.

``kernels.dag_walk.dag_walk`` keeps one ``jax.jit`` of ``walk_call`` per
(stages, operands, operand shapes and dtypes, slot count, tile) in a
bounded LRU memo. A repeat launch of the same structure makes no compile
request and no lowering; the walker table stays a runtime argument, so a
different schedule with the same slot count reuses the compiled walker
and still computes its own answer. The lowerings build their stage bodies
structurally, so a job of the same shape with new data hits, and merged
§14 batches of the same members compare equal stage by stage, so they
share one entry. Everything here runs on the CPU, in Pallas's TPU
interpret mode.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import compile_counts
from repro.core.device_schedule import build_dag_tables_cached
from repro.core.telemetry import watch_compiles
from repro.kernels import dag_walk as dw
from repro.vee.apps import (linear_regression_oracle, linreg_device_lowering,
                            merge_device_lowerings, recommendation_device_lowering,
                            recommendation_oracle, run_device_dag,
                            split_device_values)

ROWS, COLS, TILE = 256, 5, 32


@pytest.fixture(autouse=True)
def cold_memo():
    dw.clear_walker_call_cache()
    yield
    dw.clear_walker_call_cache()


def _rows_table(low):
    """The single-shard super-table of ``low`` in row space."""
    ddt = build_dag_tables_cached(low.dag, 1, "GSS")
    rows = ddt.tables[0].copy()
    rows[:, 1:] *= low.tile
    return rows


def _walk(low, table):
    out = dw.dag_walk(low.stages, low.operands, low.values, table, low.tile)
    return {k: np.asarray(v) for k, v in out.items()}


def _stats():
    s = dw.walker_call_cache_stats()
    return s["hits"], s["misses"], s["size"]


def test_repeat_walk_hits_without_compiling():
    low = linreg_device_lowering(ROWS, COLS, tile=TILE)
    table = _rows_table(low)
    first = _walk(low, table)
    assert _stats() == (0, 1, 1)
    watch_compiles()
    before = compile_counts()
    again = _walk(low, table)
    after = compile_counts()
    assert _stats() == (1, 1, 1)
    # no program past JAX's in-memory caches and no lowering; jit's Python
    # path may still look its trace cache up, which reports a trace event
    assert after["requests"] == before["requests"]
    assert after["lower_s"] == before["lower_s"]
    dw.clear_walker_call_cache()
    assert _stats() == (0, 0, 0)
    cold = _walk(low, table)
    assert _stats() == (0, 1, 1)
    for k in first:
        assert np.array_equal(again[k], cold[k]), k
        assert np.array_equal(first[k], cold[k]), k


def test_other_schedule_same_slot_count_hits_and_stays_correct():
    low = linreg_device_lowering(ROWS, COLS, tile=TILE)
    table = _rows_table(low)
    # another valid schedule: each sum stage's tiles in reverse order, the
    # barrier kept (every moments slot before every syrk_gemv slot)
    moments = table[table[:, 0] == 0][::-1]
    syrk = table[table[:, 0] == 1][::-1]
    other = np.concatenate([moments, syrk])
    assert other.shape == table.shape and not np.array_equal(other, table)
    beta_ref = linear_regression_oracle(ROWS, COLS)
    betas = []
    for t in (table, other):
        betas.append(low.finalize(_walk(low, t)))
    assert _stats() == (1, 1, 1)
    for beta in betas:
        np.testing.assert_allclose(beta, beta_ref, atol=1e-4)


def _other_shape(low):
    return linreg_device_lowering(2 * ROWS, COLS, tile=TILE)


def _other_tile(low):
    return linreg_device_lowering(ROWS, COLS, tile=2 * TILE)


def _other_body(low):
    def body(ctx, ins, out, inner=low.stages[0].body):
        inner(ctx, ins, out)

    stages = [dataclasses.replace(low.stages[0], body=body)] + low.stages[1:]
    return dataclasses.replace(low, stages=stages)


@pytest.mark.parametrize("change", [_other_shape, _other_tile, _other_body],
                         ids=["shape", "tile", "body"])
def test_other_structure_misses(change):
    low = linreg_device_lowering(ROWS, COLS, tile=TILE)
    base = _walk(low, _rows_table(low))
    changed = change(low)
    out = _walk(changed, _rows_table(changed))
    assert _stats() == (0, 2, 2)
    beta_ref = linear_regression_oracle(changed.values["W"].shape[1], COLS)
    np.testing.assert_allclose(changed.finalize(out), beta_ref, atol=1e-4)
    if change is _other_body:  # the same math through a new body
        for k in base:
            assert np.array_equal(out[k], base[k]), k


def test_repeat_merges_share_one_entry():
    lows = [linreg_device_lowering(128, COLS, tile=TILE, seed=s)
            for s in (1, 2, 3)]
    merges = [merge_device_lowerings(lows) for _ in range(2)]
    assert merges[0].stages == merges[1].stages
    assert hash(tuple(merges[0].stages)) == hash(tuple(merges[1].stages))
    runs = [run_device_dag(m, "GSS")[0] for m in merges]
    assert _stats() == (1, 1, 1)
    for k in runs[0]:
        assert np.array_equal(runs[0][k], runs[1][k]), k
    for low, vals in zip(lows, split_device_values(runs[1], len(lows))):
        alone, _ = run_device_dag(low, "GSS")
        assert set(alone) == set(vals)
        for k in alone:
            assert np.array_equal(alone[k], vals[k]), k
    # a merge of other members is another structure
    merge_other = merge_device_lowerings(lows[:2])
    assert merge_other.stages != merges[0].stages


def _lookup(low, n_slots):
    """The memo's entry for ``low``'s structure at ``n_slots``, untraced."""
    shapes = tuple((tuple(low.values[op.name].shape), low.values[op.name].dtype)
                   for op in low.operands)
    return dw._jitted_walker(tuple(low.stages), tuple(low.operands), shapes,
                             n_slots, low.tile)


def test_lru_bound_evicts_least_recent():
    """Structures past the bound evict the least recently launched one.

    A lookup builds a ``jax.jit`` without tracing it, so this drives only
    the memo, with one structure per slot count.
    """
    bound = dw._jitted_walker.cache_info().maxsize
    assert bound == 64
    low = linreg_device_lowering(64, COLS, tile=TILE)
    for n_slots in range(1, bound + 2):   # one past the bound: evicts 1
        _lookup(low, n_slots)
    assert _stats() == (0, bound + 1, bound)
    _lookup(low, 2)                       # hit: 3 is now the least recent
    assert _stats() == (1, bound + 1, bound)
    _lookup(low, 1)                       # was evicted: built again, evicts 3
    assert _stats() == (1, bound + 2, bound)
    _lookup(low, 2)
    _lookup(low, bound + 1)               # both still held
    assert _stats() == (3, bound + 2, bound)
    _lookup(low, 3)
    assert _stats() == (3, bound + 3, bound)


def _linreg_pair():
    lows = [linreg_device_lowering(ROWS, COLS, tile=TILE, seed=s)
            for s in (1, 2)]
    return lows, [lambda out, s=s: np.testing.assert_allclose(
        lows[s - 1].finalize(out), linear_regression_oracle(ROWS, COLS, seed=s),
        atol=1e-4) for s in (1, 2)]


def _recommendation_pair():
    lows = [recommendation_device_lowering(ROWS, 48, tile=TILE, seed=s)
            for s in (0, 1)]
    return lows, [lambda out, s=s: np.testing.assert_array_equal(
        out["scores"].reshape(-1), recommendation_oracle(ROWS, 48, seed=s))
        for s in (0, 1)]


@pytest.mark.parametrize("pair", [_linreg_pair, _recommendation_pair],
                         ids=["linreg", "recommendation"])
def test_new_data_same_shape_hits(pair):
    """Jobs of one shape with new data share one compiled walker.

    Each lowering builds its own operands, closures and host ops, but its
    stage bodies are module-level functions or frozen dataclasses of their
    constants, so the stages compare equal and the second job hits; each
    job still computes its own answer.
    """
    lows, checks = pair()
    assert lows[0].stages == lows[1].stages
    assert not np.array_equal(np.asarray(next(iter(lows[0].values.values()))),
                              np.asarray(next(iter(lows[1].values.values()))))
    for low, check in zip(lows, checks):
        check(_walk(low, _rows_table(low)))
    assert _stats() == (1, 1, 1)


def test_moe_lowerings_of_one_shape_compare_equal():
    """MoE lowerings of one shape give equal stages: one memo entry."""
    from repro.vee.ml_apps import moe_device_lowering, moe_dispatch_lowering

    a, b = (moe_device_lowering(moe_dispatch_lowering(n_tokens=48, seed=s))
            for s in (0, 1))
    assert a.stages == b.stages
    assert hash(tuple(a.stages)) == hash(tuple(b.stages))
