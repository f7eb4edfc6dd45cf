"""Device-side pipeline-DAG execution tests (DESIGN.md §11).

Covers the tentpole invariants:

  * ``build_dag_tables`` slot ordering respects elementwise and full
    edges for random DAG shapes/techniques/shard counts (property test);
  * the fused multi-stage walker agrees with the host PipelineExecutor
    and the float64 numpy oracles on the linreg and recommendation
    lowerings within written tolerances, and matches the per-stage-launch
    baseline bit-wise (same kernel code, one backend);
  * cc_propagate's body runs as the propagate stage of a CC iteration
    super-table (the single-stage kernel as stage-body special case);
  * frozen-replay simulation: fused makespan <= sequential launches;
  * per-(stage, chunk) rebalancing reduces the hot shard's load while
    preserving the slot-ordering invariants.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    PipelineDAG,
    PipelineExecutor,
    SchedulerConfig,
    Stage,
    StageDep,
    build_dag_tables,
    frozen_dag_makespans,
    rebalance_dag,
    select_offline_device_dag,
    simulate_dag,
)
from repro.core.partitioners import PARTITIONERS

TECHS = sorted(PARTITIONERS)


def _dummy_op(inputs, s, z):
    return np.zeros(z)


def _random_dag(n_stages, n_rows, dep_choices):
    """Chain/branch DAG over equal row counts; producers forced concat."""
    stages = []
    for i in range(n_stages):
        deps = ()
        if i > 0:
            prod, kind = dep_choices[i - 1]
            deps = (StageDep(f"s{prod % i}", kind),)
        stages.append(Stage(f"s{i}", n_rows, _dummy_op, combine="concat",
                            deps=deps))
    return PipelineDAG(stages)


def _check_table_invariants(dag, ddt, tile):
    """Exactly-once tile coverage + per-shard dependency ordering, and the
    walker's residency rule: an elementwise consumer takes tile t before
    its producer's next slot on that shard (the walker keeps one output
    block per stage on chip)."""
    names = list(ddt.stage_names)
    n_tiles = {n: dag.stages[n].n_rows // tile for n in names}
    seen = {n: {} for n in names}          # tile -> (shard, slot index)
    for sh in range(ddt.n_shards):
        for pos, (sid, start, size) in enumerate(ddt.slots(sh)):
            assert size == tile
            name = names[sid]
            t = start // tile
            assert t not in seen[name], f"tile {t} of {name} emitted twice"
            seen[name][t] = (sh, pos)
    for n in names:
        assert set(seen[n]) == set(range(n_tiles[n])), f"{n} tiles incomplete"
        for p, kind in ddt.deps[n]:
            for t, (sh, pos) in seen[n].items():
                if kind == "elementwise":
                    psh, ppos = seen[p][t]
                    assert psh == sh, f"{n}:{t} not row-aligned with {p}"
                    assert ppos < pos, f"{n}:{t} precedes producer tile"
                    later = [q for s2, q in seen[p].values()
                             if s2 == sh and q > ppos]
                    assert not later or min(later) > pos, \
                        f"{p} moved past tile {t} before {n} read it"
                else:
                    assert all(pp < pos for _, pp in seen[p].values()), \
                        f"{n}:{t} precedes full-dep producer {p}"


@settings(max_examples=25, deadline=None)
@given(
    n_stages=st.integers(2, 4),
    tiles=st.integers(2, 12),
    n_shards=st.integers(1, 4),
    tech_i=st.lists(st.integers(0, len(TECHS) - 1), min_size=4, max_size=4),
    dep_kind=st.lists(st.booleans(), min_size=3, max_size=3),
    prod=st.lists(st.integers(0, 3), min_size=3, max_size=3),
    seed=st.integers(0, 3),
)
def test_build_dag_tables_slot_order(n_stages, tiles, n_shards, tech_i,
                                     dep_kind, prod, seed):
    tile = 4
    dep_choices = [(prod[i], "elementwise" if dep_kind[i] else "full")
                   for i in range(n_stages - 1)]
    if any(k == "full" for _, k in dep_choices):
        n_shards = 1
    dag = _random_dag(n_stages, tiles * tile, dep_choices)
    techniques = {f"s{i}": TECHS[tech_i[i]] for i in range(n_stages)}
    ddt = build_dag_tables(dag, tile, techniques, n_shards=n_shards,
                           n_workers=4, seed=seed)
    _check_table_invariants(dag, ddt, tile)


def test_full_dep_requires_single_shard():
    a = Stage("a", 8, _dummy_op, combine="sum")
    b = Stage("b", 8, _dummy_op, combine="sum", deps=(StageDep("a", "full"),))
    dag = PipelineDAG([a, b])
    with pytest.raises(ValueError, match="full dep"):
        build_dag_tables(dag, 2, n_shards=2)


def test_tile_must_divide_rows():
    dag = PipelineDAG([Stage("a", 10, _dummy_op)])
    with pytest.raises(ValueError, match="multiple of tile"):
        build_dag_tables(dag, 4)


def test_multi_elementwise_producers():
    """Two elementwise producers: fine when identically sharded, a clear
    up-front error (not a mid-merge crash) when their owners diverge."""
    a = Stage("a", 16, _dummy_op, combine="concat")
    b = Stage("b", 16, _dummy_op, combine="concat")
    c = Stage("c", 16, _dummy_op, combine="concat",
              deps=(StageDep("a", "elementwise"), StageDep("b", "elementwise")))
    dag = PipelineDAG([a, b, c])
    ddt = build_dag_tables(dag, 4, "GSS", n_shards=1, n_workers=2)
    _check_table_invariants(dag, ddt, 4)
    ddt2 = build_dag_tables(dag, 4, "STATIC", n_shards=2, n_workers=2)
    _check_table_invariants(dag, ddt2, 4)
    with pytest.raises(ValueError, match="identically-sharded"):
        build_dag_tables(dag, 4, {"a": "STATIC", "b": "GSS", "c": "STATIC"},
                         n_shards=2, n_workers=2)


# ---------------------------------------------------------------------------
# end-to-end: fused walker vs host PipelineExecutor vs float64 oracle
#
# Host ops run the per-tile float32 math eagerly, the walker runs it inside
# one kernel, so the two agree to float32 rounding (RTOL_F32), not bit for
# bit. A recommended item may differ from the float64 oracle's only on a
# near-tie: its float64 score must then be within REC_REGRET of the best.
# ---------------------------------------------------------------------------

RTOL_F32 = 1e-5
REC_REGRET = 1e-6


def _assert_top_items(items, n_users, n_items, seed):
    from repro.vee.apps import recommendation_oracle, recommendation_regret

    items = np.asarray(items).reshape(-1)
    want = recommendation_oracle(n_users, n_items, seed=seed)
    regret = recommendation_regret(items, n_users, n_items, seed=seed)
    assert regret.max() <= REC_REGRET, regret.max()
    assert (items == want).mean() >= 0.99

def test_linreg_device_matches_host_bitwise():
    from repro.vee.apps import (linear_regression_oracle,
                                linreg_device_lowering, run_device_dag)

    low = linreg_device_lowering(512, 9, tile=64, seed=1)
    # SS/1 worker: the host accumulates sum stages in flat ascending tile
    # order, exactly like the walker (see DeviceLowering docstring)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    fused, ddt = run_device_dag(low, {"moments": "GSS", "syrk_gemv": "FAC2"})
    seq, _ = run_device_dag(low, {"moments": "GSS", "syrk_gemv": "FAC2"},
                            stagewise=True)
    for k in ("moments", "syrk_gemv"):
        np.testing.assert_allclose(np.asarray(host.values[k]), fused[k],
                                   rtol=RTOL_F32, atol=RTOL_F32, err_msg=k)
        assert np.array_equal(fused[k], seq[k]), k
    beta_ref = linear_regression_oracle(512, 9)
    for vals in (host.values, fused):
        np.testing.assert_allclose(low.finalize(vals), beta_ref, atol=1e-4)


def test_recommendation_device_matches_host_bitwise():
    from repro.vee.apps import (recommendation_device,
                                recommendation_device_lowering,
                                recommendation_oracle, run_device_dag)

    low = recommendation_device_lowering(256, 32, tile=32, seed=0)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    fused, _ = run_device_dag(low, "MFSC")
    R = np.asarray(low.values["R"], dtype=np.float64)
    np.testing.assert_allclose(fused["item_norms"],
                               (R ** 2).sum(axis=0, keepdims=True),
                               rtol=RTOL_F32)
    np.testing.assert_allclose(np.asarray(host.values["item_norms"]),
                               fused["item_norms"], rtol=RTOL_F32)
    # host concat values are (tiles, tile, 1), the walker's (n_users, 1)
    host_bias = np.asarray(host.values["user_bias"]).reshape(-1)
    np.testing.assert_allclose(host_bias, R.mean(axis=1), rtol=RTOL_F32)
    np.testing.assert_allclose(host_bias, fused["user_bias"].reshape(-1),
                               rtol=RTOL_F32)
    for items in (host.values["scores"], fused["scores"]):
        _assert_top_items(items, 256, 32, seed=0)
    scores, _, _ = recommendation_device(256, 32, tile=32)
    assert np.array_equal(scores, fused["scores"].reshape(-1))
    assert np.array_equal(scores, recommendation_oracle(256, 32))


def test_recommendation_concat_insensitive_to_host_config():
    """Concat stages write disjoint tiles: any host technique/worker count
    reproduces the walker's buffers bit-wise."""
    from repro.vee.apps import recommendation_device_lowering, run_device_dag

    low = recommendation_device_lowering(128, 16, tile=16, seed=3)
    fused, _ = run_device_dag(low, "GSS")
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="MFSC", queue_layout="PERCORE", n_workers=4)).run()
    for k in ("user_bias", "scores"):
        assert np.array_equal(np.asarray(host.values[k]).reshape(-1),
                              fused[k].reshape(-1)), k


@pytest.mark.parametrize("n_shards", [1, 2])
def test_cc_iteration_super_table(n_shards):
    """cc_propagate's body as the propagate stage of a CC super-table."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.cc_propagate import propagate_body
    from repro.kernels.dag_walk import (WalkOperand, WalkStage, dag_walk,
                                        dag_walk_sharded)

    n, tile_r, tile_c = 256, 32, 64
    rng = np.random.default_rng(7)
    G = (rng.uniform(size=(n, n)) < 0.05).astype(np.float32)
    np.fill_diagonal(G, 0)
    c = rng.integers(1, 1000, n).astype(np.float32)

    dag = PipelineDAG([
        Stage("propagate", n, _dummy_op, combine="concat"),
        Stage("changed", n, _dummy_op, combine="sum",
              deps=(StageDep("propagate", "elementwise"),)),
    ])
    ddt = build_dag_tables(dag, tile_r,
                           {"propagate": "MFSC", "changed": "STATIC"},
                           n_shards=n_shards, n_workers=4)

    def prop_body(ctx, ins, out):
        propagate_body(ctx.inner, ins["G"], ins["c_col"], ins["c_row"], out)

    def changed_body(ctx, ins, out):
        flips = (ins["propagate"][...] != ins["c_row"][...]).astype(jnp.int32)
        out[...] += flips.sum(axis=0, keepdims=True)

    stages = [
        WalkStage("propagate", n, (n, 1), jnp.float32, "concat", prop_body,
                  operands=("G", "c_col", "c_row"), inner=n // tile_c),
        WalkStage("changed", n, (1, 1), jnp.int32, "sum", changed_body,
                  operands=("c_row",), reads=(("propagate", "rows"),)),
    ]
    operands = [
        WalkOperand("G", (tile_r, tile_c), ("row", "inner")),
        WalkOperand("c_col", (1, tile_c), ("zero", "inner")),
        WalkOperand("c_row", (tile_r, 1), ("row", "zero")),
    ]
    values = {"G": jnp.asarray(G), "c_col": jnp.asarray(c).reshape(1, n),
              "c_row": jnp.asarray(c).reshape(n, 1)}
    if n_shards == 1:
        out = dag_walk(stages, operands, values, ddt.tables[0], tile_r)
    else:
        out = dag_walk_sharded(stages, operands, values, ddt.tables, tile_r)
    want = np.asarray(ref.cc_propagate_ref(jnp.asarray(G), jnp.asarray(c)))
    assert np.array_equal(np.asarray(out["propagate"]).reshape(-1), want)
    assert int(np.asarray(out["changed"]).sum()) == int((want != c).sum())


def test_walker_rejects_rows_read_after_producer_moved_on():
    """A TPU keeps one output block per stage on chip: a consumer reading a
    producer's tile after the producer moved to its next tile would read
    the wrong block, so the walker refuses such a table up front."""
    import jax.numpy as jnp

    from repro.kernels.dag_walk import WalkOperand, WalkStage, dag_walk

    tile, n = 8, 16

    def copy_body(ctx, ins, out):
        out[...] = ins["X"][...]

    def add_body(ctx, ins, out):
        out[...] = ins["X"][...] + ins["p"][...]

    stages = [
        WalkStage("p", n, (n, 128), jnp.float32, "concat", copy_body,
                  operands=("X",)),
        WalkStage("c", n, (n, 128), jnp.float32, "concat", add_body,
                  operands=("X",), reads=(("p", "rows"),)),
    ]
    operands = [WalkOperand("X", (tile, 128), ("row", "zero"))]
    values = {"X": jnp.ones((n, 128), jnp.float32)}
    streamed = np.array([[0, 0, tile], [1, 0, tile], [0, tile, tile],
                         [1, tile, tile]], np.int32)
    out = dag_walk(stages, operands, values, streamed, tile)
    assert np.array_equal(np.asarray(out["c"]), np.full((n, 128), 2.0))
    lagging = streamed[[0, 2, 1, 3]]
    with pytest.raises(ValueError, match="moved on"):
        dag_walk(stages, operands, values, lagging, tile)


# ---------------------------------------------------------------------------
# property test: host PipelineExecutor vs device walker vs float64 oracle on
# RANDOMIZED DAG shapes/techniques — SPLIT placements (core/hetero.py) are
# only safe because any tile can run on either substrate with the same
# results up to float32 rounding; this pins that equivalence beyond the two
# hand-built lowerings.
# ---------------------------------------------------------------------------

def _random_lowering(n_stages, tiles, tile, combine_flags, dep_prod, seed):
    """A random chain DAG whose host ops and walker bodies share per-tile
    jnp math: stage i computes ``X_tile * (i+1)`` plus its producer's
    contribution (elementwise row tile of a concat producer, or the full
    accumulator of a sum producer — the kind is forced by the producer's
    combine, mirroring the walker's supported reads)."""
    import jax.numpy as jnp

    from repro.kernels.dag_walk import WalkOperand, WalkStage

    n = tiles * tile
    w = 8
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1.0, 1.0, size=(n, w)).astype(np.float32)
    combine = ["concat" if f else "sum" for f in combine_flags[:n_stages]]
    combine[0] = "concat"  # a root producer keeps every dep kind reachable

    stages_host, stages_dev = [], []
    for i in range(n_stages):
        name = f"s{i}"
        c = np.float32(i + 1)
        dep = None
        if i > 0:
            j = dep_prod[i - 1] % i
            kind = "elementwise" if combine[j] == "concat" else "full"
            dep = (f"s{j}", kind)

        def tile_math(Xb, prod, dep=dep, c=c):
            v = Xb * c
            if prod is not None:
                v = v + prod
            return v

        def host_op(inputs, s, z, dep=dep, tile_math=tile_math,
                    comb=combine[i]):
            outs = None
            for t in range(s, s + z):
                Xb = jnp.asarray(X[t * tile:(t + 1) * tile])
                prod = None
                if dep is not None:
                    pname, kind = dep
                    prod = (jnp.asarray(inputs[pname][t])
                            if kind == "elementwise"
                            else jnp.asarray(inputs[pname]))
                v = tile_math(Xb, prod)
                if comb == "concat":
                    outs = [v] if outs is None else outs + [v]
                else:
                    v = v.sum(axis=0)
                    outs = v if outs is None else outs + v
            return jnp.stack(outs) if comb == "concat" else outs

        def dev_body(ctx, ins, out, dep=dep, tile_math=tile_math,
                     comb=combine[i]):
            prod = ins[dep[0]][...] if dep is not None else None
            v = tile_math(ins["X"][...], prod)
            if comb == "concat":
                out[...] = v
            else:
                out[...] += v.sum(axis=0)

        deps = ()
        reads = ()
        if dep is not None:
            pname, kind = dep
            deps = (StageDep(pname, kind),)
            reads = ((pname, "rows" if kind == "elementwise" else "full"),)
        stages_host.append(Stage(name, tiles, host_op, combine=combine[i],
                                 deps=deps))
        out_shape = (n, w) if combine[i] == "concat" else (w,)
        stages_dev.append(WalkStage(name, n, out_shape, jnp.float32,
                                    combine[i], dev_body, operands=("X",),
                                    reads=reads))
    operands = [WalkOperand("X", (tile, w), ("row", "zero"))]
    values = {"X": jnp.asarray(X)}
    return PipelineDAG(stages_host), stages_dev, operands, values, combine


def _random_dag_oracle(X, combine, dep_prod):
    """Float64 numpy values of ``_random_lowering``'s stages (row space)."""
    X = np.asarray(X, dtype=np.float64)
    out = []
    for i, comb in enumerate(combine):
        v = X * (i + 1)
        if i > 0:
            v = v + out[dep_prod[i - 1] % i]  # row-wise or broadcast full sum
        out.append(v if comb == "concat" else v.sum(axis=0))
    return out


@settings(max_examples=8, deadline=None)
@given(
    n_stages=st.integers(2, 3),
    tiles=st.integers(2, 6),
    combine_flags=st.lists(st.booleans(), min_size=3, max_size=3),
    dep_prod=st.lists(st.integers(0, 2), min_size=2, max_size=2),
    tech_i=st.lists(st.integers(0, len(TECHS) - 1), min_size=3, max_size=3),
    seed=st.integers(0, 4),
)
def test_random_dag_host_device_bitwise(n_stages, tiles, combine_flags,
                                        dep_prod, tech_i, seed):
    from repro.kernels.dag_walk import dag_walk

    tile = 4
    dag, dev_stages, operands, values, combine = _random_lowering(
        n_stages, tiles, tile, combine_flags, dep_prod, seed)
    oracle = _random_dag_oracle(values["X"], combine[:n_stages], dep_prod)
    # SS/1 worker: the host folds sum stages in flat ascending tile order,
    # exactly like the walker (see DeviceLowering docstring)
    host = PipelineExecutor(dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    techniques = {f"s{i}": TECHS[tech_i[i]] for i in range(n_stages)}
    ddt = build_dag_tables(dag, 1, techniques, n_shards=1, n_workers=4,
                           seed=seed)
    rows = ddt.tables[0].copy()
    rows[:, 1:] *= tile  # tile units -> row space for the walker
    out = dag_walk(dev_stages, operands, values, rows, tile)
    for i in range(n_stages):
        name = f"s{i}"
        hv = np.asarray(host.values[name])
        if combine[i] == "concat":
            hv = hv.reshape(-1, hv.shape[-1])
        dv = np.asarray(out[name])
        for got in (hv, dv):
            np.testing.assert_allclose(got, oracle[i], rtol=RTOL_F32,
                                       atol=RTOL_F32,
                                       err_msg=f"{name} {combine[i]} {techniques}")
        np.testing.assert_allclose(hv, dv, rtol=RTOL_F32, atol=RTOL_F32)


# ---------------------------------------------------------------------------
# frozen-replay simulation + device autotuning + rebalancing
# ---------------------------------------------------------------------------

def _cc_like_dag(tiles, tile):
    n = tiles * tile
    prop = Stage("prop", n, _dummy_op, combine="concat")
    chk = Stage("chk", n, _dummy_op, combine="concat",
                deps=(StageDep("prop", "elementwise"),))
    return PipelineDAG([prop, chk])


@settings(max_examples=20, deadline=None)
@given(
    tech_a=st.sampled_from(TECHS),
    tech_b=st.sampled_from(TECHS),
    n_shards=st.integers(1, 4),
    seed=st.integers(0, 5),
)
def test_frozen_fused_never_slower_than_sequential(tech_a, tech_b, n_shards,
                                                   seed):
    tile, tiles = 4, 16
    dag = _cc_like_dag(tiles, tile)
    rng = np.random.default_rng(seed)
    costs = {"prop": rng.pareto(1.5, tiles * tile) + 0.1,
             "chk": np.ones(tiles * tile) * 0.2}
    ddt = build_dag_tables(dag, tile, {"prop": tech_a, "chk": tech_b},
                           n_shards=n_shards, n_workers=4, seed=seed)
    fused, seq = frozen_dag_makespans(ddt, costs)
    assert fused <= seq + 1e-12


def test_frozen_simulate_matches_makespans_helper():
    tile, tiles = 4, 8
    dag = _cc_like_dag(tiles, tile)
    costs = {"prop": np.ones(tiles * tile), "chk": np.ones(tiles * tile)}
    ddt = build_dag_tables(dag, tile, "GSS", n_shards=2, n_workers=4)
    res = simulate_dag(dag, costs, frozen=ddt)
    fused, _ = frozen_dag_makespans(ddt, costs)
    assert res.makespan == pytest.approx(fused)
    assert res.stage_finish["chk"] <= res.makespan + 1e-12


def test_select_offline_device_dag_never_worse_than_uniform():
    tile, tiles = 4, 16
    dag = _cc_like_dag(tiles, tile)
    rng = np.random.default_rng(2)
    costs = {"prop": rng.pareto(1.2, tiles * tile) + 0.05,
             "chk": np.full(tiles * tile, 0.3)}
    assign, best, uniform = select_offline_device_dag(
        dag, costs, tile=tile, n_shards=4, passes=2)
    assert set(assign) == {"prop", "chk"}
    assert best <= min(uniform.values()) + 1e-12


def test_rebalance_dag_moves_load_and_keeps_invariants():
    tile, tiles = 4, 32
    dag = _cc_like_dag(tiles, tile)
    ddt = build_dag_tables(dag, tile, {"prop": "MFSC", "chk": "MFSC"},
                           n_shards=4, n_workers=4, assignment="contiguous")
    rng = np.random.default_rng(0)
    # per-TILE loads, skewed: the first quarter of the row space (shard 0
    # under contiguous assignment) is 10x as expensive
    tile_load = {}
    for name in ddt.stage_names:
        base = rng.uniform(1.0, 2.0, tiles)
        base[: tiles // 4] *= 10
        tile_load[name] = base

    def chunk_loads(d, name):
        return np.array([tile_load[name][s:s + z].sum()
                         for s, z in d.stage_chunks[name]])

    def max_shard_load(d):
        load = np.zeros(d.n_shards)
        for name in d.stage_names:
            cl = chunk_loads(d, name)
            for c, sh in enumerate(d.chunk_shard[name]):
                load[sh] += cl[c]
        return load.max()

    before = max_shard_load(ddt)
    measured = {name: chunk_loads(ddt, name) for name in ddt.stage_names}
    new = rebalance_dag(ddt, measured, max_moves=32)
    for name in ddt.stage_names:  # every tile still scheduled exactly once
        assert new.stage_chunks[name][:, 1].sum() == tiles
    assert max_shard_load(new) < before
    _check_table_invariants(dag, new, tile)
