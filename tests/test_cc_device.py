"""Connected components to convergence on the device-DAG path (DESIGN.md §11).

``vee.apps.cc_device_lowering`` lowers one round of the paper's Listing 1
to a two-stage super-table, and its ``Fixpoint`` makes
``run_device_dag`` walk round after round, each round's labels fed into
the next, until the ``changed`` stage reads 0. On Graph500 Kronecker
graphs at scale 9 (n = 512, tiles 128 x 256) and scale 8 (n = 256, tiles
32 x 64) the loop's labels and its round count equal the plain
``kernels.ref.connected_components_ref`` exactly (a max over labels has
no rounding), with one shard and two, and the labels equal the host
pipeline-DAG path's. At scale 10 the loop's labels equal a union-find's,
and one round, whose ``changed`` slots hold the adjacency's block
(``kernels.dag_walk._index_map``), equals the one-launch-per-stage walk
bit for bit. Rounds after the first and later jobs of the same
shape reuse the compiled walker. Everything runs on the CPU, in Pallas's
TPU interpret mode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PipelineExecutor, SchedulerConfig, Tracer,
                        compile_counts, loop_counts)
from repro.core.device_schedule import build_dag_tables_cached
from repro.kernels import dag_walk as dw
from repro.kernels import ref
from repro.vee.apps import (cc_device_lowering, connected_components_dag,
                            merge_device_lowerings, run_device_dag)
from repro.vee.sparse import rmat_graph

TECHNIQUES = {"propagate": "MFSC", "changed": "STATIC"}
EDGE_FACTOR = 16
SIZES = {"scale9": (9, 128, 256), "scale8": (8, 32, 64)}


def _lowering(size="scale9", seed=1):
    scale, tile, tile_c = SIZES[size]
    return cc_device_lowering(scale, EDGE_FACTOR, tile=tile, tile_c=tile_c,
                              seed=seed)


def _start(low):
    n = low.stages[0].n_rows
    return np.asarray(low.values["G"]), jnp.arange(1, n + 1, dtype=jnp.float32)


def _run(low, n_shards=1, tracer=None):
    """The loop's int64 labels and its walker launches, by the loop counter."""
    before = loop_counts()
    values, _ = run_device_dag(low, TECHNIQUES, n_shards=n_shards,
                               n_workers=4, tracer=tracer, job="0")
    after = loop_counts()
    assert after["jobs"] - before["jobs"] == 1
    return low.finalize(values), after["launches"] - before["launches"]


@pytest.mark.parametrize("n_shards", [1, 2])
@pytest.mark.parametrize("size,seed", [("scale9", 1), ("scale9", 2),
                                       ("scale9", 3), ("scale8", 4)])
def test_loop_equals_reference_labels_and_rounds(size, seed, n_shards):
    low = _lowering(size, seed)
    labels, launches = _run(low, n_shards)
    G, c = _start(low)
    want, rounds = ref.connected_components_ref(jnp.asarray(G), c)
    assert labels.dtype == np.int64
    assert np.array_equal(labels, np.asarray(want).astype(np.int64))
    assert launches == rounds * n_shards and rounds >= 2
    scale, _, _ = SIZES[size]
    host, iters, _ = connected_components_dag(
        rmat_graph(scale, EDGE_FACTOR, seed=seed, relabel=True),
        SchedulerConfig(n_workers=2))
    assert np.array_equal(labels, host) and iters == rounds


def _union_find_labels(G):
    """Each vertex's largest 1-based id in its component, by union-find over
    the nonzero entries of the dense adjacency."""
    n = len(G)
    parent = list(range(n))

    def root(v):
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    for u, v in zip(*np.nonzero(G)):
        ru, rv = root(int(u)), root(int(v))
        if ru != rv:
            parent[min(ru, rv)] = max(ru, rv)
    roots = np.array([root(v) for v in range(n)])
    labels = np.zeros(n, np.int64)
    np.maximum.at(labels, roots, np.arange(1, n + 1))
    return labels[roots]


def test_round_with_held_blocks_equals_stagewise_bit_for_bit():
    """One round's ``propagate`` and ``changed`` through the fused walker,
    whose ``changed`` slots hold G's and ``c_col``'s blocks, equal the
    one-launch-per-stage walk's, where no slot holds a block."""
    low = cc_device_lowering(10, EDGE_FACTOR, tile=32, tile_c=128, seed=3)
    ddt = build_dag_tables_cached(low.dag, 1, TECHNIQUES)
    rows = ddt.tables[0].copy()
    rows[:, 1:] *= low.tile
    fused = dw.dag_walk(low.stages, low.operands, low.values, rows, low.tile)
    stagewise = dw.dag_walk_stagewise(low.stages, low.operands, low.values,
                                      rows, low.tile)
    assert set(fused) == set(stagewise) == {"propagate", "changed"}
    for k in fused:
        assert np.array_equal(np.asarray(fused[k]), np.asarray(stagewise[k])), k
    assert int(np.asarray(fused["changed"]).sum()) > 0


def test_scale10_fixed_point_equals_union_find():
    low = cc_device_lowering(10, EDGE_FACTOR, tile=128, tile_c=256, seed=1)
    labels, launches = _run(low)
    want = _union_find_labels(np.asarray(low.values["G"]))
    assert np.array_equal(labels, want)
    assert launches >= 2


def test_later_rounds_and_jobs_reuse_the_compiled_walker():
    tracer = Tracer()
    dw.clear_walker_call_cache()
    try:
        _, launches = _run(_lowering(seed=1), tracer=tracer)
        stats = dw.walker_call_cache_stats()
        assert (stats["misses"], stats["hits"]) == (1, launches - 1)
        before = compile_counts()
        _, again = _run(_lowering(seed=2), tracer=tracer)  # new data, same shape
        stats = dw.walker_call_cache_stats()
        assert (stats["misses"], stats["hits"]) == (1, launches + again - 1)
        assert compile_counts()["requests"] == before["requests"]
    finally:
        dw.clear_walker_call_cache()


def test_lowering_without_the_loop_walks_once():
    low = dataclasses.replace(_lowering(seed=5), fixpoint=None)
    tracer = Tracer()
    before = loop_counts()
    values, _ = run_device_dag(low, TECHNIQUES, tracer=tracer, job="0")
    assert loop_counts() == before
    assert sum(s.kind == "devdag.launch" for s in tracer.spans()) == 1
    assert not any(s.kind == "devdag.feed" for s in tracer.spans())
    G, c = _start(low)
    want = np.asarray(ref.cc_propagate_ref(jnp.asarray(G), c))
    got = values["propagate"].reshape(-1)
    assert np.array_equal(got, want)
    assert int(values["changed"].sum()) == int((want != np.asarray(c)).sum()) > 0
    host = PipelineExecutor(low.dag, SchedulerConfig(technique="SS",
                                                     n_workers=1)).run()
    assert np.array_equal(np.asarray(host.values["propagate"]).reshape(-1), got)
    assert int(np.asarray(host.values["changed"]).sum()) == \
        int(values["changed"].sum())


def test_max_rounds_without_a_fixed_point_raises():
    low = _lowering(seed=1)
    low = dataclasses.replace(
        low, fixpoint=dataclasses.replace(low.fixpoint, max_rounds=2))
    with pytest.raises(RuntimeError, match="no fixed point after 2 rounds"):
        run_device_dag(low, TECHNIQUES)


def test_merge_refuses_a_looped_lowering():
    low = _lowering("scale8", seed=1)
    with pytest.raises(ValueError, match="fixed-point loop"):
        merge_device_lowerings([low, _lowering("scale8", seed=2)])


def test_feed_phases_and_loop_counters_count_rounds():
    tracer = Tracer()
    _, rounds = _run(_lowering(seed=3), tracer=tracer)
    spans = [s for s in tracer.spans() if s.kind.startswith("devdag.")]
    kinds = [s.kind for s in spans]
    assert kinds.count("devdag.job") == kinds.count("devdag.tables") == 1
    assert kinds.count("devdag.feed") == rounds - 1
    for kind in ("devdag.launch", "devdag.readback", "devdag.combine"):
        assert kinds.count(kind) == rounds, kind
    (job,) = [s for s in spans if s.kind == "devdag.job"]
    assert all(job.t0 <= s.t0 <= s.t1 <= job.t1 for s in spans)
    # each feed lies between one round's combine and the next round's launch
    feeds = sorted(s.t0 for s in spans if s.kind == "devdag.feed")
    launches = sorted(s.t0 for s in spans if s.kind == "devdag.launch")
    assert all(a < f < b for a, f, b in zip(launches, feeds, launches[1:]))


def test_profiler_trace_holds_feed_without_a_tracer(tmp_path):
    low = _lowering("scale8", seed=2)
    G, c = _start(low)
    _, rounds = ref.connected_components_ref(jnp.asarray(G), c)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_device_dag(low, TECHNIQUES)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(xplane))
    names = [e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("devdag.feed") == rounds - 1
    assert names.count("devdag.launch") == rounds
