"""Heterogeneous placement & co-execution (DESIGN.md §13).

Covers the tentpole invariants:

  * ``select_placement`` never loses to min(host-only, device-only) and
    strictly beats both homogeneous placements on a transfer-heavy DAG
    with opposite per-stage substrate affinities;
  * ``simulate_hetero_dag`` transfer/queue-wait accounting reconciles
    (events sum to ``transfer_s``; single-lane makespans are exact);
  * ``HeteroExecutor`` is bit-equal to the host-only PipelineExecutor on
    the vee linreg + recommendation lowerings under HOST/DEVICE/SPLIT
    placements, with cross-substrate rebalancing exercised both ways;
  * ``calibrate_hetero_costs`` folds FeedbackLog rates and frozen-replay
    overheads into the per-substrate rates;
  * ``PipelineServer(placement=...)`` routes device-placed stages to the
    walker lanes under contention without corrupting results;
  * ``tune_online_hetero`` (bandit arms extended with substrate choice)
    converges onto a mixed placement on the affinity workload.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    HeteroCostModel,
    HeteroExecutor,
    Job,
    OnlineScheduler,
    PipelineDAG,
    PipelineExecutor,
    PipelineServer,
    Placement,
    SchedulerConfig,
    Stage,
    StageDep,
    StagePlacement,
    Submission,
    TransferModel,
    calibrate_hetero_costs,
    select_offline_hetero,
    select_placement,
    simulate_hetero_dag,
    tune_online_hetero,
)
from repro.core.placement import DEVICE, HOST, SPLIT


def _op(inputs, s, z):
    return np.zeros(z)


def _affinity_dag(n=2048):
    """ingest -> (featurize | embed) -> join with opposite affinities
    (the shared §13 demo workload — also the example's)."""
    from repro.vee.apps import hetero_affinity_dag

    return hetero_affinity_dag(n)


# ---------------------------------------------------------------------------
# placement model + solver
# ---------------------------------------------------------------------------

def test_stage_placement_validation():
    with pytest.raises(ValueError, match="substrate"):
        StagePlacement("gpu")
    with pytest.raises(ValueError, match="device_fraction"):
        StagePlacement(SPLIT, 1.0)
    assert StagePlacement(HOST).device_rows(100) == 0
    assert StagePlacement(DEVICE).device_rows(100) == 100
    assert StagePlacement(SPLIT, 0.25).device_rows(100) == 25
    # SPLIT always leaves both substrates at least one row
    assert StagePlacement(SPLIT, 0.001).device_rows(4) == 1
    assert StagePlacement(SPLIT, 0.999).device_rows(4) == 3


def test_solver_never_worse_than_homogeneous_and_mixed_wins():
    dag, costs = _affinity_dag()
    placement, ms, base = select_placement(dag, costs, n_workers=8, passes=2)
    assert ms <= min(base.values()) + 1e-12
    # opposite affinities + transfer awareness: the mixed placement must
    # STRICTLY beat both homogeneous runs
    assert ms < base["host"]
    assert ms < base["device"]
    subs = {p.substrate for p in placement.stages.values()}
    assert len(subs) > 1, "solver should mix substrates on this workload"


def test_select_offline_hetero_wraps_solver():
    dag, costs = _affinity_dag(512)
    placement, ms, base = select_offline_hetero(dag, costs, n_workers=4,
                                                passes=1)
    assert ms <= min(base.values()) + 1e-12
    assert set(base) == {"host", "device"}


def test_solver_prefers_resident_branches_under_heavy_transfer():
    """With a prohibitive transfer term every stage stays on one side."""
    dag, costs = _affinity_dag(512)
    expensive = HeteroCostModel(
        host=costs.host, device=costs.device,
        transfer=TransferModel(latency_s=1.0, bytes_per_row=1e6,
                               gb_per_s=1e-3))
    placement, ms, base = select_placement(dag, expensive, n_workers=8)
    subs = {p.substrate for p in placement.stages.values()}
    assert subs == {HOST} or subs == {DEVICE}
    assert ms == pytest.approx(min(base.values()))


# ---------------------------------------------------------------------------
# virtual-time co-execution: transfer + queue-wait accounting reconciles
# ---------------------------------------------------------------------------

def test_hetero_sim_transfer_accounting_reconciles():
    dag, costs = _affinity_dag(512)
    pl = Placement({"ingest": StagePlacement(HOST),
                    "featurize": StagePlacement(HOST),
                    "embed": StagePlacement(DEVICE),
                    "join": StagePlacement(HOST)})
    res = simulate_hetero_dag(dag, costs, pl, n_workers=4)
    # every transfer event is accounted exactly once in the totals
    assert res.transfer_s == pytest.approx(
        sum(ev.t_end - ev.t_start for ev in res.transfer_events))
    assert res.transfer_s == pytest.approx(res.stats.total_transfer_s)
    assert sum(res.stats.transfers.values()) == len(res.transfer_events)
    assert res.transfer_s > 0  # the boundary was actually crossed
    # busy time reconciles with executed chunk time
    assert sum(res.per_worker_busy) == pytest.approx(res.stats.total_exec_s)
    assert res.queue_wait == pytest.approx(res.stats.total_queue_wait_s)
    # makespan bounds: no lane outlives it; the work had to fit in it
    assert res.makespan >= max(res.per_worker_busy) - 1e-12
    lanes = len(res.per_worker_busy)
    assert res.makespan >= (res.stats.total_exec_s / lanes) - 1e-12
    assert max(res.stage_finish.values()) == pytest.approx(res.makespan)


def test_hetero_sim_all_host_single_worker_is_exact():
    """One host lane, no device work: makespan == exec + per-chunk holds."""
    n = 64
    dag = PipelineDAG([Stage("a", n, _op, combine="concat")])
    costs = {"a": np.full(n, 1e-6)}
    from repro.core import SimOverheads
    ov = SimOverheads()
    res = simulate_hetero_dag(dag, costs, Placement.all_host(["a"]),
                              stage_configs=("STATIC", "CENTRALIZED", "SEQ"),
                              n_workers=1, overheads=ov)
    expect = res.stats.total_exec_s + res.stats.total_chunks * ov.h_access
    assert res.makespan == pytest.approx(expect)
    assert res.transfer_s == 0.0


def test_hetero_sim_elementwise_streams_across_boundary():
    """A host consumer starts before its device producer finishes."""
    n = 1024
    dag = PipelineDAG([
        Stage("produce", n, _op, combine="concat"),
        Stage("consume", n, _op, combine="concat",
              deps=(StageDep("produce", "elementwise"),)),
    ])
    costs = HeteroCostModel(
        host={"produce": np.full(n, 1e-6), "consume": np.full(n, 1e-6)},
        device={"produce": np.full(n, 1e-6), "consume": np.full(n, 1e-6)},
        transfer=TransferModel(latency_s=1e-6, bytes_per_row=1.0))
    pl = Placement({"produce": StagePlacement(DEVICE),
                    "consume": StagePlacement(HOST)})
    res = simulate_hetero_dag(dag, costs, pl, n_workers=4,
                              stage_configs=("GSS", "CENTRALIZED", "SEQ"))
    assert res.stage_start["consume"] < res.stage_finish["produce"], \
        "elementwise consumer should overlap its cross-substrate producer"
    assert res.transfer_s > 0


# ---------------------------------------------------------------------------
# real co-execution: bit-equality + cross-substrate rebalancing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("placement_of", [
    lambda names: Placement.all_device(names),
    lambda names: Placement({n: StagePlacement(SPLIT, 0.5) for n in names}),
    lambda names: Placement({"moments": StagePlacement(DEVICE),
                             "syrk_gemv": StagePlacement(HOST)}),
])
def test_hetero_executor_linreg_bitwise(placement_of):
    from repro.vee.apps import linreg_device_lowering

    low = linreg_device_lowering(512, 9, tile=64, seed=1)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    het = HeteroExecutor(low.dag, SchedulerConfig(technique="SS", n_workers=2),
                         placement_of(low.dag.stage_names), n_device=2).run()
    for k in host.values:
        assert np.array_equal(np.asarray(host.values[k]),
                              np.asarray(het.values[k])), k


def test_hetero_executor_recommendation_bitwise_and_stats():
    from repro.vee.apps import recommendation_device_lowering

    low = recommendation_device_lowering(256, 32, tile=32, seed=0)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    pl = Placement({"item_norms": StagePlacement(DEVICE),
                    "user_bias": StagePlacement(SPLIT, 0.5),
                    "scores": StagePlacement(HOST)})
    het = HeteroExecutor(low.dag, SchedulerConfig(technique="SS",
                                                  n_workers=2), pl).run()
    for k in host.values:
        assert np.array_equal(np.asarray(host.values[k]),
                              np.asarray(het.values[k])), k
    # host-side accounting: every executed chunk shows up in the stats
    stats = het.stats
    assert stats.total_chunks == len(het.events)
    assert stats.total_exec_s == pytest.approx(
        sum(e.t_end - e.t_start for e in het.events))
    # scores consumed item_norms (all device rows) from the host side
    assert sum(het.cross_consumptions.values()) > 0
    assert sum(stats.transfers.values()) >= sum(
        het.cross_consumptions.values())


def test_hetero_executor_rebalances_both_ways():
    from repro.vee.apps import linreg_device_lowering

    low = linreg_device_lowering(1024, 9, tile=64, seed=2)
    # all rows on device + several idle host workers: the host MUST absorb
    het = HeteroExecutor(low.dag,
                         SchedulerConfig(technique="SS", n_workers=3),
                         Placement.all_device(low.dag.stage_names)).run()
    assert het.absorbed_by_host > 0
    host_lanes = {e.worker for e in het.events if e.worker < 3}
    assert host_lanes, "idle host workers should have absorbed device tail"
    # all rows on host + an idle device lane: the device lane absorbs
    het2 = HeteroExecutor(low.dag,
                          SchedulerConfig(technique="SS", n_workers=1),
                          Placement.all_host(low.dag.stage_names)).run()
    assert het2.absorbed_by_device > 0
    # disabling rebalance pins every chunk to its placed substrate
    het3 = HeteroExecutor(low.dag,
                          SchedulerConfig(technique="SS", n_workers=2),
                          Placement.all_device(low.dag.stage_names),
                          rebalance=False).run()
    assert het3.absorbed_by_host == 0 and het3.absorbed_by_device == 0
    assert all(e.worker >= 2 for e in het3.events)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    for res in (het, het2, het3):
        for k in host.values:
            assert np.array_equal(np.asarray(host.values[k]),
                                  np.asarray(res.values[k])), k


@settings(max_examples=10, deadline=None)
@given(frac=st.floats(0.1, 0.9), n_device=st.integers(1, 3),
       n_workers=st.integers(1, 3))
def test_hetero_executor_split_fraction_property(frac, n_device, n_workers):
    """Any split fraction / lane count reproduces the host-only values."""
    from repro.vee.apps import recommendation_device_lowering

    low = recommendation_device_lowering(128, 16, tile=16, seed=3)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    pl = Placement({n: StagePlacement(SPLIT, frac)
                    for n in low.dag.stage_names})
    het = HeteroExecutor(low.dag,
                         SchedulerConfig(technique="SS", n_workers=n_workers),
                         pl, n_device=n_device).run()
    for k in host.values:
        assert np.array_equal(np.asarray(host.values[k]),
                              np.asarray(het.values[k])), k


# ---------------------------------------------------------------------------
# calibration, serving integration, online substrate bandit
# ---------------------------------------------------------------------------

def test_calibrate_from_feedback_and_frozen_replay():
    from repro.core import ChunkObservation, FeedbackLog, SimOverheads

    n = 64
    dag = PipelineDAG([Stage("a", n, _op, combine="concat")])
    fb = FeedbackLog()
    for i in range(8):
        fb.record(ChunkObservation("a", i, i * 8, 8, 8 * 2e-6))
    cm = calibrate_hetero_costs(dag, feedback=fb, device_speedup=4.0)
    assert cm.host["a"][0] == pytest.approx(2e-6, rel=1e-6)
    # device rate folds the frozen replay's launch + table-step overheads
    ov = SimOverheads()
    expect = (ov.h_launch + n * (ov.h_local + 2e-6 / 4.0)) / n
    assert cm.device["a"][0] == pytest.approx(expect, rel=1e-6)
    # explicit vectors always win
    cm2 = calibrate_hetero_costs(
        dag, feedback=fb, host_costs={"a": np.full(n, 7.0)},
        device_costs={"a": np.full(n, 9.0)})
    assert cm2.host["a"][0] == 7.0 and cm2.device["a"][0] == 9.0


def test_server_placement_routes_to_device_lanes():
    from repro.vee.apps import recommendation_device_lowering

    low = recommendation_device_lowering(128, 16, tile=16, seed=0)
    ref = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    subs = [Submission(name="placed", dag=low.dag, tenant="a",
                       placement=Placement.all_device(low.dag.stage_names)),
            Submission(name="hostonly", dag=low.dag, tenant="b")]
    srv = PipelineServer(
        SchedulerConfig(technique="SS", n_workers=2), arbiter="fair",
        n_device=1)
    res = srv.serve(subs)
    for jname in ("placed", "hostonly"):
        for k in ref.values:
            got = np.asarray(res.jobs[jname].values[k], dtype=float)
            want = np.asarray(ref.values[k], dtype=float)
            assert np.allclose(got, want, atol=1e-3), (jname, k)
    # the walker lane (id == n_workers) served the placed job
    dev_events = [e for e in res.events if e.worker >= 2]
    assert any(e.job == "placed" for e in dev_events)


def test_tune_online_hetero_finds_mixed_placement():
    dag, costs = _affinity_dag()
    res = tune_online_hetero(dag, costs, n_workers=8, rounds=160, seed=0)
    subs = {arm[3] for arm in res.assign.values()}
    assert subs == {"host", "device"}, res.assign
    assert res.assign["embed"][3] == "device"
    _, _, base = select_placement(dag, costs, n_workers=8, passes=1)
    assert res.makespan <= min(base.values()) * 1.05


def test_hetero_executor_percore_layout_with_absorption():
    """Walker lanes absorbing host chunks under distributed layouts must
    not die on victim indexing (lane ids exceed the host pool): the run
    stays bit-equal and every lane survives to completion."""
    from repro.vee.apps import recommendation_device_lowering

    low = recommendation_device_lowering(128, 16, tile=16, seed=1)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    for layout in ("PERCORE", "PERGROUP"):
        cfg = SchedulerConfig(technique="SS", queue_layout=layout,
                              n_workers=2, numa_domains=[0, 1])
        het = HeteroExecutor(
            low.dag, cfg,
            Placement({n: StagePlacement(SPLIT, 0.5)
                       for n in low.dag.stage_names}),
            n_device=2).run()
        for k in host.values:
            assert np.array_equal(np.asarray(host.values[k]),
                                  np.asarray(het.values[k])), (layout, k)
        # every chunk was recorded — no lane died mid-run
        assert sum(het.per_worker_tasks) == len(het.events)


def test_server_placement_percore_layout():
    """Server walker lanes under PERCORE must survive host absorption."""
    from repro.vee.apps import recommendation_device_lowering

    low = recommendation_device_lowering(128, 16, tile=16, seed=2)
    srv = PipelineServer(
        SchedulerConfig(technique="SS", queue_layout="PERCORE", n_workers=2),
        n_device=2)
    res = srv.serve([Submission(
        name="j", dag=low.dag, tenant="a",
        placement=Placement.all_device(low.dag.stage_names))])
    ref = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    for k in ref.values:
        assert np.allclose(np.asarray(res.jobs["j"].values[k], dtype=float),
                           np.asarray(ref.values[k], dtype=float), atol=1e-3)


def test_hetero_executor_surfaces_worker_errors():
    """A lane failing ANYWHERE (not just inside a stage op) must raise
    from run(), never return a half-built result from dead threads."""

    def boom(inputs, s, z):
        raise RuntimeError("stage exploded")

    dag = PipelineDAG([Stage("a", 8, boom, combine="concat")])
    with pytest.raises(RuntimeError, match="stage exploded"):
        HeteroExecutor(dag, SchedulerConfig(technique="SS", n_workers=2),
                       Placement({"a": StagePlacement(SPLIT, 0.5)})).run()


def test_online_scheduler_accepts_hetero_arms():
    from repro.core import default_hetero_arms

    arms = default_hetero_arms(include_ss=False)
    assert all(len(a) == 4 for a in arms)
    assert {a[3] for a in arms} == {"host", "device"}
    on = OnlineScheduler(arms=arms, resize=False, seed=0)
    ch = on.suggest("s0")
    assert ch.combo in arms
    on.observe(ch, 1.0)
    assert on.best_combos(["s0"])["s0"] == ch.combo
