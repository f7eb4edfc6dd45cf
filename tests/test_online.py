"""Online adaptive scheduling (core/online.py, DESIGN.md §12).

Convergence properties run through the deterministic virtual-time replay
(simulate_dag / replay_online_dag), so the bandit guarantees are exact:
on a stationary workload the selector must land within tolerance of the
best static technique and can never do worse than the worst static
technique. Real-pool tests assert the feedback loop never corrupts
results (exactly-once row coverage survives moldable resizing).
"""

import numpy as np
import pytest

from repro.core import (
    ChunkObservation,
    FeedbackLog,
    OnlineScheduler,
    PipelineDAG,
    PipelineExecutor,
    PipelineServer,
    Job,
    SchedulerConfig,
    ScheduledExecutor,
    Stage,
    StageDep,
    Submission,
    as_submission,
    chunk_schedule,
    default_online_arms,
    replay_online_dag,
    simulate_dag,
    tasks_from_schedule,
    tune_online_dag,
)
from repro.core.online import rechunk_pending


def _hot_stage_dag(n=512):
    return PipelineDAG([Stage("hot", n, lambda i, s, z: None)])


def _skewed_costs(n=512, seed=3):
    rng = np.random.default_rng(seed)
    return rng.pareto(1.3, n) * 2e-6 + 1e-7


def _static_makespans(dag, costs, arms, n_workers=4):
    return {c: simulate_dag(dag, costs, c, n_workers=n_workers).makespan
            for c in arms}


# ---------------------------------------------------------------------------
# arm space
# ---------------------------------------------------------------------------

def test_default_arms_cover_partitioners_x_layouts():
    arms = default_online_arms()
    assert len(arms) == 11 * 3  # 11 partitioners x 3 assignment layouts
    assert len(set(arms)) == len(arms)
    assert len(default_online_arms(include_ss=False)) == 10 * 3


def test_rechunk_pending_preserves_row_coverage():
    rng = np.random.default_rng(0)
    for _ in range(20):
        # random possibly non-contiguous pending chunks
        starts = sorted(rng.choice(1000, size=6, replace=False))
        pending = [(int(s), int(rng.integers(1, 40))) for s in starts]
        # drop overlaps by spacing starts far enough apart
        pending = [(s, min(z, 30)) for s, z in pending]
        target = int(rng.integers(1, 50))
        out = rechunk_pending(pending, target)
        rows_in = sorted(r for s, z in pending for r in range(s, s + z))
        rows_out = sorted(r for s, z in out for r in range(s, s + z))
        assert rows_in == rows_out
        assert all(z >= 1 for _, z in out)
        assert max((z for _, z in out), default=0) <= max(target,
                                                          max(z for _, z in pending))


# ---------------------------------------------------------------------------
# bandit convergence (the ISSUE's property test, deterministic via replay)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("selector", ["ucb", "exp3"])
def test_bandit_bounded_by_static_extremes(selector):
    """On a stationary workload: every round's makespan is bounded by the
    worst static technique, and the converged choice lands within
    tolerance of the best static technique."""
    dag = _hot_stage_dag(512)
    costs = {"hot": _skewed_costs(512)}
    arms = default_online_arms()
    statics = _static_makespans(dag, costs, arms, n_workers=4)
    best_s, worst_s = min(statics.values()), max(statics.values())

    online = OnlineScheduler(selector=selector, arms=arms, resize=False, seed=0)
    rounds = len(arms) + 12
    history = replay_online_dag(dag, costs, online, rounds=rounds, n_workers=4)

    # never worse than the worst static technique (stationary + resize off:
    # each round IS some static technique)
    for r in history:
        assert r.makespan <= worst_s * (1 + 1e-9)
    # converged assignment within tolerance of the best static technique
    final = simulate_dag(dag, costs, online.best_combos(["hot"]),
                         n_workers=4).makespan
    assert final <= best_s * 1.05


def test_ucb_converges_exactly_after_full_exploration():
    """UCB plays every arm once; with deterministic rewards its best arm
    is exactly the static argmin."""
    dag = _hot_stage_dag(256)
    costs = {"hot": _skewed_costs(256, seed=9)}
    arms = default_online_arms(include_ss=False)
    statics = _static_makespans(dag, costs, arms, n_workers=4)
    online = OnlineScheduler(selector="ucb", arms=arms, resize=False, seed=0)
    replay_online_dag(dag, costs, online, rounds=len(arms), n_workers=4)
    best = online.best_combos(["hot"])["hot"]
    assert statics[best] == min(statics.values())


@pytest.mark.parametrize("selector", ["ucb", "exp3"])
def test_replay_deterministic(selector):
    dag = _hot_stage_dag(256)
    costs = {"hot": _skewed_costs(256, seed=5)}

    def run():
        online = OnlineScheduler(selector=selector, seed=7)
        hist = replay_online_dag(dag, costs, online, rounds=12, n_workers=4)
        return [(tuple(sorted(r.combos.items())), r.makespan) for r in hist]

    assert run() == run()


def test_tune_online_dag_multi_stage_near_offline():
    """The autotune entry point: online lands within 1.10x of the offline
    per-stage search on the linreg-shaped workload."""
    from repro.core import select_offline_dag

    n = 1024
    rng = np.random.default_rng(11)
    dag = PipelineDAG([
        Stage("a", n, lambda i, s, z: None),
        Stage("b", n, lambda i, s, z: None, combine="sum",
              deps=(StageDep("a", "elementwise"),)),
    ])
    costs = {"a": rng.pareto(1.5, n) * 1e-7 + 2e-8, "b": np.full(n, 3e-7)}
    _, offline_ms, uniform = select_offline_dag(dag, costs, n_workers=8,
                                                passes=1)
    res = tune_online_dag(dag, costs, n_workers=8, rounds=40, seed=0)
    assert res.makespan <= offline_ms * 1.10
    statics = sorted(uniform.values())
    assert res.makespan <= statics[len(statics) // 2]  # beats the median
    assert len(res.history) == 40


# ---------------------------------------------------------------------------
# moldable chunk resizing (virtual time)
# ---------------------------------------------------------------------------

def test_resize_split_rescues_hot_tail():
    """Increasing techniques drop their biggest chunks on the hot tail;
    the resizer must split the remainder and beat the static run."""
    n = 4096
    rng = np.random.default_rng(7)
    c = np.full(n, 1e-7)
    c[3 * n // 4:] = rng.pareto(1.1, n // 4) * 2e-6 + 1e-7
    dag = _hot_stage_dag(n)
    for tech in ("FISS", "VISS", "TSS"):
        combo = (tech, "CENTRALIZED", "SEQ")
        base = simulate_dag(dag, {"hot": c}, combo, n_workers=8).makespan
        online = OnlineScheduler(seed=0, min_observe=2)
        resized = simulate_dag(dag, {"hot": c}, combo, n_workers=8,
                               online=online).makespan
        assert online.resizes.get("hot", 0) >= 1
        assert resized < base


def test_resize_merge_rescues_ss_dust():
    """Uniform rows under SS: the resizer coalesces chunk dust and must
    recover most of the queue-traffic blowup (the paper's P5)."""
    n = 2048
    dag = _hot_stage_dag(n)
    costs = {"hot": np.full(n, 1e-7)}
    combo = ("SS", "CENTRALIZED", "SEQ")
    base = simulate_dag(dag, costs, combo, n_workers=8).makespan
    online = OnlineScheduler(seed=0, min_observe=2)
    resized = simulate_dag(dag, costs, combo, n_workers=8,
                           online=online).makespan
    assert online.resizes.get("hot", 0) >= 1
    assert resized < base * 0.5


def test_resize_budget_respected():
    n = 4096
    c = {"hot": _skewed_costs(n, seed=1)}
    online = OnlineScheduler(seed=0, min_observe=1, max_resizes=2)
    simulate_dag(_hot_stage_dag(n), c, ("GSS", "CENTRALIZED", "SEQ"),
                 n_workers=8, online=online)
    assert online.resizes.get("hot", 0) <= 2


# ---------------------------------------------------------------------------
# real-pool integration: feedback must never corrupt results
# ---------------------------------------------------------------------------

def _aggressive_online(**kw):
    """An OnlineScheduler tuned to trigger resizes on real (jittery) costs."""
    kw.setdefault("min_observe", 1)
    kw.setdefault("cv_split", 0.0)
    kw.setdefault("max_resizes", 50)
    kw.setdefault("arms", default_online_arms(include_ss=False))
    return OnlineScheduler(**kw)


def test_executor_online_rounds_stay_correct():
    """PipelineExecutor under the loop with forced resizing: values match
    the serial oracle every round and realized schedules stay exact."""
    from repro.vee.apps import linreg_dag, linear_regression_oracle

    n = 512
    dag, finalize = linreg_dag(n, 6, seed=1)
    online = _aggressive_online(seed=0)
    oracle = linear_regression_oracle(n, 6, seed=1)
    for layout_pin in (None, {"moments": ("MFSC", "PERCORE", "SEQ")}):
        for _ in range(3):
            res = PipelineExecutor(dag, SchedulerConfig(n_workers=4)).run(
                Submission(per_stage=layout_pin, online=online))
            assert np.allclose(finalize(res.values), oracle)
            for name, sr in res.stages.items():
                # realized schedule covers the stage exactly once
                assert sr.schedule[:, 1].sum() == dag.stages[name].n_rows
                assert len(sr.per_task_costs) == len(sr.schedule)


def test_executor_online_honours_stage_config_pin():
    """A Stage.config pin must win over the bandit (as in the server)."""
    n = 256
    pinned = Stage("pinned", n, lambda i, s, z: np.arange(s, s + z),
                   config=SchedulerConfig(technique="GSS",
                                          queue_layout="CENTRALIZED"))
    free = Stage("free", n, lambda i, s, z: float(z), combine="sum",
                 deps=(StageDep("pinned", "elementwise"),))
    dag = PipelineDAG([pinned, free])
    online = OnlineScheduler(seed=0, resize=False)
    res = PipelineExecutor(dag, SchedulerConfig(n_workers=2)).run(
        Submission(online=online))
    assert res.stages["pinned"].config.technique == "GSS"
    assert online.selector_for("pinned").counts.sum() == 0  # never consulted
    assert online.selector_for("free").counts.sum() == 1


def test_executor_online_resizes_fire_and_learn():
    from repro.vee.apps import recommendation_oracle, recommendation_online

    top, history, online = recommendation_online(
        512, 16, SchedulerConfig(n_workers=4), rounds=3, seed=0,
        online=_aggressive_online(seed=0))
    assert np.array_equal(top, recommendation_oracle(512, 16, seed=0))
    # every stage's bandit was consulted and credited each round
    for stage in ("item_norms", "user_bias", "scores"):
        assert online.selector_for(stage).counts.sum() == 3


def test_server_online_lazy_build_and_correctness():
    """PipelineServer under the loop: stage runs build lazily per job, the
    selector is consulted per (job, stage), results stay exact, and
    explicitly pinned stages are never overridden."""
    n = 256
    oracle_prop = np.arange(n, dtype=np.int64)

    def make_job(name, arrival, pin=False):
        prop = Stage("prop", n,
                     lambda i, s, z: np.arange(s, s + z, dtype=np.int64))
        chk = Stage("chk", n,
                    lambda i, s, z: int(i["prop"][s:s + z].sum()),
                    combine="sum", deps=(StageDep("prop", "elementwise"),))
        red = Stage("red", 16, lambda i, s, z: float(z), combine="sum",
                    deps=(StageDep("prop", "full"),))
        per = {"prop": ("STATIC", "CENTRALIZED", "SEQ")} if pin else None
        return Job(name, PipelineDAG([prop, chk, red]), arrival_s=arrival,
                   per_stage=per)

    online = _aggressive_online(seed=0)
    srv = PipelineServer(SchedulerConfig(n_workers=4), arbiter="fair",
                         online=online)
    jobs = [make_job("j0", 0.0), make_job("j1", 0.001),
            make_job("pinned", 0.002, pin=True)]
    res = srv.serve([as_submission(j) for j in jobs])
    for name in ("j0", "j1", "pinned"):
        jr = res.jobs[name]
        assert np.array_equal(jr.values["prop"], oracle_prop)
        assert jr.values["chk"] == int(oracle_prop.sum())
        assert jr.values["red"] == 16.0
        assert jr.finish_s >= jr.arrival_s
    # unpinned stages consulted the bandit for both unpinned jobs; the
    # pinned job consulted it only for its unpinned stages
    assert online.selector_for("prop").counts.sum() == 2
    assert online.selector_for("chk").counts.sum() == 3
    assert online.selector_for("red").counts.sum() == 3


def test_server_online_empty_job_completes():
    dag = PipelineDAG([Stage("z", 0, lambda i, s, z: None)])
    res = PipelineServer(SchedulerConfig(n_workers=2),
                         online=OnlineScheduler(seed=1)).serve(
        [as_submission(Job("empty", dag))])
    assert res.jobs["empty"].finish_s == 0.0


def test_scheduled_executor_observer_streams_all_tasks():
    """The flat executor's record path feeds every completed task to the
    observer (the ISSUE's executor.py hook)."""
    n = 200
    sched = chunk_schedule("MFSC", n, 4)
    tasks = tasks_from_schedule(sched, lambda s, z: z)
    log = FeedbackLog()
    cfg = SchedulerConfig(technique="MFSC", queue_layout="PERCORE", n_workers=4)
    results, _ = ScheduledExecutor(cfg, observer=log,
                                   observer_stage="flat").run(tasks)
    assert len(results) == len(tasks)
    fb = log.stage("flat")
    assert fb is not None
    assert fb.n == len(tasks)
    assert fb.rows == n


def test_feedback_cv_separates_uniform_from_skewed():
    log = FeedbackLog()
    for i in range(32):
        log.record(ChunkObservation("uniform", i, i * 8, 8, 8e-6))
        log.record(ChunkObservation("skewed", i, i * 8, 8,
                                    8e-6 * (10.0 if i % 8 == 0 else 0.1)))
    assert log.stage("uniform").cv < 0.05
    assert log.stage("skewed").cv > 0.5
