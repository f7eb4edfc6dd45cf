"""Serving front door (DESIGN.md §14): admission, batching, autoscaling,
the open-loop replayer, the unified Submission surface, the string-spec
registry, and the deprecation shims for the pre-§14 signatures."""

import numpy as np
import pytest

from repro.core import (
    AdmissionController,
    AutoscalePolicy,
    BatchPolicy,
    FeedbackLog,
    FrontDoor,
    Job,
    PipelineDAG,
    PipelineExecutor,
    PipelineServer,
    SchedulerConfig,
    Stage,
    StageDep,
    Submission,
    TokenBucket,
    batch_signature,
    coalesce_submissions,
    heavy_tailed_trace,
    make,
    make_config,
    make_placement,
    merge_dags,
    replay_open_loop,
    simulate_dag,
)
from repro.core.admission import BATCH_SEP


def _two_stage(offset=0, n=32, deadline=None, **kw):
    a = Stage("a", n, lambda i, s, z: np.arange(s, s + z, dtype=np.int64) + offset,
              combine="concat")
    b = Stage("b", n, lambda i, s, z: int(i["a"][s:s + z].sum()),
              combine="sum", deps=(StageDep("a", "elementwise"),))
    costs = {"a": np.full(n, 1e-5), "b": np.full(n, 1e-5)}
    return Submission(dag=PipelineDAG([a, b]), deadline_s=deadline,
                      stage_costs=costs, **kw)


# ---------------------------------------------------------------------------
# token bucket + admission edge cases
# ---------------------------------------------------------------------------

def test_token_bucket_refills_over_time():
    tb = TokenBucket(rate=10.0, capacity=2)
    assert tb.take(0.0) and tb.take(0.0)
    assert not tb.take(0.0)            # burst exhausted
    assert tb.take(0.1)                # 0.1s * 10/s = 1 token back
    assert not tb.take(0.1)
    assert tb.take(10.0) and tb.take(10.0)   # refill caps at capacity
    assert not tb.take(10.0)


def test_zero_capacity_bucket_admits_nothing():
    tb = TokenBucket(rate=100.0, capacity=0)
    assert not tb.take(0.0)
    assert not tb.take(1e9)            # rate never matters at capacity 0
    adm = AdmissionController(buckets={"t": TokenBucket(rate=5.0, capacity=0)})
    sub = _two_stage(name="j", tenant="t")
    dec = adm.decide(sub.to_job(), 0.0, 0.0, 4)
    assert not dec.admitted and dec.reason == "throttled"


def test_deadline_already_past_at_arrival_is_expired():
    adm = AdmissionController()
    sub = _two_stage(name="late", deadline=0.0)   # expired the moment it lands
    dec = adm.decide(sub.to_job(), sub.arrival_s, 0.0, 4)
    assert not dec.admitted and dec.reason == "expired"
    # a batching delay can also expire a positive deadline
    sub2 = _two_stage(name="late2", deadline=0.5)
    dec2 = adm.decide(sub2.to_job(), sub2.arrival_s + 0.5, 0.0, 4)
    assert not dec2.admitted and dec2.reason == "expired"


def test_no_slack_shed_uses_live_backlog():
    adm = AdmissionController()
    sub = _two_stage(name="tight", deadline=1e-3)   # service 64e-5 over 1 worker
    assert adm.decide(sub.to_job(), 0.0, 0.0, 1).admitted
    dec = adm.decide(sub.to_job(), 0.0, backlog_s=1.0, active_workers=1)
    assert not dec.admitted and dec.reason == "no_slack"


def test_admission_estimates_from_feedback_log():
    from repro.core import ChunkObservation

    fb = FeedbackLog()
    for i in range(16):   # observed rate: 1e-3 s/row, far above declared costs
        fb.record(ChunkObservation("a", i, i, 1, 1e-3, 0, 0.0))
        fb.record(ChunkObservation("b", i, i, 1, 1e-3, 0, 0.0))
    blind = AdmissionController()
    informed = AdmissionController(feedback=fb)
    sub = _two_stage(name="j", deadline=None)
    job = sub.to_job()
    assert informed.estimate_service_s(job) > blind.estimate_service_s(job) * 10


def test_all_jobs_shed_trace():
    subs = [_two_stage(name=f"j{i}", arrival_s=i * 1e-4, deadline=0.0)
            for i in range(8)]
    res = replay_open_loop(subs, n_workers=2, admission=AdmissionController())
    assert res.n_shed == 8 and res.n_admitted == 0
    assert res.shed_rate == 1.0
    assert res.shed_reasons == {"expired": 8}
    assert res.latencies() == {}
    assert res.deadline_hit_rate() == 0.0      # every shed deadline = a miss
    assert res.latency_percentile(99.9) == 0.0


# ---------------------------------------------------------------------------
# batch coalescing
# ---------------------------------------------------------------------------

def test_batch_signature_groups_same_shape_same_tenant():
    a, b = _two_stage(offset=1, name="a"), _two_stage(offset=2, name="b")
    c = _two_stage(name="c", tenant="other")
    d = _two_stage(name="d", n=64)
    assert batch_signature(a) == batch_signature(b)   # ops may differ
    assert batch_signature(a) != batch_signature(c)   # tenant differs
    assert batch_signature(a) != batch_signature(d)   # shape differs


def test_merge_dags_members_stay_disjoint_and_correct():
    subs = [_two_stage(offset=10 * j, name=f"m{j}") for j in range(3)]
    merged = merge_dags([s.dag for s in subs])
    assert sorted(merged.stages) == sorted(
        f"{n}{BATCH_SEP}{j}" for j in range(3) for n in ("a", "b"))
    res = PipelineExecutor(merged, SchedulerConfig(n_workers=2)).run()
    for j, s in enumerate(subs):
        ref = PipelineExecutor(s.dag, SchedulerConfig(n_workers=2)).run()
        assert np.array_equal(res.values[f"a{BATCH_SEP}{j}"], ref.values["a"])
        assert res.values[f"b{BATCH_SEP}{j}"] == ref.values["b"]


def test_merge_dags_rejects_reserved_separator():
    bad = PipelineDAG([Stage(f"x{BATCH_SEP}1", 4, lambda i, s, z: None)])
    with pytest.raises(ValueError, match="reserved"):
        merge_dags([bad])


def test_coalesce_submissions_metadata():
    subs = [
        _two_stage(name="a", priority=1, arrival_s=0.0, deadline=1.0),
        _two_stage(name="b", priority=3, arrival_s=0.4, deadline=None),
        _two_stage(name="c", arrival_s=0.5, deadline=2.0),
    ]
    merged = coalesce_submissions(subs, name="batch")
    assert merged.arrival_s == 0.5                  # latest member arrival
    # tightest absolute deadline is a's 0.0+1.0, re-expressed from 0.5
    assert merged.deadline_s == pytest.approx(0.5)
    assert merged.priority == 3
    assert set(merged.stage_costs) == {
        f"{n}{BATCH_SEP}{j}" for j in range(3) for n in ("a", "b")}
    with pytest.raises(ValueError, match="tenants"):
        coalesce_submissions([_two_stage(name="x"),
                              _two_stage(name="y", tenant="t2")])
    lone = _two_stage(name="solo")
    assert coalesce_submissions([lone]) is lone


def test_host_batched_execution_bit_equal_to_unbatched():
    subs = [_two_stage(offset=100 * j, name=f"q{j}") for j in range(4)]
    merged = coalesce_submissions(subs)
    res = PipelineServer(SchedulerConfig(n_workers=2)).serve([merged])
    out = res.jobs[merged.name].values
    for j, s in enumerate(subs):
        ref = PipelineExecutor(s.dag, SchedulerConfig(n_workers=2)).run()
        assert np.array_equal(out[f"a{BATCH_SEP}{j}"], ref.values["a"])
        assert out[f"b{BATCH_SEP}{j}"] == ref.values["b"]


def test_device_batched_execution_bit_equal_to_unbatched():
    from repro.vee.apps import (linreg_device_lowering,
                                merge_device_lowerings, run_device_dag,
                                split_device_values)

    lows = [linreg_device_lowering(128, 9, tile=64, seed=s) for s in (1, 2)]
    singles = [run_device_dag(low, "SS")[0] for low in lows]
    merged = merge_device_lowerings(lows)
    vals, ddt = run_device_dag(merged, "SS")
    assert ddt.tables.shape[1] == sum(
        2 * (128 // 64) for _ in lows)            # ONE super-table, all members
    members = split_device_values(vals, len(lows))
    for j in range(len(lows)):
        for k in singles[j]:
            assert np.array_equal(members[j][k], singles[j][k]), (j, k)
    fin = merged.finalize(vals)
    for j, low in enumerate(lows):
        assert np.array_equal(fin[j], low.finalize(singles[j]))


# ---------------------------------------------------------------------------
# autoscaling
# ---------------------------------------------------------------------------

def test_autoscale_targets_stay_in_bounds():
    pol = AutoscalePolicy(min_workers=2, max_workers=8, depth_per_worker=2.0)
    assert pol.decide(4, 0, None) == 2              # idle -> floor
    assert pol.decide(4, 100, None) == 8            # deep queue -> ceiling
    assert pol.decide(4, 8, None) == 4
    assert pol.decide(4, 0, -1.0) == 6              # slack pressure: +step
    assert pol.decide(8, 0, -1.0) == 8              # never above max
    with pytest.raises(ValueError):
        AutoscalePolicy(min_workers=0, max_workers=4)
    with pytest.raises(ValueError):
        AutoscalePolicy(min_workers=4, max_workers=2)


def test_replay_autoscale_pool_varies_and_work_completes():
    trace = heavy_tailed_trace(120, seed=1, load=1.2, n_workers=4)
    res = replay_open_loop(
        trace, n_workers=4,
        autoscale=AutoscalePolicy(min_workers=1, max_workers=4,
                                  interval_s=2e-3))
    assert res.n_shed == 0
    assert len(res.latencies()) == 120              # everything completes
    sizes = {n for _, n in res.pool_timeline}
    assert sizes <= set(range(1, 5)) and len(sizes) > 1
    assert 1.0 <= res.avg_pool() <= 4.0


# ---------------------------------------------------------------------------
# the open-loop replayer + the gate property
# ---------------------------------------------------------------------------

def test_replay_open_loop_percentiles_and_accounting():
    trace = heavy_tailed_trace(300, seed=3, load=0.5, n_workers=8)
    res = replay_open_loop(trace, n_workers=8)
    assert res.n_jobs == 300 and res.n_shed == 0
    lat = list(res.latencies().values())
    assert len(lat) == 300 and all(v > 0 for v in lat)
    p50, p99, p999 = (res.latency_percentile(q) for q in (50, 99, 99.9))
    assert p50 <= p99 <= p999
    assert res.makespan_s > 0
    assert sum(res.worker_busy_s) > 0


def test_front_door_beats_fifo_baseline_on_overload():
    """Admission and batching beat no-admission FIFO on p99.9 latency and
    deadline hit-rate on an overloaded open-loop trace."""
    trace = heavy_tailed_trace(400, seed=3, load=1.5, n_workers=8)
    base = replay_open_loop(trace, n_workers=8, arbiter="fifo")
    fb = FeedbackLog()
    adm = AdmissionController(
        buckets={"etl": TokenBucket(rate=400.0, capacity=20)}, feedback=fb)
    front = replay_open_loop(trace, n_workers=8, arbiter="fair",
                             admission=adm, batching=BatchPolicy(2e-3, 8),
                             feedback=fb)
    assert front.latency_percentile(99.9) <= base.latency_percentile(99.9)
    assert front.deadline_hit_rate() >= base.deadline_hit_rate()
    assert front.n_batches > 0 and front.n_coalesced > front.n_batches


def test_replay_batching_flushes_on_window_and_size():
    mk = lambda i, t: _two_stage(name=f"j{i}", arrival_s=t)
    # 3 same-shape arrivals inside one window -> one merged engine job
    res = replay_open_loop([mk(0, 0.0), mk(1, 1e-4), mk(2, 2e-4)],
                           n_workers=2,
                           batching=BatchPolicy(window_s=5e-3, max_batch=8))
    assert res.n_batches == 1 and res.n_coalesced == 3
    batches = {m.batch for m in res.members.values()}
    assert len(batches) == 1 and None not in batches
    # max_batch=2 flushes early: 3 arrivals -> a pair plus a singleton
    res2 = replay_open_loop([mk(0, 0.0), mk(1, 1e-4), mk(2, 2e-4)],
                            n_workers=2,
                            batching=BatchPolicy(window_s=5e-3, max_batch=2))
    assert res2.n_batches == 1 and res2.n_coalesced == 2


def test_replay_trace_is_deterministic():
    trace = heavy_tailed_trace(150, seed=7, load=1.0, n_workers=4)
    a = replay_open_loop(trace, n_workers=4, admission=AdmissionController())
    b = replay_open_loop(trace, n_workers=4, admission=AdmissionController())
    assert a.latencies() == b.latencies()
    assert a.shed_reasons == b.shed_reasons


# ---------------------------------------------------------------------------
# FrontDoor: the same plan on the real pool
# ---------------------------------------------------------------------------

def test_front_door_real_pool_splits_batch_members():
    fd = FrontDoor(SchedulerConfig(n_workers=2),
                   admission=AdmissionController(),
                   batching=BatchPolicy(window_s=5e-3, max_batch=4))
    subs = [_two_stage(offset=10 * j, name=f"m{j}", arrival_s=1e-4 * j)
            for j in range(3)]
    subs.append(_two_stage(name="late", arrival_s=0.0, deadline=0.0))
    for s in subs:
        fd.submit(s)
    res = fd.serve()
    assert res.shed == {"late": "expired"}
    assert res.n_batches == 1
    assert set(res.jobs) == {"m0", "m1", "m2"}
    for j in range(3):
        ref = PipelineExecutor(subs[j].dag, SchedulerConfig(n_workers=2)).run()
        r = res.jobs[f"m{j}"]
        assert np.array_equal(r.values["a"], ref.values["a"])
        assert r.values["b"] == ref.values["b"]
        assert r.latency_s >= 0.0


# ---------------------------------------------------------------------------
# the string-spec registry
# ---------------------------------------------------------------------------

def test_make_config_specs():
    cfg = make_config("gss/percore/rnd", n_workers=4)
    assert (cfg.technique, cfg.queue_layout, cfg.victim_strategy,
            cfg.n_workers) == ("GSS", "PERCORE", "RND", 4)
    assert make_config("mfsc").queue_layout == "CENTRALIZED"   # defaults keep
    assert make_config(("tss", "pergroup")).technique == "TSS"
    base = SchedulerConfig(technique="SS")
    assert make_config(base) is base
    assert make_config(base, n_workers=9).n_workers == 9
    for bad in ("nope", "gss/nope", "gss/percore/nope", "a/b/c/d", ""):
        with pytest.raises(ValueError):
            make_config(bad)


def test_make_placement_specs():
    pl = make_placement("device", stage_names=["a", "b"])
    assert pl.get("a").substrate == "device"
    sp = make_placement("split:0.25", stage_names=["a"]).get("a")
    assert sp.substrate == "split" and sp.device_fraction == 0.25
    keyed = make_placement("a=host, b=split:0.5")
    assert keyed.get("a").substrate == "host"
    assert keyed.get("b").device_fraction == 0.5
    assert keyed.get("unlisted").substrate == "host"
    with pytest.raises(ValueError):
        make_placement("split")                    # fraction required
    with pytest.raises(ValueError):
        make_placement("device")                   # uniform needs names


def test_registry_dispatch():
    assert make("config", "ss").technique == "SS"
    assert type(make("arbiter", "fifo")).__name__ == "FifoArbiter"
    with pytest.raises(ValueError, match="unknown registry kind"):
        make("scheduler", "x")


# ---------------------------------------------------------------------------
# the unified Submission surface
# ---------------------------------------------------------------------------

def test_submission_roundtrip_and_validation():
    sub = _two_stage(name="j", tenant="t", priority=2, deadline=1.0)
    job = sub.to_job()
    assert isinstance(job, Job)
    assert (job.name, job.tenant, job.priority, job.deadline_s) == \
        ("j", "t", 2, 1.0)
    with pytest.raises(ValueError, match="no dag"):
        Submission(name="empty").to_job()
    with pytest.raises(ValueError, match="weight"):
        Submission(weight=0.0)
    with pytest.raises(ValueError, match="deadline"):
        Submission(deadline_s=-1.0)


def test_submission_accepted_by_every_surface():
    sub = _two_stage(name="u")
    r1 = PipelineExecutor(sub.dag, SchedulerConfig(n_workers=2)).run(sub)
    srv = PipelineServer(SchedulerConfig(n_workers=2))
    srv.submit(sub)
    r2 = srv.serve()
    assert np.array_equal(r1.values["a"], r2.jobs["u"].values["a"])
    from repro.core import simulate_server

    r3 = simulate_server([sub], n_workers=2)       # Submissions: no warning
    assert "u" in r3.job_finish


def test_retired_shims_fail_loudly():
    """The pre-§14 grace period is over: Job records are rejected on the
    public surfaces (with a pointer to Submission), and the retired ctor
    keywords are plain TypeErrors — not silent kwargs swallowed by **kw."""
    sub = _two_stage(name="d")
    dag, cfg = sub.dag, SchedulerConfig(n_workers=2)
    with pytest.raises(TypeError, match="per_stage"):
        PipelineExecutor(dag, cfg, per_stage={"a": ("SS", "CENTRALIZED", "SEQ")})
    with pytest.raises(TypeError, match="online"):
        PipelineExecutor(dag, cfg, online=object())
    with pytest.raises(TypeError, match="placement"):
        PipelineServer(cfg, placement={})
    with pytest.raises(TypeError, match="Submission instead"):
        PipelineServer(cfg).serve([sub.to_job()])
    with pytest.raises(TypeError, match="Submission instead"):
        PipelineServer(cfg).submit(sub.to_job())
    with pytest.raises(TypeError, match="stage_configs"):
        simulate_dag(dag, stage_costs=sub.stage_costs,
                     stage_configs=("SS", "CENTRALIZED", "SEQ"), n_workers=2)


def test_hetero_submission_override():
    from repro.core import HeteroExecutor, Placement
    from repro.vee.apps import linreg_device_lowering

    low = linreg_device_lowering(128, 9, tile=64)
    cfg = SchedulerConfig(technique="SS", n_workers=1)
    host = Placement.all_host(low.dag.stage_names)
    ref = PipelineExecutor(low.dag, cfg).run()
    ex = HeteroExecutor(low.dag, cfg, host)
    res = ex.run(Submission(
        placement=make_placement("moments=device", low.dag.stage_names)))
    for k in ref.values:
        assert np.array_equal(np.asarray(ref.values[k]),
                              np.asarray(res.values[k]))
