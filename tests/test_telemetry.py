"""Unified scheduler telemetry tests (core/telemetry.py, DESIGN.md §18).

The span invariants, property-tested over random DAG shapes x techniques
x queue layouts x queue implementations:

  * every executed chunk gets exactly ONE exec span, identity-matched
    (stage, chunk) against the independent TaskEvent timeline;
  * nesting holds — every exec span (including its preceding queue wait)
    sits inside its synthesized stage span, and every span inside its
    job span (no span outlives its job);
  * the Chrome-trace export of every run passes schema validation.

Plus: critical-path attribution telescoping to the measured makespan and
reconciling against DagStats on BOTH the real pool and simulate_dag
replays; the slot-vs-deque queue-wait differential (the wait_s
reconciliation fix); the uniform TransferEvent/PreemptionEvent result
surfaces; the device-DAG path's ``devdag.*`` phases (tracer rows, the
profiler's host plane, the Chrome export) and the compile-path counters;
and the MetricsRegistry (memoization, Prometheus exposition, drain-time
collectors over the queues' uniform ``counters()`` API).
"""

import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    DEP_ELEMENTWISE,
    DEP_FULL,
    NULL_TRACER,
    HeteroExecutor,
    MetricsRegistry,
    NullTracer,
    PipelineDAG,
    PipelineExecutor,
    PipelineServer,
    PreemptiveRunner,
    SchedulerConfig,
    Stage,
    StageDep,
    Submission,
    Tracer,
    analyze_critical_path,
    as_tracer,
    collect_compile_metrics,
    collect_queue_metrics,
    compile_counts,
    simulate_dag,
    validate_chrome_trace,
)
from repro.core.queues import (
    CentralizedQueue,
    DistributedQueues,
    SlotCentralizedQueue,
    SlotDistributedQueues,
)
from repro.core.telemetry import DEVDAG_PHASES, F_DEVICE, WORK_KINDS

TECHS = ["STATIC", "SS", "MFSC", "GSS", "FAC2", "TSS"]
LAYOUTS = ["CENTRALIZED", "PERCORE", "PERGROUP"]
IMPLS = ["deque", "slot"]
EPS = 1e-9


def _chain_dag(n, n_stages, full_deps):
    """A linear pipeline: concat source then n_stages-1 row-wise consumers,
    each edge elementwise or full per ``full_deps``."""
    stages = [Stage("s0", n,
                    lambda i, s, z: np.arange(s, s + z, dtype=np.int64),
                    combine="concat")]
    for k in range(1, n_stages):
        prev = f"s{k - 1}"
        kind = DEP_FULL if full_deps[k - 1] else DEP_ELEMENTWISE
        if kind == DEP_ELEMENTWISE:
            fn = (lambda i, s, z, p=prev: i[p][s:s + z] + 1)
        else:
            fn = (lambda i, s, z, p=prev: i[p][:1] + np.arange(z))
        stages.append(Stage(f"s{k}", n, fn, combine="concat",
                            deps=(StageDep(prev, kind),)))
    return PipelineDAG(stages)


def _costs(dag, seed=0):
    rng = np.random.default_rng(seed)
    return {name: rng.uniform(1.0, 3.0, dag.stages[name].n_rows)
            for name in dag.order}


def _check_invariants(tracer, events):
    """The §18 span invariants shared by the host and simulated runs."""
    spans = tracer.spans()
    execs = [s for s in spans if s.kind == "exec"]
    # exactly one exec span per executed chunk, identity-matched
    want = sorted((e.stage, e.task_id) for e in events)
    got = sorted((s.stage, s.chunk) for s in execs)
    assert got == want
    stage_spans = {(s.job, s.stage): s for s in spans if s.kind == "stage"}
    job_spans = {s.job: s for s in spans if s.kind == "job"}
    for s in execs:
        parent = stage_spans[(s.job, s.stage)]
        assert parent.t0 - EPS <= s.t0 - s.wait_s
        assert s.t1 <= parent.t1 + EPS
    for s in spans:
        j = job_spans[s.job]
        assert j.t0 - EPS <= s.t0 - s.wait_s or s.kind not in WORK_KINDS
        assert s.t1 <= j.t1 + EPS, f"{s.kind} span outlives job {s.job}"
    for (job, _), p in stage_spans.items():
        j = job_spans[job]
        assert j.t0 - EPS <= p.t0 and p.t1 <= j.t1 + EPS
    return execs


# ---------------------------------------------------------------------------
# span invariants, real pool
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(n=st.integers(8, 48), n_stages=st.integers(2, 4),
       full_a=st.booleans(), full_b=st.booleans(), full_c=st.booleans(),
       tech=st.sampled_from(TECHS), layout=st.sampled_from(LAYOUTS),
       impl=st.sampled_from(IMPLS), workers=st.integers(1, 4))
def test_span_invariants_host_pool(n, n_stages, full_a, full_b, full_c,
                                   tech, layout, impl, workers):
    dag = _chain_dag(n, n_stages, [full_a, full_b, full_c])
    cfg = SchedulerConfig(technique=tech, queue_layout=layout,
                          n_workers=workers, queue_impl=impl)
    tracer = Tracer(job="prop")
    res = PipelineExecutor(dag, cfg, tracer=tracer).run()
    execs = _check_invariants(tracer, list(res.events))
    assert all(s.job == "prop" for s in execs)
    assert validate_chrome_trace(tracer.to_chrome_trace()) == []


@settings(max_examples=10, deadline=None)
@given(n=st.integers(16, 64), tech=st.sampled_from(TECHS),
       layout=st.sampled_from(LAYOUTS), workers=st.integers(2, 6),
       full_dep=st.booleans())
def test_span_invariants_simulated(n, tech, layout, workers, full_dep):
    dag = _chain_dag(n, 3, [full_dep, not full_dep])
    tracer = Tracer(job="sim")
    sim = simulate_dag(dag, _costs(dag), per_stage=None, n_workers=workers,
                       tracer=tracer)
    spans = tracer.spans()
    execs = [s for s in spans if s.kind == "exec"]
    assert len(execs) == sim.stats.total_chunks
    # virtual time: chunk bodies are exact, so the critical path telescopes
    rep = analyze_critical_path(tracer, makespan=sim.makespan)
    rep.reconcile(sim.stats, sim.makespan, rel_tol=1e-6)
    assert validate_chrome_trace(tracer.to_chrome_trace()) == []


def test_chrome_trace_schema_fields():
    dag = _chain_dag(16, 2, [False])
    tracer = Tracer(job="schema")
    PipelineExecutor(dag, SchedulerConfig(technique="GSS", n_workers=2),
                     tracer=tracer).run()
    obj = tracer.to_chrome_trace()
    assert validate_chrome_trace(obj) == []
    # round-trips through JSON and keeps both processes + metadata rows
    obj2 = json.loads(json.dumps(obj))
    evs = obj2["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert pids == {1, 2}
    names = {e["args"].get("name") for e in evs if e["ph"] == "M"}
    assert {"pool", "jobs"} <= names
    cats = {e.get("cat") for e in evs if e["ph"] != "M"}
    assert "exec" in cats or "steal" in cats
    assert "stage" in cats and "job" in cats
    # validator actually rejects malformed events
    assert validate_chrome_trace({"traceEvents": [{"ph": "X", "pid": 1,
                                                   "tid": 0, "name": "x",
                                                   "ts": 0.0, "dur": -1}]})
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert not nt.enabled
    nt.record_raw("exec", "j", "s", 0, 0, 0.0, 1.0)
    nt.mark("shed", 0.5)
    nt.extend_raw([("exec", "j", "s", 0, 0, 0.0, 1.0, 0, 0.0, "")])
    assert len(nt) == 0 and nt.spans() == []
    assert as_tracer(None) is NULL_TRACER
    t = Tracer()
    assert as_tracer(t) is t


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------

def test_critical_path_reconciles_real_pool():
    dag = _chain_dag(64, 3, [False, True])
    tracer = Tracer(job="cp")
    res = PipelineExecutor(dag, SchedulerConfig(
        technique="GSS", queue_layout="PERCORE", n_workers=4),
        tracer=tracer).run()
    rep = analyze_critical_path(tracer, makespan=res.wall_time_s)
    # sums to the measured makespan and never attributes more exec time
    # to a stage than the independent DagStats accounting measured
    rep.reconcile(res.stats, res.wall_time_s, rel_tol=0.05, abs_tol=1e-6)
    assert rep.breakdown["exec"] > 0
    assert rep.path, "walk must traverse at least one work span"
    assert "exec=" in rep.describe()


def test_critical_path_empty_and_synthetic():
    rep = analyze_critical_path(Tracer(), makespan=1.0)
    assert rep.sched_overhead_s == {"_idle": 1.0}
    assert rep.total == pytest.approx(1.0)
    # hand-built timeline: exec 0-1 on lane 0, gap 1-2 (wait 0.6),
    # exec 2-3; transfer 3-3.5; makespan 4 -> 0.5 drain
    t = Tracer(job="synth")
    t.record_raw("exec", "synth", "a", 0, 0, 0.0, 1.0)
    t.record_raw("exec", "synth", "b", 0, 0, 2.0, 3.0, 0, 0.6)
    t.record_raw("transfer", "synth", "b", 1, 0, 3.0, 3.5)
    rep = analyze_critical_path(t, makespan=4.0)
    b = rep.breakdown
    assert b["exec"] == pytest.approx(2.0)
    assert b["transfer"] == pytest.approx(0.5)
    assert b["queue_wait"] == pytest.approx(0.6)
    assert b["sched_overhead"] == pytest.approx(0.9)  # 0.4 gap + 0.5 drain
    assert rep.total == pytest.approx(4.0)
    assert rep.sched_overhead_s["_drain"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# slot-vs-deque queue-wait differential (the wait_s reconciliation fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
def test_queue_wait_populated_per_impl(impl):
    dag = _chain_dag(256, 2, [False])
    cfg = SchedulerConfig(technique="SS", queue_layout="CENTRALIZED",
                          n_workers=4, queue_impl=impl)
    res = PipelineExecutor(dag, cfg).run()
    waits = [e.wait_s for e in res.events]
    assert all(w >= 0.0 for w in waits)
    assert any(w > 0.0 for w in waits), (
        f"{impl}: no queue wait measured across {len(waits)} chunks")
    # DagResult.stats folds the same numbers — no reconciliation gap
    st_ = res.stats
    assert st_.total_queue_wait_s == pytest.approx(sum(waits), rel=1e-9)


def test_slot_vs_deque_differential_stats():
    """The slot path used to drop queue waits entirely; both impls must
    now produce the same chunk accounting (the schedule is deterministic)
    with wait_s populated and internally consistent."""
    dag = _chain_dag(96, 3, [False, False])
    per = {}
    for impl in IMPLS:
        cfg = SchedulerConfig(technique="GSS", queue_layout="PERCORE",
                              n_workers=4, queue_impl=impl)
        res = PipelineExecutor(dag, cfg).run()
        st_ = res.stats
        assert st_.total_queue_wait_s > 0.0, f"{impl}: waits not populated"
        assert st_.total_queue_wait_s == pytest.approx(
            sum(e.wait_s for e in res.events), rel=1e-9)
        per[impl] = st_
    # same technique -> same chunk plan, whichever queue holds it
    assert per["deque"].chunks == per["slot"].chunks


@pytest.mark.parametrize("impl", IMPLS)
def test_scheduled_executor_queue_wait_stat(impl):
    from repro.core import ScheduledExecutor, tasks_from_schedule
    cfg = SchedulerConfig(technique="SS", queue_layout="CENTRALIZED",
                          n_workers=4, queue_impl=impl)
    tasks = tasks_from_schedule([(i, 1) for i in range(0, 128)],
                                lambda s, z: float(s))
    _, st_ = ScheduledExecutor(cfg).run(tasks)
    assert st_.queue_wait_s > 0.0


# ---------------------------------------------------------------------------
# uniform result surfaces: TransferEvent / PreemptionEvent
# ---------------------------------------------------------------------------

def test_result_surfaces_are_uniform():
    dag = _chain_dag(32, 2, [False])
    cfg = SchedulerConfig(technique="SS", n_workers=2)
    from repro.core import Placement
    res = PipelineExecutor(dag, cfg).run()
    hres = HeteroExecutor(dag, cfg, Placement.all_host(dag.order)).run()
    _, ck = PreemptiveRunner(dag, cfg, preempt_after=2).run()
    server = PipelineServer(cfg)
    server.submit(Submission(dag, "u1"))
    sres = server.serve()
    for r in (res, hres, sres):
        assert isinstance(r.transfer_events, list)
        assert isinstance(r.preemptions, list)
        st_ = r.stats
        # transfers folded into stats uniformly: one count per event
        assert sum(st_.transfers.values()) == len(r.transfer_events)
    assert ck is not None and ck.remaining_chunks > 0


def test_server_spans_and_preemption_marks():
    dag = _chain_dag(48, 2, [False])
    cfg = SchedulerConfig(technique="GSS", n_workers=2)
    tracer = Tracer()
    server = PipelineServer(cfg, arbiter="fair", tracer=tracer)
    for name in ("alpha", "beta"):
        server.submit(Submission(_chain_dag(48, 2, [False]), name))
    res = server.serve()
    spans = tracer.spans()
    jobs = {s.job for s in spans if s.kind == "exec"}
    assert jobs == {"alpha", "beta"}
    _check_invariants(tracer, list(res.events))
    rep = analyze_critical_path(tracer, makespan=res.makespan_s)
    rep.reconcile(res.stats, res.makespan_s, rel_tol=0.05, abs_tol=1e-6)


def test_preemptive_runner_marks_checkpoint():
    dag = _chain_dag(32, 2, [False])
    cfg = SchedulerConfig(technique="SS", n_workers=1)
    tracer = Tracer()
    _, ck = PreemptiveRunner(dag, cfg, preempt_after=2, job="pj",
                             tracer=tracer).run()
    kinds = {s.kind for s in tracer.spans()}
    assert "checkpoint" in kinds
    from repro.core import resume_on_host
    resume_on_host(ck, dag, cfg, tracer=tracer)
    kinds = {s.kind for s in tracer.spans()}
    assert "resume" in kinds
    assert validate_chrome_trace(tracer.to_chrome_trace()) == []


# ---------------------------------------------------------------------------
# device-DAG path phases and compile counters
# ---------------------------------------------------------------------------

def _two_stage_lowering(n=256, tile=32, seed=3):
    """A concat stage and a sum stage reading its rows: no full edge, so
    the walk may take several shards."""
    import jax.numpy as jnp

    from repro.kernels.dag_walk import WalkOperand, WalkStage
    from repro.vee.apps import DeviceLowering

    def op(inputs, s, z):
        return np.zeros(z)

    dag = PipelineDAG([
        Stage("scaled", n // tile, op, combine="concat"),
        Stage("total", n // tile, op, combine="sum",
              deps=(StageDep("scaled", DEP_ELEMENTWISE),)),
    ])

    def scaled_body(ctx, ins, out):
        out[...] = ins["x"][...] * 2.0 + 1.0

    def total_body(ctx, ins, out):
        out[...] += ins["scaled"][...].sum(axis=0, keepdims=True)

    stages = [
        WalkStage("scaled", n, (n, 8), jnp.float32, "concat", scaled_body,
                  operands=("x",)),
        WalkStage("total", n, (1, 8), jnp.float32, "sum", total_body,
                  reads=(("scaled", "rows"),)),
    ]
    x = np.random.default_rng(seed).uniform(size=(n, 8)).astype(np.float32)
    return DeviceLowering(dag, stages, [WalkOperand("x", (tile, 8),
                                                    ("row", "zero"))],
                          {"x": jnp.asarray(x)}, tile)


def _phases(tracer):
    return [s for s in tracer.spans() if s.kind in DEVDAG_PHASES]


@pytest.mark.parametrize("n_shards", [1, 2])
def test_run_device_dag_phases_nest_in_one_job(n_shards):
    from repro.vee.apps import run_device_dag

    tracer = Tracer()
    run_device_dag(_two_stage_lowering(), "STATIC", n_shards=n_shards,
                   n_workers=2, tracer=tracer, job="7")
    spans = _phases(tracer)
    kinds = sorted(s.kind for s in spans)
    per_shard = ["devdag.launch", "devdag.readback", "devdag.table_put"]
    assert kinds == sorted(["devdag.job", "devdag.tables", "devdag.combine"]
                           + per_shard * n_shards)
    assert {s.job for s in spans} == {"7"}
    (job,) = [s for s in spans if s.kind == "devdag.job"]
    for s in spans:
        assert job.t0 <= s.t0 <= s.t1 <= job.t1
    for kind in per_shard:
        assert sorted(s.detail for s in spans if s.kind == kind) == \
            [f"shard={k}" for k in range(n_shards)]
    # each phase in the order the call runs them
    first = {k: min(s.t0 for s in spans if s.kind == k) for k in set(kinds)}
    assert (first["devdag.tables"] <= first["devdag.table_put"]
            <= first["devdag.launch"] <= first["devdag.readback"]
            <= first["devdag.combine"])


def test_merged_run_adds_merge_phase():
    from repro.vee.apps import (linreg_device_lowering,
                                merge_device_lowerings, run_device_dag)

    tracer = Tracer()
    lows = [linreg_device_lowering(128, 5, tile=32, seed=s) for s in (1, 2)]
    merged = merge_device_lowerings(lows, tracer=tracer, job="3")
    run_device_dag(merged, "SS", n_workers=2, tracer=tracer, job="3")
    spans = _phases(tracer)
    (merge,) = [s for s in spans if s.kind == "devdag.merge"]
    (job,) = [s for s in spans if s.kind == "devdag.job"]
    assert merge.job == job.job == "3"
    assert merge.t1 <= job.t0
    assert sum(s.kind == "devdag.launch" for s in spans) == 1


def test_untraced_call_records_nothing_and_registers_no_listener():
    import jax
    from jax._src import monitoring

    from repro.core import telemetry
    from repro.vee.apps import linreg_device_lowering, run_device_dag

    if telemetry._watching:  # start from a process that watches nothing
        jax.monitoring.unregister_event_duration_listener(
            telemetry._on_duration)
        jax.monitoring.unregister_event_listener(telemetry._on_event)
        telemetry._watching = False

    def listeners():
        return (monitoring.get_event_duration_listeners(),
                monitoring.get_event_listeners())

    low = linreg_device_lowering(128, 5, tile=32)
    before = listeners()
    plain, _ = run_device_dag(low, "SS", n_workers=2)
    assert listeners() == before and not telemetry._watching
    assert len(NULL_TRACER) == 0
    tracer = Tracer()
    traced, _ = run_device_dag(low, "SS", n_workers=2, tracer=tracer, job="1")
    assert telemetry._watching
    durations, events = listeners()
    assert durations.count(telemetry._on_duration) == 1
    assert events.count(telemetry._on_event) == 1
    assert set(plain) == set(traced)
    for k in plain:
        assert np.array_equal(plain[k], traced[k])


def test_disabled_phase_reads_no_clock(monkeypatch):
    from repro.core import telemetry

    def clock():
        raise AssertionError("a disabled phase read the clock")

    monkeypatch.setattr(telemetry, "perf_counter", clock)
    with telemetry.phase(NULL_TRACER, "devdag.launch", "1"):
        pass
    with pytest.raises(AssertionError):
        with telemetry.phase(Tracer(), "devdag.launch", "1"):
            pass


def test_compile_counter_splits_synthetic_events():
    import jax

    from repro.core import telemetry

    telemetry.watch_compiles()
    before = compile_counts()
    rec = jax.monitoring.record_event_duration_secs
    rec(telemetry.TRACE_EVENT, 0.25)
    rec(telemetry.LOWER_EVENT, 0.5)
    for _ in range(2):  # two persistent-cache loads
        jax.monitoring.record_event(telemetry.CACHE_HIT_EVENT)
        rec(telemetry.CACHE_LOAD_EVENT, 0.125)
        rec(telemetry.COMPILE_EVENT, 0.25)
    rec(telemetry.COMPILE_EVENT, 2.0)  # one backend compile
    rec("/jax/some/other_event", 9.0)
    after = compile_counts()
    delta = {k: after[k] - before[k] for k in before if k != "watching"}
    assert delta == {"trace_s": 0.25, "lower_s": 0.5, "requests": 3,
                     "request_s": 2.5, "cache_loads": 2,
                     "cache_load_s": 0.25, "backend_compiles": 1}
    reg = MetricsRegistry()
    collect_compile_metrics(reg)
    counters = reg.snapshot()["counters"]
    assert counters["sched_compile_requests"] == after["requests"]
    assert counters["sched_compile_backend_compiles"] == \
        after["requests"] - after["cache_loads"]
    assert counters["sched_compile_cache_load_seconds"] == \
        after["cache_load_s"]


def test_phase_spans_export_as_slices():
    from repro.core import telemetry

    tracer = Tracer()
    with telemetry.phase(tracer, "devdag.job", "9"):
        with telemetry.phase(tracer, "devdag.launch", "9", "shard=0"):
            pass
    tracer.mark("admit", 0.0, job="9")
    obj = tracer.to_chrome_trace()
    assert validate_chrome_trace(obj) == []
    slices = [e for e in obj["traceEvents"] if e.get("cat") == "devdag"]
    assert sorted(e["name"] for e in slices) == ["devdag.job",
                                                 "devdag.launch"]
    assert all(e["ph"] == "X" and e["pid"] == 3 and e["tid"] == 1
               for e in slices)
    (hull,) = [s for s in tracer.spans() if s.kind == "job"]
    (job,) = [s for s in tracer.spans() if s.kind == "devdag.job"]
    assert hull.t0 <= job.t0 and job.t1 <= hull.t1


def test_profiler_trace_holds_devdag_spans(tmp_path):
    import jax

    from repro.vee.apps import linreg_device_lowering, run_device_dag

    low = linreg_device_lowering(128, 5, tile=32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_device_dag(low, "SS", n_workers=2)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(xplane))
    host = [e for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("devdag.")]
    # a single launch: no merge, and no fixed-point loop to feed
    want = set(DEVDAG_PHASES) - {"devdag.merge", "devdag.feed"}
    assert {e.name for e in host} == want
    (job,) = [e for e in host if e.name == "devdag.job"]
    assert all(job.start_ns <= e.start_ns and e.end_ns <= job.end_ns
               for e in host)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_sharded_combine_from_host_copies(n_shards):
    """Reading each shard back once, then combining on the host, gives
    the values the per-slot combine of the device arrays gave."""
    from repro.core import build_dag_tables
    from repro.kernels.dag_walk import dag_walk, dag_walk_sharded

    low = _two_stage_lowering()
    ddt = build_dag_tables(low.dag, 1, "STATIC", n_shards=n_shards,
                           n_workers=2)
    rows = ddt.tables.copy()
    rows[:, :, 1:] *= low.tile
    got = dag_walk_sharded(low.stages, low.operands, low.values, rows,
                           low.tile)
    shard_outs = [dag_walk(low.stages, low.operands, low.values, rows[s],
                           low.tile) for s in range(n_shards)]
    acc = shard_outs[0]["total"]
    for o in shard_outs[1:]:
        acc = acc + o["total"]
    assert np.array_equal(got["total"], np.asarray(acc))
    want = np.zeros((low.stages[0].n_rows, 8), np.float32)
    for sh in range(n_shards):
        for sid, start, size in rows[sh]:
            if sid == 0 and size > 0:
                want[start:start + size] = np.asarray(
                    shard_outs[sh]["scaled"])[start:start + size]
    assert np.array_equal(got["scaled"], want)
    x = np.asarray(low.values["x"])
    np.testing.assert_allclose(got["scaled"], x * 2 + 1, rtol=1e-6)
    np.testing.assert_allclose(got["total"][0], (x * 2 + 1).sum(axis=0),
                               rtol=1e-5)


def test_cache_metrics_export_walker_call_cache():
    """The walker call memo's counters reach the registry beside the
    device table cache's: one miss, then a hit per repeat launch."""
    from repro.core.telemetry import collect_cache_metrics
    from repro.kernels.dag_walk import (clear_walker_call_cache,
                                        walker_call_cache_stats)
    from repro.vee.apps import run_device_dag

    low = _two_stage_lowering()
    clear_walker_call_cache()
    for _ in range(3):
        run_device_dag(low, "STATIC")
    assert walker_call_cache_stats() == {"hits": 2, "misses": 1, "size": 1}
    reg = MetricsRegistry()
    collect_cache_metrics(reg)
    snap = reg.snapshot()
    assert snap["counters"]["sched_walker_call_cache_hits"] == 2
    assert snap["counters"]["sched_walker_call_cache_misses"] == 1
    assert snap["gauges"]["sched_walker_call_cache_hit_rate"] == \
        pytest.approx(2 / 3)
    assert "sched_device_table_cache_hits" in snap["counters"]
    clear_walker_call_cache()


def test_cache_metrics_export_loop_counters():
    """A fixed-point loop job's count and its launches reach the registry;
    a one-walk job adds to neither."""
    from repro.core.telemetry import collect_cache_metrics, loop_counts
    from repro.vee.apps import cc_device_lowering, run_device_dag

    def exported():
        reg = MetricsRegistry()
        collect_cache_metrics(reg)
        counters = reg.snapshot()["counters"]
        return (counters["sched_devdag_loop_jobs"],
                counters["sched_devdag_loop_launches"])

    start = exported()
    assert start == (loop_counts()["jobs"], loop_counts()["launches"])
    run_device_dag(_two_stage_lowering(), "STATIC")
    assert exported() == start
    tracer = Tracer()
    run_device_dag(cc_device_lowering(8, 16, tile=64, tile_c=128, seed=2),
                   {"propagate": "MFSC", "changed": "STATIC"}, tracer=tracer)
    launches = sum(s.kind == "devdag.launch" for s in tracer.spans())
    assert launches >= 2
    assert exported() == (start[0] + 1, start[1] + launches)


def test_cache_metrics_export_walker_hold_share():
    """A CC job's launches hold the adjacency and column-label blocks at
    half their steps (the ``changed`` slots'); a linreg job, whose operands
    have no inner axis, adds 0 of 0."""
    from repro.core.telemetry import collect_cache_metrics
    from repro.vee.apps import (cc_device_lowering, linreg_device_lowering,
                                run_device_dag)

    def exported():
        reg = MetricsRegistry()
        collect_cache_metrics(reg)
        counters = reg.snapshot()["counters"]
        return (counters["sched_walker_held_steps"],
                counters["sched_walker_inner_steps"])

    start = exported()
    run_device_dag(_two_stage_lowering(), "STATIC")
    assert exported() == start
    run_device_dag(linreg_device_lowering(256, 5, tile=32), "GSS")
    assert exported() == start
    tracer = Tracer()
    run_device_dag(cc_device_lowering(8, 16, tile=64, tile_c=128, seed=2),
                   {"propagate": "MFSC", "changed": "STATIC"}, tracer=tracer)
    launches = sum(s.kind == "devdag.launch" for s in tracer.spans())
    held, inner = (b - a for a, b in zip(start, exported()))
    # per launch: 8 slots x 2 inner steps x 2 operands (G, c_col)
    assert inner == 32 * launches and launches >= 2
    assert held / inner == 0.5


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_memoization_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("hits", "cache hits")
    c.inc()
    reg.counter("hits").inc(2)
    assert reg.counter("hits") is c and c.value == 3
    # distinct labels -> distinct series
    reg.counter("hits", labels={"cache": "a"}).inc()
    reg.gauge("depth").set(7)
    h = reg.histogram("lat", labels={"tenant": "t"})
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 3
    assert snap["counters"]['hits{cache="a"}'] == 1
    assert snap["gauges"]["depth"] == 7
    s = snap["histograms"]['lat{tenant="t"}']
    assert s["count"] == 4 and s["sum"] == pytest.approx(10.0)
    assert s["min"] == 1.0 and s["max"] == 4.0
    json.loads(reg.to_json())  # JSON-clean


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("sched_steals", "work steals").inc(5)
    reg.gauge("sched_queue_depth", labels={"q": "0"}).set(2)
    reg.histogram("sched_job_latency_seconds").observe(0.25)
    text = reg.to_prometheus()
    assert "# TYPE sched_steals counter" in text
    assert "# HELP sched_steals work steals" in text
    assert "sched_steals 5.0" in text
    assert 'sched_queue_depth{q="0"} 2.0' in text
    assert "sched_job_latency_seconds_count 1" in text
    assert 'quantile="0.99"' in text


@pytest.mark.parametrize("qcls,dist", [
    (CentralizedQueue, False), (SlotCentralizedQueue, False),
    (DistributedQueues, True), (SlotDistributedQueues, True)])
def test_queue_counters_uniform_api(qcls, dist):
    from repro.core import RangeTask, make_partitioner
    tasks = [RangeTask(i, i, 1) for i in range(6)]
    if qcls is CentralizedQueue:
        q = qcls(tasks, make_partitioner("SS", len(tasks), 2))
    else:
        q = qcls(tasks, "SS", 2)
    q.pop_local(0) if dist else q.pop()
    c = q.counters()
    assert c["depth"] == 5
    assert c["pops"] == 1
    if dist:
        assert {"steals", "failed_steals"} <= set(c)
    else:
        assert "contended_pops" in c
    reg = MetricsRegistry()
    collect_queue_metrics(reg, c, labels={"impl": qcls.__name__})
    snap = reg.snapshot()
    key = f'sched_queue_depth{{impl="{qcls.__name__}"}}'
    assert snap["gauges"][key] == 5


def test_server_metrics_collection():
    from repro.core import collect_server_metrics
    dag = _chain_dag(32, 2, [False])
    cfg = SchedulerConfig(technique="GSS", n_workers=2)
    server = PipelineServer(cfg)
    server.submit(Submission(dag, "m1", tenant="t1"))
    res = server.serve()
    reg = MetricsRegistry()
    collect_server_metrics(reg, res)
    snap = reg.snapshot()
    assert snap["counters"]["sched_chunks"] == len(list(res.events))
    assert snap["histograms"]["sched_job_latency_seconds"]["count"] == 1
    assert any(k.startswith("sched_tenant_service_seconds")
               for k in snap["counters"])
