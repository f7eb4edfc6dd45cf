"""Preemptive multi-tenancy tests (core/preempt.py, DESIGN.md §15).

The invariant harness for checkpoint/preempt/migrate:

  * exactly-once chunk execution under preemption at random chunk
    boundaries on random DAG shapes, techniques, and worker counts — on
    the real thread pool; ``StageCheckpoint.validate`` proves no chunk
    is lost, duplicated, or torn, and the resumed values equal an
    unpreempted reference run (property test);
  * the bit-equality matrix: checkpoint a host run mid-flight, migrate
    host->device and device->host, resume — bit-equal to never-preempted
    runs for BOTH the vee linreg and recommendation lowerings;
  * edge cases: seeded heavy_tailed_trace determinism ACROSS processes,
    preemption decisions over an already-expired job, and checkpointing
    a stage whose remainder is empty (preempt after its last pop);
  * the ``preemptive`` arbiter composing with the threaded server, the
    virtual-time simulator, and the open-loop replay engine.
"""

import hashlib
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ARBITERS,
    JobCheckpoint,
    PipelineDAG,
    PipelineExecutor,
    PipelineServer,
    PreemptableStageRun,
    PreemptiveArbiter,
    PreemptiveRunner,
    SchedulerConfig,
    Stage,
    StageCheckpoint,
    StageDep,
    Submission,
    heavy_tailed_trace,
    make_arbiter,
    replay_open_loop,
    resume_on_host,
    simulate_server,
)
from repro.core.preempt import migrate_to_device, run_device_prefix
from repro.core.server import Job, JobState

TECHS = ["STATIC", "SS", "GSS", "FAC2"]
LAYOUTS = ["CENTRALIZED", "PERCORE"]


def _int_dag(n, shape, kind):
    """Integer-valued DAGs: results are association-independent, so any
    legal execution order must reproduce them exactly."""
    a = Stage("a", n,
              lambda i, s, z: np.arange(s, s + z, dtype=np.int64) * 3 + 1,
              combine="concat")
    if shape == "chain2":
        b = Stage("b", n, lambda i, s, z: int(i["a"][s:s + z].sum()),
                  combine="sum", deps=(StageDep("a", kind),))
        return PipelineDAG([a, b])
    if shape == "chain3":
        b = Stage("b", n, lambda i, s, z: i["a"][s:s + z] * 2,
                  combine="concat", deps=(StageDep("a", "elementwise"),))
        c = Stage("c", n, lambda i, s, z: int(i["b"][s:s + z].sum()),
                  combine="sum", deps=(StageDep("b", kind),))
        return PipelineDAG([a, b, c])
    b = Stage("b", n, lambda i, s, z: i["a"][s:s + z] + 7,
              combine="concat", deps=(StageDep("a", "elementwise"),))
    c = Stage("c", n, lambda i, s, z: int(i["a"][s:s + z].sum()),
              combine="sum", deps=(StageDep("a", kind),))
    d = Stage("d", n, lambda i, s, z: int(i["b"][s:s + z].sum()) + i["c"],
              combine="sum", deps=(StageDep("b", "elementwise"),
                                   StageDep("c", "full")))
    return PipelineDAG([a, b, c, d])


def _values_equal(got, want):
    for k in want:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


# ---------------------------------------------------------------------------
# the exactly-once property under random preemption points (real pool)
# ---------------------------------------------------------------------------

@settings(max_examples=14, deadline=None)
@given(
    n=st.integers(1, 200),
    p_workers=st.integers(1, 4),
    tech=st.sampled_from(TECHS),
    layout=st.sampled_from(LAYOUTS),
    shape=st.sampled_from(["chain2", "chain3", "diamond"]),
    kind=st.sampled_from(["full", "elementwise"]),
    cut=st.integers(0, 60),
)
def test_exactly_once_under_random_preemption(n, p_workers, tech, layout,
                                              shape, kind, cut):
    dag = _int_dag(n, shape, kind)
    cfg = SchedulerConfig(technique=tech, queue_layout=layout,
                          victim_strategy="RND", n_workers=p_workers, seed=0)
    ref = PipelineExecutor(dag, cfg).run()
    res, ck = PreemptiveRunner(dag, cfg, preempt_after=max(1, cut)).run()
    if ck is None:
        # the cut landed at/after the last chunk: nothing left to preempt
        _values_equal(res.values, ref.values)
        return
    # validate() proves pending ∪ done covers each stage's rows exactly
    # once — no lost, duplicated, or torn chunks at the boundary
    ck.validate(dag)
    pending_chunks = ck.remaining_chunks
    assert pending_chunks > 0
    fin = resume_on_host(ck, dag, cfg)
    # the resume executes the checkpointed remainder and nothing else
    assert len(fin.events) == pending_chunks
    _values_equal(fin.values, ref.values)


def test_trigger_form_and_resumed_runner_can_repreempt():
    dag = _int_dag(64, "chain3", "elementwise")
    cfg = SchedulerConfig(technique="SS", queue_layout="CENTRALIZED",
                          n_workers=1)
    ref = PipelineExecutor(dag, cfg).run()
    _, ck = PreemptiveRunner(dag, cfg, trigger=lambda d: d >= 5).run()
    assert ck is not None and ck.substrate == "host"
    # preempt the resumed run again mid-flight, then finish: still exact
    res2, ck2 = PreemptiveRunner(dag, cfg, preempt_after=3).run(resume_from=ck)
    assert res2 is None
    ck2.validate(dag)
    _values_equal(resume_on_host(ck2, dag, cfg).values, ref.values)


def test_resume_with_rechunk_target_is_exact():
    dag = _int_dag(96, "diamond", "elementwise")
    cfg = SchedulerConfig(technique="STATIC", queue_layout="CENTRALIZED",
                          n_workers=2)
    ref = PipelineExecutor(dag, cfg).run()
    _, ck = PreemptiveRunner(dag, cfg, preempt_after=2).run()
    fin, left = PreemptiveRunner(dag, cfg, rechunk_target=8).run(
        resume_from=ck)
    assert left is None
    _values_equal(fin.values, ref.values)


# ---------------------------------------------------------------------------
# checkpoint-format invariants (the validate() harness itself)
# ---------------------------------------------------------------------------

def _concat_ck(**kw):
    base = dict(stage="a", n_rows=4, combine="concat",
                pending=((2, 2),), row_done=np.array([1, 1, 0, 0], bool),
                out=np.zeros(4))
    base.update(kw)
    return StageCheckpoint(**base)


def test_validate_rejects_torn_checkpoints():
    with pytest.raises(ValueError, match="out of range"):
        _concat_ck(pending=((3, 2),)).validate()
    with pytest.raises(ValueError, match="overlapping"):
        _concat_ck(pending=((2, 2), (3, 1)),
                   row_done=np.array([1, 1, 0, 0], bool)).validate()
    with pytest.raises(ValueError, match="overlaps completed"):
        _concat_ck(pending=((1, 3),)).validate()
    with pytest.raises(ValueError, match="lost"):
        _concat_ck(pending=((2, 1),)).validate()
    with pytest.raises(ValueError, match="no out buffer"):
        _concat_ck(out=None).validate()
    sum_base = dict(stage="s", n_rows=4, combine="sum",
                    pending=((2, 2),), row_done=np.array([1, 1, 0, 0], bool))
    with pytest.raises(ValueError, match="exceeds the completed prefix"):
        StageCheckpoint(acc=1.0, acc_next=3, **sum_base).validate()
    with pytest.raises(ValueError, match="acc=None"):
        StageCheckpoint(acc=None, acc_next=2, **sum_base).validate()
    with pytest.raises(ValueError, match="already folded"):
        StageCheckpoint(acc=1.0, acc_next=2, parts=((0, 2, 5.0),),
                        **sum_base).validate()
    with pytest.raises(ValueError, match="unfolded"):
        StageCheckpoint(stage="s", n_rows=4, combine="sum", pending=(),
                        row_done=np.ones(4, bool), acc=1.0, acc_next=2,
                        parts=((2, 2, 5.0),)).validate()


def test_job_checkpoint_validate_against_dag():
    dag = _int_dag(8, "chain2", "full")
    _, ck = PreemptiveRunner(dag, SchedulerConfig(
        technique="SS", n_workers=1), preempt_after=1).run()
    ck.validate(dag)
    other = _int_dag(16, "chain2", "full")
    with pytest.raises(ValueError, match="!= DAG"):
        ck.validate(other)
    bad = JobCheckpoint(job="j", stages={"x": ck.stages["a"]})
    with pytest.raises(ValueError, match="checkpoint key"):
        bad.validate()


def test_empty_remainder_checkpoint():
    """Preempt after a stage's last pop: its checkpoint is empty and the
    restore lands directly in ``done`` with the checkpointed value."""
    n = 4
    dag = _int_dag(n, "chain2", "full")
    cfg = SchedulerConfig(technique="SS", queue_layout="CENTRALIZED",
                          n_workers=1)
    ref = PipelineExecutor(dag, cfg).run()
    # SS/1-worker pops a's n one-row chunks first (b's full dep gates it),
    # so the cut at n lands exactly after a's last pop
    _, ck = PreemptiveRunner(dag, cfg, preempt_after=n).run()
    assert ck is not None
    assert ck.stages["a"].empty and ck.stages["a"].executed == n
    assert not ck.empty and ck.stages["b"].remaining_rows == n
    _values_equal(resume_on_host(ck, dag, cfg).values, ref.values)
    # the fully-empty checkpoint: resume completes at once
    fin, left = PreemptiveRunner(dag, cfg).run(resume_from=JobCheckpoint(
        job="done", stages={
            "a": StageCheckpoint(
                stage="a", n_rows=n, combine="concat", pending=(),
                row_done=np.ones(n, bool),
                out=np.asarray(ref.values["a"]).copy(), executed=n),
            "b": StageCheckpoint(
                stage="b", n_rows=n, combine="sum", pending=(),
                row_done=np.ones(n, bool), acc=ref.values["b"],
                acc_next=n, executed=n),
        }))
    assert left is None and len(fin.events) == 0
    _values_equal(fin.values, ref.values)


def test_restore_rejects_mismatched_stage():
    dag = _int_dag(8, "chain2", "full")
    _, ck = PreemptiveRunner(dag, SchedulerConfig(
        technique="SS", n_workers=1), preempt_after=1).run()
    other = Stage("a", 16, lambda i, s, z: np.zeros(z), combine="concat")
    with pytest.raises(ValueError, match="does not match"):
        PreemptableStageRun.restore(ck.stages["a"], other,
                                    SchedulerConfig(n_workers=1), [0])


# ---------------------------------------------------------------------------
# the bit-equality migration matrix (host<->device, both vee lowerings)
# ---------------------------------------------------------------------------

def _lowerings():
    from repro.vee.apps import (linreg_device_lowering,
                                recommendation_device_lowering)
    return [("linreg", linreg_device_lowering(256, 9, tile=64)),
            ("recommendation", recommendation_device_lowering(128, 192,
                                                              tile=64))]


@pytest.mark.parametrize("which", ["linreg", "recommendation"])
def test_migration_matrix_bit_equal(which):
    pytest.importorskip("jax")
    from repro.vee.apps import run_device_dag

    low = dict(_lowerings())[which]
    cfg = SchedulerConfig(technique="SS", queue_layout="CENTRALIZED",
                          n_workers=1)
    host_ref = PipelineExecutor(low.dag, cfg).run()
    dev_ref, _ = run_device_dag(low, "SS")
    total = sum(low.dag.stages[n].n_rows for n in low.dag.order)
    for p in (1, 2, total - 1):
        # host -> device: preempt the host run, re-lower the remainder
        res, ck = PreemptiveRunner(low.dag, cfg, preempt_after=p).run()
        assert res is None, f"cut {p} did not preempt"
        vals = migrate_to_device(ck, low)
        for k in dev_ref:
            assert np.array_equal(vals[k], dev_ref[k]), (p, k)
        # device -> host: freeze a device prefix, finish on the pool
        ck2, _ = run_device_prefix(low, p)
        assert ck2.substrate == "device"
        fin = resume_on_host(ck2, low.dag, cfg)
        for k in host_ref.values:
            assert np.array_equal(np.asarray(fin.values[k]),
                                  np.asarray(host_ref.values[k])), (p, k)


def test_device_prefix_bounds():
    pytest.importorskip("jax")
    low = dict(_lowerings())["linreg"]
    cfg = SchedulerConfig(technique="SS", n_workers=1)
    ref = PipelineExecutor(low.dag, cfg).run()
    # n_slots=0: nothing ran on-device, the host does everything
    ck, walked = run_device_prefix(low, 0)
    assert walked == {} and ck.remaining_chunks > 0
    _values_equal(resume_on_host(ck, low.dag, cfg).values, ref.values)
    # n_slots past the table end clamps: resume completes immediately
    total = sum(low.dag.stages[n].n_rows for n in low.dag.order)
    ck_all, _ = run_device_prefix(low, total + 99)
    assert ck_all.empty
    _values_equal(resume_on_host(ck_all, low.dag, cfg).values, ref.values)


def test_migrate_rejects_out_of_order_sum_partials():
    pytest.importorskip("jax")
    low = dict(_lowerings())["linreg"]
    cfg = SchedulerConfig(technique="SS", n_workers=1)
    _, ck = PreemptiveRunner(low.dag, cfg, preempt_after=1).run()
    name = next(n for n, s in ck.stages.items() if s.combine == "sum")
    sck = ck.stages[name]
    done = sck.row_done.copy()
    done[2] = True
    pend = tuple((s, z) for s, z in sck.pending if s != 2)
    bad = dict(ck.stages)
    bad[name] = StageCheckpoint(
        stage=sck.stage, n_rows=sck.n_rows, combine="sum", pending=pend,
        row_done=done, acc=sck.acc, acc_next=sck.acc_next,
        parts=((2, 1, np.zeros(9)),), executed=sck.executed + 1)
    with pytest.raises(ValueError, match="resume on host"):
        migrate_to_device(JobCheckpoint(job=ck.job, stages=bad), low)


def test_vee_migration_wrappers_bit_equal():
    pytest.importorskip("jax")
    from repro.vee.apps import (linear_regression_device,
                                linear_regression_migrated,
                                recommendation_device,
                                recommendation_migrated)

    beta_ref, _, _ = linear_regression_device(256, 9, tile=64)
    for direction in ("host_to_device", "device_to_host"):
        beta = linear_regression_migrated(256, 9, cut=2, direction=direction)
        assert np.array_equal(beta, beta_ref), direction
    scores_ref = np.asarray(recommendation_device(128, 192, tile=64)[1]
                            ["scores"]).reshape(-1)
    for direction in ("host_to_device", "device_to_host"):
        scores = recommendation_migrated(128, 192, cut=3,
                                         direction=direction)
        assert np.array_equal(scores, scores_ref), direction
    with pytest.raises(ValueError, match="migration direction"):
        linear_regression_migrated(256, 9, cut=1, direction="sideways")


def test_hetero_preemption_resumes_bit_equal():
    pytest.importorskip("jax")
    from repro.core import HeteroExecutor, Placement, StagePlacement
    from repro.vee.apps import linreg_device_lowering

    low = linreg_device_lowering(256, 9, tile=64)
    cfg = SchedulerConfig(technique="SS", n_workers=2)
    ref = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    split = Placement({n: StagePlacement("split", 0.5)
                       for n in low.dag.stage_names})
    res, ck = HeteroExecutor(low.dag, cfg, split).run_preemptible(
        preempt_after=2)
    if res is not None:
        pytest.skip("pool drained before the cut (tiny DAG, fast machine)")
    assert ck.substrate == "hetero"
    ck.validate(low.dag)
    fin = resume_on_host(ck, low.dag, SchedulerConfig(
        technique="SS", n_workers=1))
    _values_equal(fin.values, ref.values)


# ---------------------------------------------------------------------------
# edge cases: trace determinism across processes, expired jobs
# ---------------------------------------------------------------------------

_DIGEST_SRC = """
import hashlib
from repro.core import heavy_tailed_trace
t = heavy_tailed_trace(96, seed=11, load=2.0, n_workers=4)
parts = [(s.name, s.tenant, s.weight, repr(s.arrival_s), repr(s.deadline_s),
          sorted((k, v.tobytes()) for k, v in s.stage_costs.items()))
         for s in t]
print(hashlib.sha256(repr(parts).encode()).hexdigest())
"""


def test_heavy_tailed_trace_deterministic_across_processes():
    scope = {}
    src_root = str(Path(__file__).resolve().parents[1] / "src")
    exec(compile(_DIGEST_SRC.replace("print", "__digest__ ="),
                 "<local>", "exec"), scope)
    env = dict(os.environ, PYTHONPATH=src_root, PYTHONHASHSEED="99")
    out = subprocess.run([sys.executable, "-c", _DIGEST_SRC], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == scope["__digest__"]


def _js(name, seq, *, priority=0, deadline=None, cost=1.0, arrival=0.0,
        service=0.0):
    dag = PipelineDAG([Stage("a", 4, lambda i, s, z: np.zeros(z),
                             combine="concat")])
    job = Job(name=name, dag=dag, priority=priority, deadline_s=deadline,
              stage_costs={"a": np.full(4, cost / 4.0)})
    return JobState(job=job, seq=seq, arrival=arrival, service=service)


def test_preemptive_arbiter_parks_victims_and_skips_expired():
    arb = PreemptiveArbiter(inner="fair", n_workers=1, slack_s=0.0)
    pressured = _js("tight", 0, priority=2, deadline=1.5, cost=1.0)
    batch = _js("batch", 1, priority=0, deadline=None, cost=9.0)
    expired = _js("late", 2, priority=1, deadline=0.25, cost=1.0)
    jobs = [pressured, batch, expired]
    got = [js.job.name for js in arb.order(jobs, now=1.0)]
    # the expired job is never PRESSURED (its miss is sunk) but IS a
    # victim; the deadline-free batch job parks alongside it
    assert got == ["tight"]
    assert batch.preempted and expired.preempted and not pressured.preempted
    kinds = [(e.job, e.kind) for e in arb.preemption_log]
    assert ("batch", "preempt") in kinds and ("late", "preempt") in kinds
    # pressure clears (the tight job finished, engines stop passing it):
    # the victims resume — being schedulable again IS the resume
    pressured.done = True
    got2 = [js.job.name for js in arb.order([batch, expired], now=2.0)]
    assert set(got2) == {"batch", "late"}
    assert not batch.preempted and not expired.preempted
    assert ("batch", "resume") in [(e.job, e.kind) for e in arb.preemption_log]


def test_preemptive_arbiter_respects_priority_fence():
    arb = PreemptiveArbiter(inner="fair", n_workers=1, slack_s=0.0)
    pressured = _js("tight", 0, priority=1, deadline=1.0, cost=1.0)
    above = _js("vip", 1, priority=5, deadline=None, cost=9.0)
    jobs = [pressured, above]
    got = {js.job.name for js in arb.order(jobs, now=0.5)}
    # higher-priority jobs are never parked for a lower-priority deadline
    assert got == {"tight", "vip"} and not above.preempted


def test_make_arbiter_lazy_registration():
    arb = make_arbiter("preemptive", inner="fair", n_workers=4, slack_s=0.5)
    assert isinstance(arb, PreemptiveArbiter)
    assert arb.n_workers == 4 and "preemptive" in ARBITERS
    with pytest.raises(ValueError, match="unknown arbiter"):
        make_arbiter("nonesuch")


# ---------------------------------------------------------------------------
# composition with the three engines
# ---------------------------------------------------------------------------

def _pressured_trace(n=240):
    return heavy_tailed_trace(n, seed=3, load=5.0, n_workers=8)


def test_replay_open_loop_preemptive_beats_fair():
    trace = _pressured_trace()
    base = replay_open_loop(trace, n_workers=8, arbiter="fair")
    pre = replay_open_loop(trace, n_workers=8, arbiter="preemptive",
                           arbiter_kwargs={"inner": "fair", "n_workers": 8,
                                           "slack_s": 0.5})
    assert pre.preemptions, "pressured trace must trigger preemptions"
    assert {e.kind for e in pre.preemptions} <= {"preempt", "resume"}
    assert pre.deadline_hit_rate() >= base.deadline_hit_rate()
    # virtual time is deterministic: same trace, same decisions
    again = replay_open_loop(trace, n_workers=8, arbiter="preemptive",
                             arbiter_kwargs={"inner": "fair", "n_workers": 8,
                                             "slack_s": 0.5})
    assert again.deadline_hit_rate() == pre.deadline_hit_rate()
    assert len(again.preemptions) == len(pre.preemptions)


def test_simulate_server_surfaces_preemptions():
    subs = _pressured_trace(80)
    res = simulate_server(subs, n_workers=4, arbiter="preemptive",
                          arbiter_kwargs={"inner": "fair", "n_workers": 4,
                                          "slack_s": 0.5})
    assert len(res.job_finish) == len(subs)
    assert isinstance(res.preemptions, list)
    fair = simulate_server(subs, n_workers=4, arbiter="fair")
    assert fair.preemptions == []


def test_threaded_server_with_preemptive_arbiter():
    cfg = SchedulerConfig(technique="SS", n_workers=2)
    srv = PipelineServer(cfg, arbiter=make_arbiter(
        "preemptive", inner="fair", n_workers=2, slack_s=0.0))
    dag = _int_dag(32, "chain2", "full")
    want = PipelineExecutor(dag, cfg).run().values["b"]
    for i in range(3):
        srv.submit(Submission(dag=_int_dag(32, "chain2", "full"),
                              name=f"j{i}", deadline_s=None if i else 30.0,
                              stage_costs={"a": np.full(32, 1e-6),
                                           "b": np.full(32, 1e-6)}))
    res = srv.serve()
    assert isinstance(res.preemptions, list)
    for i in range(3):
        assert res.jobs[f"j{i}"].values["b"] == want
