"""Heterogeneous co-execution: host chunk workers + device walker lanes (§13).

``core/placement.py`` decides WHERE each stage runs; this module runs the
decision. A ``HeteroExecutor`` executes one PipelineDAG on BOTH substrates
at once:

* **Host side** — ``config.n_workers`` threads drive the §9 machinery
  unchanged: per-stage queues/techniques, victim-ordered stealing,
  FIFO-head dependency gating, rotating stage cursors.
* **Device side** — ``n_device`` walker lanes each drain a frozen
  super-table shard: the stage's device row range [0, k) in ascending
  row order (exactly the §11 ``build_dag_tables`` slot order), streaming
  behind producers via the same row-completion gates. Slots execute the
  stage's host op — the vee device lowerings guarantee the per-tile math
  is bit-identical to the Pallas walker bodies (tests/test_device_dag.py),
  so a lane IS the walker's schedule, and swapping in the real kernel
  changes where the arithmetic runs, not what it computes.
* **Cross-substrate streaming** — elementwise consumers on either side
  pop as soon as the producer rows complete, regardless of which side
  produced them (the shared ``row_done`` gate is substrate-blind).
* **Cross-substrate rebalancing** — an idle host worker absorbs the TAIL
  of a device shard's unpopped remainder (coalescing contiguous concat
  tiles to its own granularity via the §12 ``rechunk_pending``), and a
  device lane whose shards are drained/blocked absorbs host chunks via
  the ordinary ``_try_pop`` path — so neither substrate idles while the
  other has work, the threaded analogue of ``rebalance_dag``'s
  persistent re-balancing.

**Bit-equality.** Sum stages fold their per-chunk partials in ascending
row order at stage completion (not completion order), so the combined
value depends only on the chunk boundaries — not on which substrate or
thread ran each chunk, nor on absorption. Run at tile granularity
(technique ``SS`` on a tile-unit DAG) this reproduces the host-only
``PipelineExecutor(technique="SS", n_workers=1)`` result bit-wise on the
vee linreg/recommendation lowerings (``tests/test_placement.py``).
Concat stages write disjoint rows and are bit-equal under any
placement/technique.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from .dag import (
    DagResult,
    EventLog,
    PipelineDAG,
    StageResult,
    TaskEvent,
    _resolve_stage_config,
    _stage_inputs,
    _StageRun,
    _task_ready,
    _try_pop,
)
from .executor import SchedulerConfig
from .online import rechunk_pending
from .placement import Placement, TransferEvent
from .telemetry import F_DEVICE, F_STOLEN, as_tracer

__all__ = ["HeteroExecutor", "HeteroResult", "split_device_tasks",
           "pop_device_task", "steal_device_tail"]


def split_device_tasks(
    sr: _StageRun, k: int, n_device: int
) -> tuple[list[deque], int]:
    """Carve the device row range [0, k) out of a freshly built stage run.

    Re-chunks the queued schedule so no chunk straddles the boundary
    (via ``_StageRun.resize_remaining``), then moves every task starting
    below ``k`` from the host queues into ``n_device`` shard deques
    (ascending rows, dealt round-robin — the ``assign_chunks`` analogue).
    Returns ``(shard_deques, remaining_delta)``; the caller folds the
    delta into its outstanding-task totals. Call before any pop.
    """
    shards: list[deque] = [deque() for _ in range(max(1, n_device))]
    if k <= 0:
        return shards, 0
    pend = sr.pending_chunks()
    split = []
    for s, z in pend:
        if s < k < s + z:
            split += [(s, k - s), (k, s + z - k)]
        else:
            split.append((s, z))
    delta = 0
    if split != pend:
        delta = sr.resize_remaining(split)
    dev_tasks = []
    for q in sr.queues:
        keep = [t for t in q if t[1] >= k]
        dev_tasks += [t for t in q if t[1] < k]
        q.clear()
        q.extend(keep)
    dev_tasks.sort(key=lambda t: t[1])
    for j, t in enumerate(dev_tasks):
        shards[j % len(shards)].append(t)
    return shards, delta


def pop_device_task(shards: list[deque], lane: int, sr: _StageRun,
                    runs: dict) -> tuple | None:
    """Pop the next runnable device slot for walker lane ``lane``.

    FIFO head of the lane's own shard first (super-table order), then the
    other shards' heads (a drained lane helps its neighbours before
    absorbing host work). Returns the task tuple or None.
    """
    n = len(shards)
    for j in range(n):
        dq = shards[(lane + j) % n]
        if dq and _task_ready(sr, runs, dq[0]):
            return dq.popleft()
    return None


def steal_device_tail(shards: list[deque], sr: _StageRun,
                      runs: dict) -> tuple[tuple | None, int]:
    """Absorb part of a device shard's unpopped tail onto the host side.

    Steals from the TAIL of the fullest shard deque (the §2 thief
    discipline). For concat stages a contiguous, runnable tail run of up
    to half the deque is coalesced into ONE host-granularity chunk via
    ``rechunk_pending`` (appended to the stage's realized schedule); sum
    stages move a single task unchanged, preserving the chunk boundaries
    the ascending partial fold depends on. Returns
    ``(task_or_None, remaining_delta)`` for the caller's totals.
    """
    dq = max(shards, key=len, default=None)
    if not dq:
        return None, 0
    if not _task_ready(sr, runs, dq[-1]):
        return None, 0
    if sr.stage.combine != "concat" or len(dq) < 2:
        return dq.pop(), 0
    # longest runnable, contiguous tail run (bounded to half the deque)
    run: list[tuple] = [dq[-1]]
    limit = max(1, len(dq) // 2)
    idx = len(dq) - 2
    while len(run) < limit and idx >= 0:
        t = dq[idx]
        if t[1] + t[2] != run[0][1] or not _task_ready(sr, runs, t):
            break
        run.insert(0, t)
        idx -= 1
    for _ in run:
        dq.pop()
    if len(run) == 1:
        return run[0], 0
    # the run is contiguous by construction, so merging at target=total
    # always collapses it to exactly one host-granularity chunk
    total = sum(z for _, _, z in run)
    (s0, z0), = rechunk_pending([(s, z) for _, s, z in run], total)
    task = (len(sr.costs), int(s0), int(z0))
    sr.schedule = np.vstack([
        np.asarray(sr.schedule).reshape(-1, 2),
        np.array([[s0, z0]]).reshape(-1, 2),
    ]).astype(np.int32)
    sr.costs = np.concatenate([sr.costs, np.zeros(1)])
    sr.executed = np.concatenate([sr.executed, np.zeros(1, dtype=bool)])
    sr.remaining += 1 - len(run)
    sr.resizes += 1
    return task, 1 - len(run)


@dataclass
class HeteroResult(DagResult):
    """Whole-DAG outcome of one heterogeneous co-execution run.

    Extends DagResult: ``per_worker_busy_s``/``per_worker_tasks`` list the
    host workers first, then the ``n_device`` walker lanes.
    ``absorbed_by_host`` / ``absorbed_by_device`` count cross-substrate
    rebalancing moves; ``cross_consumptions`` counts chunks that consumed
    at least one row the other substrate produced. Each such consumption
    also lands as a ``TransferEvent`` in ``transfer_events`` (zero
    duration — the copy is not separately timed on the threaded pool), so
    the inherited ``DagResult.stats`` folds the same counts into
    ``DagStats.transfers``/``transfer_s`` that the hetero simulator
    reports.
    """

    n_host_workers: int = 0
    n_device: int = 0
    absorbed_by_host: int = 0
    absorbed_by_device: int = 0
    cross_consumptions: dict[str, int] = field(default_factory=dict)
    placement: Placement | None = None


class HeteroExecutor:
    """Run a PipelineDAG across the host pool AND device walker lanes.

    ``config`` shapes the host side exactly as in PipelineExecutor
    (``Submission.per_stage`` overrides included); ``placement`` (a
    core.placement.Placement) assigns each stage HOST, DEVICE, or
    SPLIT(fraction) — the device owning the leading rows. ``n_device``
    walker lanes drain the device ranges in super-table order; with
    ``rebalance=True`` (default) idle host workers absorb device tails
    and drained device lanes absorb host chunks. See the module
    docstring for the substrate, streaming, and bit-equality semantics.
    """

    def __init__(
        self,
        dag: PipelineDAG,
        config: SchedulerConfig,
        placement: Placement,
        n_device: int = 1,
        rebalance: bool = True,
        tracer=None,
    ):
        self.dag = dag
        self.config = config
        self.placement = placement
        d = config.numa_domains
        self._domains = list(d) if d is not None else [0] * config.n_workers
        self.n_device = max(1, n_device)
        self.rebalance = rebalance
        self.tracer = as_tracer(tracer)

    def run(self, sub=None) -> HeteroResult:
        """Execute every stage to completion across both substrates.

        ``sub`` (a §14 ``Submission``) may carry per-submission knobs:
        ``sub.dag`` replaces the constructor DAG for this run,
        ``sub.per_stage`` supplies per-stage overrides, and
        ``sub.placement`` replaces the constructor placement.
        """
        res, _ck = self._run(sub, preempt_after=None)
        return res

    def run_preemptible(self, preempt_after: int, sub=None):
        """Run until ``preempt_after`` chunks have folded, then checkpoint.

        The §15 eviction protocol on the co-execution pool: once the
        count is reached, host workers and device lanes stop *popping*
        but finish the chunk they hold (chunk-boundary semantics), and
        the unpopped remainder — host queues AND device shard deques —
        freezes into a ``core.preempt.JobCheckpoint``. Returns
        ``(HeteroResult, None)`` when the run drains first, else
        ``(None, checkpoint)``; ``core.preempt.resume_on_host`` (or a
        fresh device lowering) continues it bit-equal, because the sum
        fold here is already the ascending-prefix association the
        checkpoint format requires.
        """
        return self._run(sub, preempt_after=int(preempt_after))

    def _run(self, sub, preempt_after: int | None):
        """Shared body of run/run_preemptible."""
        overrides = {}
        if sub is not None:
            from .submit import as_submission

            sub = as_submission(sub)
            if (sub.dag is not None and sub.dag is not self.dag) \
                    or sub.placement is not None:
                ex = HeteroExecutor(
                    sub.dag if sub.dag is not None else self.dag,
                    self.config,
                    sub.placement if sub.placement is not None
                    else self.placement,
                    n_device=self.n_device, rebalance=self.rebalance,
                    tracer=self.tracer)
                return ex._run(sub.replace(dag=None, placement=None),
                               preempt_after)
            overrides.update(sub.per_stage or {})
        runs = {name: _StageRun(
                    self.dag.stages[name],
                    _resolve_stage_config(self.config, self.dag.stages[name],
                                          overrides.get(name)),
                    self._domains)
                for name in self.dag.order}
        order = [runs[n] for n in self.dag.order]
        nstages = len(order)
        n_workers = self.config.n_workers
        n_device = self.n_device
        n_lanes = n_workers + n_device

        device_qs: dict[str, list[deque]] = {}
        remaining_total = sum(sr.remaining for sr in order)
        for name in self.dag.order:
            sr = runs[name]
            k = self.placement.device_rows(name, sr.stage.n_rows)
            shards, delta = split_device_tasks(sr, k, n_device)
            device_qs[name] = shards
            remaining_total += delta

        # which substrate produced each row (0 host, 1 device): feeds the
        # cross-substrate consumption accounting in HeteroResult.stats
        row_side = {n: np.zeros(runs[n].stage.n_rows, dtype=np.int8)
                    for n in self.dag.order}
        # per sum stage: [accumulator, next row to fold, out-of-order
        # partials] — chunks fold into the accumulator the moment the
        # ascending prefix is contiguous, so memory stays bounded by the
        # out-of-order window instead of the whole chunk count
        sum_state: dict[str, list] = {
            n: [None, 0, {}] for n in self.dag.order
            if runs[n].stage.combine == "sum"}
        full_cross: dict[tuple[str, int], bool] = {}

        cond = threading.Condition()
        events = EventLog(TaskEvent)
        tracer = self.tracer
        traced = tracer.enabled
        tjob = tracer.job
        transfers: list[TransferEvent] = []
        errors: list[BaseException] = []
        busy = [0.0] * n_lanes
        ntasks = [0] * n_lanes
        steals = [0]
        absorbed = [0, 0]   # [by_host, by_device]
        cross: dict[str, int] = {}
        n_done = [0]
        stop = [False]      # §15: lanes stop popping at the next boundary
        t0_run = time.perf_counter()

        def consumed_cross(sr: _StageRun, task, is_dev: bool) -> str | None:
            """Producer whose rows crossed the substrate boundary, or None."""
            _, s, z = task
            me = 1 if is_dev else 0
            for d in sr.stage.deps:
                side = row_side[d.producer]
                if d.kind == "full":
                    # the producer is done (pop gating), so its row sides
                    # are final: scan once per (producer, substrate)
                    key = (d.producer, me)
                    if key not in full_cross:
                        full_cross[key] = bool((side != me).any())
                    if full_cross[key]:
                        return d.producer
                elif (side[s:s + z] != me).any():
                    return d.producer
            return None

        def record(sr, task, value, dt, lane, rel0, rel1, stolen, wait_s,
                   is_dev):
            """Fold one chunk into stage + run accounting (lock held)."""
            nonlocal remaining_total
            i, s, z = task
            sr.record(task, value, dt, rel0, rel1)
            if is_dev:
                row_side[sr.stage.name][s:s + z] = 1
            name = sr.stage.name
            state = sum_state.get(name)
            if state is not None:
                # ascending-row fold: bit-equal to the host-only SS/1-worker
                # accumulation no matter which lane ran which chunk
                state[2][s] = (value, z)
                acc, nxt, parts = state
                while nxt in parts:
                    v, zz = parts.pop(nxt)
                    acc = v if acc is None else acc + v
                    nxt += zz
                state[0], state[1] = acc, nxt
                if sr.done:
                    sr.acc = sr.value = acc
            remaining_total -= 1
            events.append_raw(name, i, s, z, lane, rel0, rel1, stolen, wait_s)
            if traced:
                tracer.record_raw(
                    "exec", tjob, name, i, lane, rel0, rel1,
                    (F_STOLEN if stolen else 0) | (F_DEVICE if is_dev else 0),
                    wait_s)
            busy[lane] += dt
            ntasks[lane] += 1
            steals[0] += int(stolen)
            n_done[0] += 1
            if (preempt_after is not None and not stop[0]
                    and remaining_total > 0 and n_done[0] >= preempt_after):
                stop[0] = True

        def pick(lane: int, is_dev: bool, cursor: int):
            """Next (run, task, stolen, absorbed, cursor, remaining-delta)
            for this lane, or None (lock held)."""
            if is_dev:
                d = lane - n_workers
                for kk in range(nstages):
                    idx = (cursor + kk) % nstages
                    sr = order[idx]
                    got = pop_device_task(device_qs[sr.stage.name], d, sr,
                                          runs)
                    if got is not None:
                        return sr, got, False, False, (idx + 1) % nstages, 0
                if self.rebalance:
                    for kk in range(nstages):
                        idx = (cursor + kk) % nstages
                        sr = order[idx]
                        if sr.remaining == 0:
                            continue
                        got, stolen = _try_pop(sr, runs, lane)
                        if got is not None:
                            absorbed[1] += 1
                            return (sr, got, stolen, True,
                                    (idx + 1) % nstages, 0)
                return None
            for kk in range(nstages):
                idx = (cursor + kk) % nstages
                sr = order[idx]
                if sr.remaining == 0:
                    continue
                got, stolen = _try_pop(sr, runs, lane)
                if got is not None:
                    return sr, got, stolen, False, (idx + 1) % nstages, 0
            if self.rebalance:
                for kk in range(nstages):
                    idx = (cursor + kk) % nstages
                    sr = order[idx]
                    got, delta = steal_device_tail(
                        device_qs[sr.stage.name], sr, runs)
                    if got is not None:
                        absorbed[0] += 1
                        return sr, got, True, True, (idx + 1) % nstages, delta
            return None

        def worker(lane: int) -> None:
            """Pool/walker thread: pop runnable chunks until the DAG drains.

            The whole loop runs under one error boundary: an exception
            anywhere (pick/steal bookkeeping as much as a stage op) lands
            in ``errors`` and is re-raised by run() — a lane must never
            die silently and leave the run to report success without it.
            """
            nonlocal remaining_total
            is_dev = lane >= n_workers
            cursor = lane % nstages
            try:
                while True:
                    sr = task = None
                    stolen = was_absorbed = False
                    t_idle = time.perf_counter()
                    with cond:
                        while True:
                            if errors or stop[0] or remaining_total == 0:
                                return
                            got = pick(lane, is_dev, cursor)
                            if got is not None:
                                (sr, task, stolen, was_absorbed, cursor,
                                 delta) = got
                                remaining_total += delta
                                break
                            cond.wait(timeout=0.05)
                        inputs = _stage_inputs(sr, runs)
                        is_cross = consumed_cross(sr, task, is_dev)
                    _, s, z = task
                    t0 = time.perf_counter()
                    value = sr.stage.op(inputs, s, z)
                    t1 = time.perf_counter()
                    with cond:
                        record(sr, task, value, t1 - t0, lane,
                               t0 - t0_run, t1 - t0_run,
                               stolen or was_absorbed, t0 - t_idle, is_dev)
                        if is_cross is not None:
                            cross[sr.stage.name] = \
                                cross.get(sr.stage.name, 0) + 1
                            # zero duration: the threaded pool shares
                            # memory, the copy is not separately timed
                            transfers.append(TransferEvent(
                                is_cross, sr.stage.name, z,
                                t0 - t0_run, t0 - t0_run, is_dev))
                            if traced:
                                tracer.record_raw(
                                    "transfer", tjob, sr.stage.name,
                                    task[0], lane, t0 - t0_run, t0 - t0_run,
                                    F_DEVICE if is_dev else 0, 0.0,
                                    f"from={is_cross}")
                        cond.notify_all()
            except BaseException as e:  # surfaced to the caller below
                with cond:
                    errors.append(e)
                    cond.notify_all()

        threads = [threading.Thread(target=worker, args=(lane,), daemon=True)
                   for lane in range(n_lanes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        wall = time.perf_counter() - t0_run

        if stop[0] and remaining_total > 0:
            from .preempt import JobCheckpoint, StageCheckpoint

            stages_ck = {}
            for name in self.dag.order:
                sr = runs[name]
                pend = list(sr.pending_chunks())
                for dq in device_qs[name]:
                    pend.extend((int(s), int(z)) for _i, s, z in dq)
                state = sum_state.get(name)
                if state is not None:
                    acc, nxt, parts = state
                    parts_t = tuple((int(s), int(z), v)
                                    for s, (v, z) in sorted(parts.items()))
                else:
                    acc, nxt, parts_t = None, 0, ()
                stages_ck[name] = StageCheckpoint(
                    stage=name, n_rows=int(sr.stage.n_rows),
                    combine=sr.stage.combine,
                    pending=tuple(sorted(pend)),
                    row_done=sr.row_done.copy(),
                    out=None if sr.out is None else sr.out.copy(),
                    acc=acc, acc_next=int(nxt), parts=parts_t,
                    executed=int(sr.executed.sum()))
            ck = JobCheckpoint(job="hetero", stages=stages_ck,
                               substrate="hetero", taken_at=wall,
                               reason="preempt_after")
            ck.validate(self.dag)
            if traced:
                tracer.mark("checkpoint", wall, tjob,
                            detail="preempt_after")
            return None, ck

        stage_results = {
            name: StageResult(value=sr.value, schedule=sr.schedule,
                              per_task_costs=sr.costs, config=sr.cfg,
                              t_first=sr.t_first, t_last=sr.t_last)
            for name, sr in runs.items()
        }
        res = HeteroResult(
            values={n: r.value for n, r in stage_results.items()},
            stages=stage_results, events=events, wall_time_s=wall,
            steals=steals[0], per_worker_busy_s=busy, per_worker_tasks=ntasks,
            n_host_workers=n_workers, n_device=n_device,
            absorbed_by_host=absorbed[0], absorbed_by_device=absorbed[1],
            cross_consumptions=cross, placement=self.placement,
            transfer_events=transfers)
        return res, None
