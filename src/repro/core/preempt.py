"""Preemptive multi-tenancy: checkpoint, preempt, and migrate in-flight jobs.

DESIGN.md §15. The §10 arbiters decide whose chunk runs *next*; this
module makes them able to stop a RUNNING job and move it:

* ``StageCheckpoint`` freezes one stage's unpopped remainder — the
  queued ``(start, size)`` chunks plus everything needed to resume
  bit-equal: the concat row buffer, the ascending-prefix sum
  accumulator, and any out-of-order sum partials.
* ``PreemptableStageRun`` is a ``_StageRun`` that folds sum partials in
  ascending row order (the §13 hetero fold) so a checkpoint taken at ANY
  chunk boundary has a well-defined resumable accumulator.
* ``PreemptiveRunner`` runs a DAG on the real thread pool with
  chunk-boundary preemption: workers finish the chunk they hold, then
  stop popping; ``run`` returns either a ``DagResult`` or a
  ``JobCheckpoint``. ``run(resume_from=ck)`` continues a checkpoint.
* ``migrate_to_device`` re-lowers a host checkpoint's remainder onto the
  device walker (kernels/dag_walk.py) via ``build_dag_tables``:
  completed stages become plain operands, partially-done sum stages are
  seeded with their prefix accumulator at their first pending slot, and
  completed concat tiles still read by pending elementwise consumers are
  replayed (bit-identical rewrites). ``run_device_prefix`` +
  ``resume_on_host`` is the reverse direction.
* ``PreemptiveArbiter`` wraps any §10 arbiter: when a deadline job's
  fluid slack (the §14 admission estimate) goes negative, lower-priority
  jobs with no live deadline are parked at their next chunk boundary and
  resume when the pressure clears. Composes with the threaded
  ``PipelineServer``, virtual-time ``simulate_server``, and the §14
  ``replay_open_loop`` engine unchanged — all three consult
  ``Arbiter.order`` per pop, which is exactly the chunk boundary.

Why chunk-boundary-only preemption keeps bit-equality: ops run outside
the runtime lock and fold at ``record()``; a preempted worker never
abandons a chunk mid-op, so the checkpoint sees each chunk either fully
folded or still queued — never a torn partial. Resuming replays the
queued remainder through the same ascending fold the unpreempted run
uses, so the float association is identical. Across substrates
(``migrate_to_device``, ``run_device_prefix``) the association is kept
too, so the result matches a never-preempted run as closely as host ops
and walker bodies agree: bit for bit where they run the same arithmetic
(the CPU), to float32 rounding on a TPU (vee.apps.DeviceLowering).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from .dag import (DagResult, EventLog, PipelineDAG, StageResult, TaskEvent,
                  _StageRun, _resolve_stage_config, _stage_inputs, _try_pop)
from .online import rechunk_pending
from .server import Arbiter
from .telemetry import F_STOLEN, as_tracer

__all__ = [
    "StageCheckpoint", "JobCheckpoint", "PreemptableStageRun",
    "PreemptiveRunner", "resume_on_host", "migrate_to_device",
    "run_device_prefix", "PreemptionEvent", "PreemptiveArbiter",
]


# ---------------------------------------------------------------------------
# checkpoint format


@dataclass(frozen=True, eq=False)
class StageCheckpoint:
    """One stage frozen at a chunk boundary.

    ``pending`` is the unpopped remainder as ascending disjoint
    ``(start, size)`` row ranges; together with the True rows of
    ``row_done`` it covers the stage's row space exactly once (no chunk
    is lost or duplicated — ``validate`` proves it). ``out`` is the
    concat buffer (rows outside ``row_done`` are unspecified), ``acc``
    the ascending-prefix sum accumulator covering rows
    ``[0, acc_next)``, and ``parts`` any completed sum chunks that
    arrived out of order (``(start, size, value)``, waiting for the
    prefix to reach them). ``executed`` counts chunks folded before the
    checkpoint — the exactly-once ledger the property tests audit.
    """

    stage: str
    n_rows: int
    combine: str
    pending: tuple[tuple[int, int], ...]
    row_done: np.ndarray
    out: np.ndarray | None = None
    acc: Any = None
    acc_next: int = 0
    parts: tuple[tuple[int, int, Any], ...] = ()
    executed: int = 0

    @property
    def empty(self) -> bool:
        """True when the preemption landed after the stage's last pop."""
        return not self.pending

    @property
    def remaining_rows(self) -> int:
        """Rows still to execute."""
        return int(sum(z for _, z in self.pending))

    def validate(self) -> None:
        """Prove the exactly-once invariant: pending ∪ done == rows, disjoint."""
        cover = np.zeros(self.n_rows, dtype=int)
        for s, z in self.pending:
            if z <= 0 or s < 0 or s + z > self.n_rows:
                raise ValueError(
                    f"stage {self.stage!r}: pending chunk ({s},{z}) out of "
                    f"range for n_rows={self.n_rows}")
            cover[s:s + z] += 1
        if (cover > 1).any():
            raise ValueError(f"stage {self.stage!r}: overlapping pending chunks")
        done = np.asarray(self.row_done, dtype=bool)
        if done.shape != (self.n_rows,):
            raise ValueError(f"stage {self.stage!r}: row_done shape mismatch")
        if (cover[done] > 0).any():
            raise ValueError(
                f"stage {self.stage!r}: pending chunk overlaps completed rows")
        if not (done | (cover > 0)).all():
            raise ValueError(
                f"stage {self.stage!r}: rows neither done nor pending (lost)")
        if self.combine == "sum":
            if not done[:self.acc_next].all():
                raise ValueError(
                    f"stage {self.stage!r}: acc_next={self.acc_next} exceeds "
                    "the completed prefix")
            if self.acc_next > 0 and self.acc is None:
                raise ValueError(
                    f"stage {self.stage!r}: non-empty prefix with acc=None")
            for s, z, _v in self.parts:
                if s < self.acc_next:
                    raise ValueError(
                        f"stage {self.stage!r}: partial at {s} already folded")
                if not done[s:s + z].all():
                    raise ValueError(
                        f"stage {self.stage!r}: partial at {s} not marked done")
            if not self.pending and self.parts:
                raise ValueError(
                    f"stage {self.stage!r}: complete stage with unfolded "
                    "partials (hole in row space)")
        elif self.combine == "concat":
            if done.any() and self.out is None:
                raise ValueError(
                    f"stage {self.stage!r}: completed rows but no out buffer")
            if self.out is not None and self.out.shape[0] != self.n_rows:
                raise ValueError(f"stage {self.stage!r}: out buffer shape "
                                 f"{self.out.shape} != n_rows {self.n_rows}")


@dataclass(frozen=True, eq=False)
class JobCheckpoint:
    """A whole job frozen at a chunk boundary, ready to resume anywhere.

    ``substrate`` records where the work ran before the freeze ("host"
    or "device") — informational; the checkpoint format is
    substrate-agnostic, which is what makes mid-flight migration a plain
    resume on the other side.
    """

    job: str
    stages: dict[str, StageCheckpoint]
    substrate: str = "host"
    taken_at: float = 0.0
    reason: str = "preempted"

    @property
    def empty(self) -> bool:
        """True when no stage has pending work (resume completes at once)."""
        return all(s.empty for s in self.stages.values())

    @property
    def remaining_chunks(self) -> int:
        """Unpopped chunks across all stages."""
        return sum(len(s.pending) for s in self.stages.values())

    def validate(self, dag: PipelineDAG | None = None) -> None:
        """Per-stage invariants, plus shape agreement with ``dag`` if given."""
        for name, sck in self.stages.items():
            if name != sck.stage:
                raise ValueError(f"checkpoint key {name!r} != stage {sck.stage!r}")
            sck.validate()
        if dag is not None:
            if set(self.stages) != set(dag.order):
                raise ValueError(
                    f"checkpoint stages {sorted(self.stages)} != DAG stages "
                    f"{sorted(dag.order)}")
            for name in dag.order:
                st = dag.stages[name]
                sck = self.stages[name]
                if sck.n_rows != st.n_rows or sck.combine != st.combine:
                    raise ValueError(
                        f"stage {name!r}: checkpoint ({sck.n_rows}, "
                        f"{sck.combine!r}) != DAG ({st.n_rows}, {st.combine!r})")


# ---------------------------------------------------------------------------
# preemptable host execution


class PreemptableStageRun(_StageRun):
    """A ``_StageRun`` whose sum fold is ascending-prefix, hence freezable.

    The base class folds sum chunks in completion order — fine for a run
    that always finishes, but a checkpoint taken mid-run would hold an
    accumulator with an unreproducible association. This subclass keeps
    the §13 hetero fold instead: completed chunks park in ``sum_state``
    until the ascending prefix reaches them, so at ANY chunk boundary
    ``acc`` covers exactly ``[0, acc_next)`` in row order and the
    leftover partials are explicit. Unpreempted runs produce the same
    final value as ``HeteroExecutor`` — and bit-equal the §9 host
    reference under the SS / single-worker regime the device tests pin.
    """

    __slots__ = ("sum_state",)

    def __init__(self, stage, cfg, domains):
        super().__init__(stage, cfg, domains)
        # [prefix acc, next row to fold, {start: (value, size)}]
        self.sum_state = None if stage.combine == "concat" else [None, 0, {}]

    def record(self, task, value, dt, rel0, rel1) -> None:
        """Base fold plus the ascending sum fold (caller holds the lock)."""
        super().record(task, value, dt, rel0, rel1)
        st = self.sum_state
        if st is None:
            return
        _i, s, z = task
        st[2][int(s)] = (value, int(z))
        acc, nxt, parts = st
        while nxt in parts:
            v, zz = parts.pop(nxt)
            acc = v if acc is None else acc + v
            nxt += zz
        st[0], st[1] = acc, nxt
        if self.done:
            # override the base completion-order fold with the
            # deterministic ascending association
            self.acc = self.value = acc

    def checkpoint(self) -> StageCheckpoint:
        """Freeze the unpopped remainder (caller holds the lock)."""
        pend = tuple(sorted((int(s), int(z))
                            for (s, z) in self.pending_chunks()))
        if self.sum_state is not None:
            acc, nxt, parts = self.sum_state
            parts_t = tuple((int(s), int(z), v)
                            for s, (v, z) in sorted(parts.items()))
        else:
            acc, nxt, parts_t = None, 0, ()
        return StageCheckpoint(
            stage=self.stage.name, n_rows=int(self.stage.n_rows),
            combine=self.stage.combine, pending=pend,
            row_done=self.row_done.copy(),
            out=None if self.out is None else self.out.copy(),
            acc=acc, acc_next=int(nxt), parts=parts_t,
            executed=int(self.executed.sum()))

    @classmethod
    def restore(cls, ck: StageCheckpoint, stage, cfg, domains,
                rechunk_target: int | None = None) -> "PreemptableStageRun":
        """Rebuild a run whose queued work is the checkpoint's remainder.

        The pending ranges are dealt as fresh tasks under this run's
        queue layout (optionally re-chunked to ``rechunk_target`` rows
        for concat stages — sum remainders keep their boundaries, which
        the ascending fold's bit-equality depends on). An empty
        remainder restores directly to ``done`` with the checkpointed
        value — the preempt-after-last-pop edge.
        """
        if (ck.stage != stage.name or ck.n_rows != stage.n_rows
                or ck.combine != stage.combine):
            raise ValueError(
                f"checkpoint ({ck.stage!r}, {ck.n_rows}, {ck.combine!r}) does "
                f"not match stage ({stage.name!r}, {stage.n_rows}, "
                f"{stage.combine!r})")
        sr = cls(stage, cfg, domains)
        pend = [(int(s), int(z)) for s, z in ck.pending]
        if rechunk_target is not None and stage.combine == "concat" and pend:
            pend = [(int(s), int(z))
                    for s, z in rechunk_pending(pend, rechunk_target)]
        tasks = [(i, s, z) for i, (s, z) in enumerate(pend)]
        for q in sr.queues:
            q.clear()
        sr.tasks = tasks
        sr.schedule = np.array([[s, z] for _, s, z in tasks],
                               dtype=np.int32).reshape(-1, 2)
        sr._deal(tasks)
        sr.row_done = np.asarray(ck.row_done, dtype=bool).copy()
        sr.remaining = len(tasks)
        sr.out = None if ck.out is None else np.array(ck.out, copy=True)
        sr.acc = ck.acc
        sr.costs = np.zeros(len(tasks))
        sr.executed = np.zeros(len(tasks), dtype=bool)
        sr.resizes = 0
        if sr.sum_state is not None:
            sr.sum_state = [ck.acc, int(ck.acc_next),
                            {int(s): (v, int(z)) for s, z, v in ck.parts}]
        sr.done = sr.remaining == 0
        if sr.done:
            sr.value = sr.out if stage.combine == "concat" else ck.acc
        return sr


class PreemptiveRunner:
    """PipelineExecutor with chunk-boundary preemption and resume.

    ``preempt_after`` stops the run once that many chunks have been
    folded *this run* (workers finish the chunk they hold first);
    ``trigger(n_done)`` is the programmable form. ``run`` returns
    ``(DagResult, None)`` on completion or ``(None, JobCheckpoint)``
    when preempted with work left; ``run(resume_from=ck)`` continues a
    checkpoint (from this runner, ``HeteroExecutor``, or a device prefix
    — the format is substrate-agnostic).
    """

    def __init__(self, dag: PipelineDAG, config,
                 preempt_after: int | None = None,
                 trigger: Callable[[int], bool] | None = None,
                 rechunk_target: int | None = None,
                 job: str = "job", tracer=None):
        self.dag = dag
        self.config = config
        d = config.numa_domains
        self._domains = list(d) if d is not None else [0] * config.n_workers
        self.preempt_after = preempt_after
        self.trigger = trigger
        self.rechunk_target = rechunk_target
        self.job = job
        self.tracer = as_tracer(tracer)

    def _want_preempt(self, n_done: int) -> bool:
        if self.preempt_after is not None and n_done >= self.preempt_after:
            return True
        return self.trigger is not None and self.trigger(n_done)

    def run(self, resume_from: JobCheckpoint | None = None, overrides=None):
        """Execute (or continue) the DAG; see the class docstring."""
        overrides = dict(overrides or {})
        if resume_from is not None:
            resume_from.validate(self.dag)
        runs: dict[str, PreemptableStageRun] = {}
        for name in self.dag.order:
            stage = self.dag.stages[name]
            cfg = _resolve_stage_config(self.config, stage,
                                        overrides.get(name))
            if resume_from is None:
                runs[name] = PreemptableStageRun(stage, cfg, self._domains)
            else:
                runs[name] = PreemptableStageRun.restore(
                    resume_from.stages[name], stage, cfg, self._domains,
                    rechunk_target=self.rechunk_target)
        order = [runs[n] for n in self.dag.order]
        nstages = len(order)
        n_workers = self.config.n_workers
        cond = threading.Condition()
        remaining_total = sum(sr.remaining for sr in order)
        events = EventLog(TaskEvent)
        tracer = self.tracer
        traced = tracer.enabled
        if traced and resume_from is not None:
            tracer.mark("resume", 0.0, self.job, detail=resume_from.reason)
        errors: list[BaseException] = []
        busy = [0.0] * n_workers
        ntasks = [0] * n_workers
        steals = [0]
        n_done = [0]
        stop = [False]
        t0_run = time.perf_counter()

        def record(sr, task, value, dt, wid, rel0, rel1, stolen, wait_s=0.0):
            nonlocal remaining_total
            i, s, z = task
            sr.record(task, value, dt, rel0, rel1)
            remaining_total -= 1
            events.append_raw(sr.stage.name, i, s, z, wid, rel0, rel1,
                              stolen, wait_s)
            if traced:
                tracer.record_raw("exec", self.job, sr.stage.name, i, wid,
                                  rel0, rel1, F_STOLEN if stolen else 0,
                                  wait_s)
            busy[wid] += dt
            ntasks[wid] += 1
            steals[0] += int(stolen)
            n_done[0] += 1
            # the preemption point: every chunk boundary, after the fold
            if (not stop[0] and remaining_total > 0
                    and self._want_preempt(n_done[0])):
                stop[0] = True

        def worker(wid: int) -> None:
            cursor = wid % nstages
            while True:
                sr = task = None
                stolen = False
                t_idle = time.perf_counter()
                with cond:
                    while True:
                        if errors or stop[0] or remaining_total == 0:
                            return
                        for k in range(nstages):
                            idx = (cursor + k) % nstages
                            cand = order[idx]
                            if cand.remaining == 0:
                                continue
                            got, stolen = _try_pop(cand, runs, wid)
                            if got is not None:
                                sr, task = cand, got
                                cursor = (idx + 1) % nstages
                                break
                        if task is not None:
                            break
                        cond.wait(timeout=0.05)
                    inputs = _stage_inputs(sr, runs)
                _, s, z = task
                t0 = time.perf_counter()
                try:
                    value = sr.stage.op(inputs, s, z)
                    t1 = time.perf_counter()
                    with cond:
                        record(sr, task, value, t1 - t0, wid,
                               t0 - t0_run, t1 - t0_run, stolen, t0 - t_idle)
                        cond.notify_all()
                except BaseException as e:
                    with cond:
                        errors.append(e)
                        cond.notify_all()
                    return

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        wall = time.perf_counter() - t0_run
        if stop[0] and remaining_total > 0:
            ck = JobCheckpoint(
                job=self.job,
                stages={n: runs[n].checkpoint() for n in self.dag.order},
                substrate="host", taken_at=wall, reason="trigger")
            ck.validate(self.dag)
            if traced:
                tracer.mark("checkpoint", wall, self.job,
                            detail=f"chunks_left={ck.remaining_chunks}")
            return None, ck
        stage_results = {
            name: StageResult(value=sr.value, schedule=sr.schedule,
                              per_task_costs=sr.costs, config=sr.cfg,
                              t_first=sr.t_first, t_last=sr.t_last)
            for name, sr in runs.items()
        }
        res = DagResult(
            values={n: r.value for n, r in stage_results.items()},
            stages=stage_results, events=events, wall_time_s=wall,
            steals=steals[0], per_worker_busy_s=busy, per_worker_tasks=ntasks)
        return res, None


def resume_on_host(ck: JobCheckpoint, dag: PipelineDAG, config,
                   overrides=None, tracer=None) -> DagResult:
    """Run a checkpoint's remainder to completion on the host pool."""
    res, left = PreemptiveRunner(dag, config, job=ck.job,
                                 tracer=tracer).run(
        resume_from=ck, overrides=overrides)
    assert left is None  # no trigger installed, the run cannot re-preempt
    return res


# ---------------------------------------------------------------------------
# host <-> device mid-flight migration


def _tile_sets(ck: JobCheckpoint) -> dict[str, set[int]]:
    """Pending tile indices per stage (checkpoint rows ARE tile units)."""
    pending: dict[str, set[int]] = {}
    for n, sck in ck.stages.items():
        tiles: set[int] = set()
        for s, z in sck.pending:
            tiles.update(range(s, s + z))
        pending[n] = tiles
    return pending


def migrate_to_device(ck: JobCheckpoint, lowering, tracer=None):
    """Resume a host checkpoint on the device walker, bit-equal.

    ``lowering`` is the vee ``DeviceLowering`` whose tile-unit host DAG
    produced ``ck``. The remainder is re-lowered with ``build_dag_tables``
    (technique SS — one tile per slot, matching the checkpoint's tile
    granularity) and filtered to the pending tiles:

    * fully-completed stages are dropped from the walker and their
      checkpointed values fed back as plain operands (the producer-as-operand
      trick of ``dag_walk_stagewise``);
    * partially-done sum stages keep their pending slots and are seeded
      with the checkpoint's prefix accumulator at their first slot —
      added once under ``pl.when``, before the slot's own contribution,
      so the fold continues the exact host association (requires an
      ascending-prefix checkpoint: out-of-order partials raise, resume
      those on host);
    * completed concat tiles still read by a pending elementwise
      consumer are replayed — the rewrite is bit-identical, so replay
      beats shipping per-tile state into the kernel.

    Returns ``{stage: np.ndarray}`` in row space for every stage — the
    same shape ``run_device_dag`` produces, bit-equal to the
    never-preempted run under the SS / single-worker host regime.
    """
    from jax.experimental import pallas as pl

    from ..kernels.dag_walk import WalkOperand, dag_walk
    from .device_schedule import build_dag_tables_cached

    dag = lowering.dag
    tile = lowering.tile
    ck.validate(dag)
    ddt = build_dag_tables_cached(dag, 1, "SS", n_shards=1)
    table = ddt.tables[0]
    names = list(ddt.stage_names)
    by_name = {s.name: s for s in lowering.stages}

    pending = _tile_sets(ck)
    for n, sck in ck.stages.items():
        if sck.combine == "sum" and sck.parts:
            raise ValueError(
                f"stage {n!r}: out-of-order sum partials cannot be seeded "
                "into the walker's ascending fold; resume on host instead")

    # tiles each stage must execute on-device: its pending tiles, plus
    # replays of completed producer tiles that pending consumers read
    need = {n: set(pending[n]) for n in names}
    changed = True
    while changed:
        changed = False
        for n in names:
            for prod, kind in by_name[n].reads:
                if kind != "rows":
                    continue  # full reads see the (seeded) final accumulator
                missing = {t for t in need[n]
                           if t not in need[prod] and t not in pending[prod]}
                if missing:
                    need[prod] |= missing
                    changed = True

    kept = [n for n in names if need[n]]
    kept_set = set(kept)
    new_id = {n: k for k, n in enumerate(kept)}

    operands = list(lowering.operands)
    values = dict(lowering.values)
    stages = []
    for n in kept:
        ws = by_name[n]
        sck = ck.stages[n]
        if ws.combine == "sum" and sck.acc is not None:
            # seed the prefix accumulator once, at this stage's first slot
            key = f"{n}__resume"
            operands.append(WalkOperand(key, tuple(ws.out_shape),
                                        ("zero",) * len(ws.out_shape)))
            values[key] = np.asarray(sck.acc, dtype=ws.out_dtype)
            stages.append((ws, key))
        else:
            stages.append((ws, None))

    rows_tbl = []
    for sid, start, size in table:
        if size <= 0:
            continue
        n = names[int(sid)]
        if n in kept_set and int(start) in need[n]:
            rows_tbl.append((new_id[n], int(start), int(size)))
    new_table = np.asarray(rows_tbl, dtype=np.int32).reshape(-1, 3)

    first_slot = {}
    for i, (sid, _s, _z) in enumerate(new_table):
        first_slot.setdefault(int(sid), i)

    def _seeded(body, key, k0):
        def wrapped(ctx, ins, out):
            @pl.when((ctx.slot == k0) & (ctx.inner == 0))
            def _resume():
                out[...] += ins[key][...]
            body(ctx, ins, out)
        return wrapped

    walk_stages = []
    for ws, key in stages:
        if key is not None:
            ws = dataclasses.replace(
                ws, operands=ws.operands + (key,),
                body=_seeded(ws.body, key, first_slot[new_id[ws.name]]))
        walk_stages.append(ws)

    # dropped stages read by kept ones come back as plain operands
    for ws in walk_stages:
        for prod, kind in ws.reads:
            if prod in kept_set:
                continue
            p = by_name[prod]
            sck = ck.stages[prod]
            if kind == "full":
                operands.append(WalkOperand(prod, tuple(p.out_shape),
                                            ("zero",) * len(p.out_shape)))
                values[prod] = np.asarray(sck.acc, dtype=p.out_dtype)
            else:
                operands.append(WalkOperand(
                    prod, (tile,) + tuple(p.out_shape[1:]),
                    ("row",) + ("zero",) * (len(p.out_shape) - 1)))
                values[prod] = np.asarray(sck.out, dtype=p.out_dtype).reshape(
                    tuple(p.out_shape))

    tracer = as_tracer(tracer)
    if tracer.enabled:
        tracer.mark("migrate", float(ck.taken_at), ck.job,
                    detail=f"to_device slots={len(new_table)}")
    if len(new_table):
        scaled = new_table.copy()
        scaled[:, 1:] *= tile
        walked = dag_walk(walk_stages, operands, values, scaled, tile)
    else:
        walked = {}

    final: dict[str, np.ndarray] = {}
    for n in names:
        ws = by_name[n]
        sck = ck.stages[n]
        if n in kept_set:
            if ws.combine == "sum":
                final[n] = np.asarray(walked[n])
            else:
                buf = (np.zeros(tuple(ws.out_shape), ws.out_dtype)
                       if sck.out is None
                       else np.asarray(sck.out).reshape(tuple(ws.out_shape)))
                dev = np.asarray(walked[n])
                for t in sorted(need[n]):
                    buf[t * tile:(t + 1) * tile] = dev[t * tile:(t + 1) * tile]
                final[n] = buf
        else:
            if ws.combine == "sum":
                final[n] = np.asarray(sck.acc)
            elif sck.out is None:
                final[n] = np.zeros(tuple(ws.out_shape), ws.out_dtype)
            else:
                final[n] = np.asarray(sck.out).reshape(tuple(ws.out_shape))
    return final


def run_device_prefix(lowering, n_slots: int):
    """Run the first ``n_slots`` super-table slots, then checkpoint.

    The device side of mid-flight migration: freeze the lowering with
    ``build_dag_tables`` (SS, one tile per slot), drain only a prefix of
    the table — a prefix is always dependency-closed, since every
    producer slot precedes its consumers — and package the rest as a
    ``JobCheckpoint`` in the host format (tile-unit rows): concat tiles
    land in the ``out`` buffer, sum slots fold into an ascending-prefix
    ``acc``. ``resume_on_host`` then finishes the job bit-equal to the
    never-preempted host run.

    Returns ``(checkpoint, walked)`` where ``walked`` is the raw
    row-space walker output of the prefix.
    """
    from ..kernels.dag_walk import dag_walk
    from .device_schedule import build_dag_tables_cached

    dag = lowering.dag
    tile = lowering.tile
    ddt = build_dag_tables_cached(dag, 1, "SS", n_shards=1)
    live = ddt.tables[0][ddt.tables[0][:, 2] > 0]
    names = list(ddt.stage_names)
    by_name = {s.name: s for s in lowering.stages}
    n_slots = max(0, min(int(n_slots), len(live)))
    prefix = live[:n_slots]

    if n_slots:
        scaled = prefix.copy()
        scaled[:, 1:] *= tile
        walked = dag_walk(lowering.stages, lowering.operands, lowering.values,
                          scaled, tile)
    else:
        walked = {}

    stages: dict[str, StageCheckpoint] = {}
    for k, n in enumerate(names):
        ws = by_name[n]
        units = int(dag.stages[n].n_rows)
        done_tiles = sorted(int(s) for sid, s, _z in prefix if int(sid) == k)
        if done_tiles != list(range(len(done_tiles))):
            raise ValueError(
                f"stage {n!r}: prefix executed non-contiguous tiles "
                f"{done_tiles}; cannot form an ascending checkpoint")
        p = len(done_tiles)
        row_done = np.zeros(units, dtype=bool)
        row_done[:p] = True
        pend = tuple((t, 1) for t in range(p, units))
        if ws.combine == "sum":
            acc = np.asarray(walked[n]) if p else None
            out = None
        else:
            acc = None
            if p:
                dev = np.asarray(walked[n]).reshape(
                    (units, tile) + tuple(ws.out_shape[1:]))
                out = np.zeros_like(dev)
                out[:p] = dev[:p]
            else:
                out = None
        stages[n] = StageCheckpoint(
            stage=n, n_rows=units, combine=ws.combine, pending=pend,
            row_done=row_done, out=out, acc=acc, acc_next=p, parts=(),
            executed=p)
    ck = JobCheckpoint(job="device", stages=stages, substrate="device",
                       reason="prefix")
    ck.validate(dag)
    return ck, walked


# ---------------------------------------------------------------------------
# the preemptive arbiter


@dataclass(frozen=True)
class PreemptionEvent:
    """One park/resume decision: when, who, which way, and why."""

    t: float
    job: str
    kind: str      # "preempt" | "resume"
    reason: str


class PreemptiveArbiter(Arbiter):
    """Wrap any §10 arbiter with deadline-pressure eviction.

    Per ``order`` call (one per chunk boundary in all three engines), a
    deadline job is *pressured* when its fluid slack — time to deadline
    minus remaining-work estimate spread over ``n_workers`` — drops
    below ``slack_s``. While any job is pressured, jobs at or below the
    most urgent pressured priority whose deadline is absent or already
    expired are parked: dropped from the dispatch order, so their next
    chunk never pops, which is exactly a chunk-boundary preemption of
    the §9 machinery. The moment pressure clears they reappear — their
    queued remainder is intact in the live ``_StageRun`` state, so
    "resume" is simply being schedulable again (an implicit checkpoint;
    no state is copied). Already-expired deadline jobs are never
    pressured (the miss is unavoidable) and ARE victim-eligible.

    ``admission`` (a §14 AdmissionController) sharpens the remaining-work
    estimate with feedback rates; without it the estimate is the job's
    declared stage costs. Park/resume transitions land in
    ``preemption_log``, which the server/simulator results surface.
    """

    name = "preemptive"

    def __init__(self, inner: str | Any = "fair", n_workers: int = 1,
                 slack_s: float = 0.0, admission=None, **inner_kwargs):
        from .server import make_arbiter

        self.inner = (inner if not isinstance(inner, str)
                      else make_arbiter(inner, **inner_kwargs))
        self.n_workers = max(1, int(n_workers))
        self.slack_s = float(slack_s)
        self.admission = admission
        self.preemption_log: list[PreemptionEvent] = []
        self._est: dict[str, float] = {}

    def _estimate(self, js) -> float:
        """Total service-seconds estimate for this job (cached)."""
        from .server import job_stage_costs

        key = js.job.name
        if key not in self._est:
            if self.admission is not None:
                self._est[key] = float(
                    self.admission.estimate_service_s(js.job))
            else:
                self._est[key] = float(sum(
                    np.asarray(c, dtype=float).sum()
                    for c in job_stage_costs(js.job).values()))
        return self._est[key]

    def slack(self, js, now: float) -> float:
        """Fluid slack: deadline minus projected finish, seconds."""
        deadline = js.arrival + js.job.deadline_s
        left = max(self._estimate(js) - js.service, 0.0)
        return deadline - (now + left / self.n_workers)

    def order(self, jobs, now: float):
        """Inner order minus the currently-parked victims."""
        ordered = self.inner.order(jobs, now)
        pressured = []
        for js in jobs:
            if js.job.deadline_s is None or js.done:
                continue
            if now >= js.arrival + js.job.deadline_s:
                continue  # expired: the miss is sunk, don't thrash for it
            if self.slack(js, now) < self.slack_s:
                pressured.append(js)
        victims: set[str] = set()
        if pressured:
            pmax = max(p.job.priority for p in pressured)
            pressed = {p.job.name for p in pressured}
            for js in jobs:
                if js.done or js.job.name in pressed:
                    continue
                if js.job.priority > pmax:
                    continue
                live_deadline = (js.job.deadline_s is not None
                                 and now < js.arrival + js.job.deadline_s)
                if not live_deadline:
                    victims.add(js.job.name)
        for js in jobs:
            parked = js.job.name in victims
            if parked and not js.preempted:
                self.preemption_log.append(PreemptionEvent(
                    now, js.job.name, "preempt", "deadline_pressure"))
            elif js.preempted and not parked:
                self.preemption_log.append(PreemptionEvent(
                    now, js.job.name, "resume", "pressure_cleared"))
            js.preempted = parked
        if not victims:
            return ordered
        return [js for js in ordered if js.job.name not in victims]

    def charge(self, js, dt: float, now: float) -> None:
        """Delegate accounting to the wrapped arbiter."""
        self.inner.charge(js, dt, now)


def _register() -> None:
    """Make ``make_arbiter("preemptive", ...)`` resolve to this module."""
    from .server import ARBITERS

    ARBITERS.setdefault("preemptive", PreemptiveArbiter)


_register()
