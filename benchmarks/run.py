"""Benchmark harness: one entry per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows and emits the paper-figure
analogues + claims validation into artifacts/ (bench.csv + bench.json —
the JSON is uploaded as a CI artifact).

  fig7/fig89/fig10   paper_repro.py (simulated 20/56-core platforms,
                     measured task costs) — paper Figures 7a,7b,8,9,10
  partitioner_*      chunk-calculation overhead per DLS technique
  queue_*            centralized pop / steal costs (the lock path)
  executor_*         threaded end-to-end scheduling overhead
  pipeline_dag_*     §9 DAG runtime: per-stage tuning vs global baseline
  device_dag_*       §11 device path: fused super-table walker vs per-stage
                     launches (interpreted off the TPU)
  pipeline_server_*  §10 serving runtime: fair-share vs FIFO on mixed jobs;
                     §14 open-loop admission front door; §15 preemptive
                     arbiter hit-rate + mid-flight migration bit-equality
  online_*           §12 runtime feedback loop: bandit-tuned makespan vs the
                     offline search and the static techniques; moldable
                     chunk-resize rescue of a mis-chunked stage
  hetero_*           §13 heterogeneous placement: the transfer-aware solver
                     vs the all-HOST / all-DEVICE baselines, plus real
                     host+device co-execution bit-equality
  moe_dispatch_* /   §17 model zoo: online adaptivity on the skewed MoE
  model_zoo_*        expert fan-out; transformer step chain + two-model
                     serving pair bit-equal to the direct model calls
  telemetry_*        §18 tracer overhead: fully-traced run vs NullTracer
                     on the real pool, critical-path reconciliation, and
                     the sample trace/metrics artifacts
  cc_vee_*           the paper's CC hot loop on the real VEE
  schedule_quality_* device-side assignment quality (LPT vs round-robin)
  roofline_*         summary of artifacts/roofline.json (dry-run derived)
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from repro.core import (PARTITIONERS, CentralizedQueue, RangeTask,  # noqa: E402
                        SchedulerConfig, ScheduledExecutor, chunk_schedule,
                        cost_balanced_assignment, assign_chunks,
                        build_task_table, make_partitioner,
                        tasks_from_schedule)
from repro.vee import rmat_graph  # noqa: E402

ART = Path(__file__).resolve().parents[1] / "artifacts"
ROWS: list[tuple[str, float, str]] = []


def substrate_provenance() -> dict:
    """Where these numbers came from: jax backend, device kind, host cores.

    Stamped into every BENCH_<run>.json and bench_meta.json so baseline
    comparisons across machines FAIL LOUDLY (check_gates.py refuses a
    substrate mismatch) instead of silently drifting when a runner
    generation, accelerator, or core count changes under the numbers.
    """
    import platform

    import jax

    return {
        "host_cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "jax_backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": jax.device_count(),
    }


def row(name: str, us: float, derived: str = "") -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.3f},{derived}", flush=True)


def bench_partitioners() -> None:
    """Chunk-calculation overhead (the cost a worker pays per GetTask)."""
    n, p = 1_000_000, 56
    for tech in sorted(PARTITIONERS):
        part = make_partitioner(tech, n, p)
        t0 = time.perf_counter()
        calls = 0
        while part.next_chunk() and calls < 20_000:
            calls += 1
        dt = time.perf_counter() - t0
        row(f"partitioner_{tech}", dt / max(calls, 1) * 1e6, f"chunks={calls}")


def bench_queue_ops() -> None:
    n = 50_000
    tasks = [RangeTask(i, i, 1, lambda s, z: None, 1.0) for i in range(n)]
    q = CentralizedQueue(tasks, make_partitioner("SS", n, 8))
    t0 = time.perf_counter()
    while q.pop(0):
        pass
    row("queue_centralized_pop", (time.perf_counter() - t0) / n * 1e6,
        "SS chunk=1 (worst case)")

    from repro.core import DistributedQueues
    tasks = [RangeTask(i, i, 1, lambda s, z: None, 1.0) for i in range(n)]
    dq = DistributedQueues(tasks, "GSS", 8, layout="PERCORE")
    t0 = time.perf_counter()
    steals = 0
    while True:
        got = dq.steal(0, (steals % 7) + 1)
        if not got:
            break
        steals += 1
    row("queue_steal", (time.perf_counter() - t0) / max(steals, 1) * 1e6,
        f"steals={steals} technique-driven amounts")


def bench_sched_overhead(quick: bool = False) -> None:
    """Hot-path microcosts (DESIGN.md §16): slot-array vs deque queues.

    ``sched_overhead_per_task`` is the CI-gated row: on the PERCORE/GSS
    host pool the slot-array pop (index-view primitive the executor
    drains) and the fused ``steal_to_home`` must each be >= 5x cheaper
    per chunk than the deque reference's pop_local and steal+push_local
    (pop_margin5 >= 0, steal_margin5 >= 0), and must stay under absolute
    ``max_us`` ceilings so both sides of the ratio can't drift together.
    """
    from repro.core import DistributedQueues, SlotDistributedQueues

    n, P, tech = 20_000, 8, "GSS"
    reps = 4 if quick else 12
    tasks = [RangeTask(i, i, 1, lambda s, z: None, 1.0) for i in range(n)]

    t_pop = {"slot": 0.0, "deque": 0.0}
    c_pop = {"slot": 0, "deque": 0}
    t_steal = {"slot": 0.0, "deque": 0.0}
    c_steal = {"slot": 0, "deque": 0}
    for _ in range(reps):
        # pop: each worker drains its own pre-filled queue
        dq = SlotDistributedQueues(tasks, tech, P, layout="PERCORE")
        t0 = time.perf_counter()
        for w in range(P):
            while len(dq.pop_local_idx(w)):
                c_pop["slot"] += 1
        t_pop["slot"] += time.perf_counter() - t0

        dq = DistributedQueues(tasks, tech, P, layout="PERCORE")
        t0 = time.perf_counter()
        for w in range(P):
            while dq.pop_local(w):
                c_pop["deque"] += 1
        t_pop["deque"] += time.perf_counter() - t0

        # steal: worker 0 robs every other queue dry, loot lands in its
        # home queue (the full theft transaction both executors pay)
        dq = SlotDistributedQueues(tasks, tech, P, layout="PERCORE")
        t0 = time.perf_counter()
        victims = list(range(1, P))
        while victims:
            victims = [v for v in victims if dq.steal_to_home(0, v)]
            c_steal["slot"] += len(victims)
        t_steal["slot"] += time.perf_counter() - t0

        dq = DistributedQueues(tasks, tech, P, layout="PERCORE")
        t0 = time.perf_counter()
        victims = list(range(1, P))
        while victims:
            keep = []
            for v in victims:
                got = dq.steal(0, v)
                if got:
                    dq.push_local(0, got)
                    keep.append(v)
            victims = keep
            c_steal["deque"] += len(victims)
        t_steal["deque"] += time.perf_counter() - t0

    pop = {k: t_pop[k] / max(1, c_pop[k]) * 1e6 for k in t_pop}
    steal = {k: t_steal[k] / max(1, c_steal[k]) * 1e6 for k in t_steal}
    row("sched_overhead_per_task", pop["slot"],
        f"pop_slot={pop['slot']:.3f}us pop_deque={pop['deque']:.3f}us "
        f"steal_slot={steal['slot']:.3f}us steal_deque={steal['deque']:.3f}us "
        f"pop_gain={pop['deque'] / pop['slot']:.2f}x "
        f"steal_gain={steal['deque'] / steal['slot']:.2f}x "
        f"pop_margin5={(pop['deque'] - 5 * pop['slot']) / pop['deque'] * 100:.2f}% "
        f"steal_margin5={(steal['deque'] - 5 * steal['slot']) / steal['deque'] * 100:.2f}% "
        f"tasks={n} reps={reps} technique={tech} layout=PERCORE")


def bench_executor() -> None:
    """End-to-end threaded scheduling overhead per task (null ops)."""
    n = 20_000
    for tech, layout in (("GSS", "CENTRALIZED"), ("GSS", "PERCORE")):
        sched = chunk_schedule(tech, n, 4)
        tasks = tasks_from_schedule(sched, lambda s, z: None)
        cfg = SchedulerConfig(technique=tech, queue_layout=layout, n_workers=4)
        t0 = time.perf_counter()
        ScheduledExecutor(cfg).run(tasks)
        dt = time.perf_counter() - t0
        row(f"executor_{tech}_{layout}", dt / len(tasks) * 1e6,
            f"tasks={len(tasks)}")


def bench_cc_vee() -> None:
    """The paper's CC hot loop on the real VEE (numpy CSR)."""
    from repro.vee import connected_components
    G = rmat_graph(scale=13, edge_factor=8, seed=1, relabel="blocks")
    for tech in ("STATIC", "MFSC"):
        cfg = SchedulerConfig(technique=tech, queue_layout="CENTRALIZED",
                              n_workers=4)
        t0 = time.perf_counter()
        labels, iters, _ = connected_components(G, cfg, max_iter=4)
        dt = time.perf_counter() - t0
        row(f"cc_vee_{tech}", dt / (G.n_rows * min(iters, 4)) * 1e6,
            f"n={G.n_rows} iters={iters}")


def bench_schedule_quality() -> None:
    """Device-side assignment quality: LPT vs round-robin on skewed tiles
    (the TPU 'persistent stealing' payoff, DESIGN.md §3)."""
    G = rmat_graph(scale=13, edge_factor=8, seed=2)  # raw: hubs clustered
    tile, shards = 64, 8
    nnz = G.row_nnz()
    tile_cost = nnz.reshape(-1, tile).sum(1).astype(float)
    table = build_task_table("MFSC", G.n_rows // tile, shards)
    table = table[table[:, 1] > 0]
    chunk_costs = np.array([tile_cost[s:s + z].sum() for s, z in table])
    rr = assign_chunks(len(table), shards, "roundrobin")
    lpt = cost_balanced_assignment(table, chunk_costs, shards)

    def imbalance(assign):
        loads = np.array([chunk_costs[assign == s].sum() for s in range(shards)])
        return loads.max() / loads.mean()

    row("schedule_quality_roundrobin", imbalance(rr) * 100, "max/mean load %")
    row("schedule_quality_lpt", imbalance(lpt) * 100,
        "max/mean load % (cost-balanced)")

    # persistent re-balancing = the SPMD work-stealing analogue (DESIGN.md
    # §3): start from round-robin, feed back measured per-shard loads each
    # "iteration" (as a CC while-loop would), chunks migrate to neighbours.
    from repro.core import rebalance
    assign = rr.copy()
    for _ in range(12):
        loads = np.array([chunk_costs[assign == s_].sum() for s_ in range(shards)])
        assign = rebalance(assign, loads, chunk_costs, max_moves=16)
    row("schedule_quality_rebalanced", imbalance(assign) * 100,
        "max/mean load % after 12 persistent-stealing iterations")


def bench_pipeline_dag(quick: bool = False) -> None:
    """Pipeline-DAG runtime rows (§9): per-stage-tuned simulated makespan vs
    the best single-global-config baseline, plus measured real-pool overlap.

    ``pipeline_dag_cc_regression`` is the CI-gated row: the per-stage search
    starts from the best uniform assignment and only accepts improvements,
    so tuned <= baseline must hold on every run.
    """
    from repro.core import SchedulerConfig, select_offline_dag
    from repro.vee import recommendation_pipeline, rmat_graph
    from repro.vee.apps import cc_iteration_dag

    G = rmat_graph(scale=11 if quick else 13, edge_factor=8, seed=7,
                   relabel="blocks")
    n = G.n_rows
    nnz = G.row_nnz().astype(float)
    dag = cc_iteration_dag(G, np.arange(1, n + 1, dtype=np.int64))
    stage_costs = {"propagate": nnz * 2e-7 + 5e-8,
                   "changed": np.full(n, 2e-8)}
    assign, tuned, uniform = select_offline_dag(
        dag, stage_costs, n_workers=20, passes=1 if quick else 2)
    base_combo = min(uniform, key=uniform.get)
    base = uniform[base_combo]
    tag = " ".join(f"{s}={'/'.join(c)}" for s, c in assign.items())
    row("pipeline_dag_cc_regression", tuned * 1e6,
        f"baseline={base * 1e6:.1f}us ({'/'.join(base_combo)}) "
        f"tuned {tag} gain={(base - tuned) / base * 100:.2f}%")

    _, rec = recommendation_pipeline(4096, 64, SchedulerConfig(
        technique="MFSC", queue_layout="CENTRALIZED", n_workers=4))
    row("pipeline_dag_branch_overlap",
        rec.overlap_s("item_norms", "user_bias") * 1e6,
        "independent branches active together (real pool, us)")


def bench_device_dag(quick: bool = False) -> None:
    """Device-DAG rows (§11): the fused multi-stage Pallas walker vs one
    launch per stage, on the linreg pipeline (interpreted off the TPU).

    ``device_dag_linreg`` is the CI-gated row: ``equal=1`` asserts the
    fused super-table run reproduces the per-stage-launch results (and
    the host PipelineExecutor's, bit-wise), and ``sim_gain`` asserts the
    fused launch is never slower than sequential launches in simulated
    makespan (fused pays h_launch once; max-of-sums <= sum-of-maxes).
    """
    from repro.core import (PipelineExecutor, build_dag_tables,
                            frozen_dag_makespans, select_offline_device_dag)
    from repro.vee.apps import linreg_device_lowering, run_device_dag

    n, d, tile = (512, 9, 64) if quick else (2048, 9, 64)
    low = linreg_device_lowering(n, d, tile=tile)
    units = n // tile
    costs = {"moments": np.full(units, 1e-5),
             "syrk_gemv": np.full(units, 2e-5)}
    techs, _, _ = select_offline_device_dag(low.dag, costs, tile=1,
                                            n_shards=1, passes=1)
    t0 = time.perf_counter()
    fused, ddt = run_device_dag(low, techs)
    dt_fused = time.perf_counter() - t0
    t0 = time.perf_counter()
    seq, _ = run_device_dag(low, techs, stagewise=True)
    dt_seq = time.perf_counter() - t0
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    equal = all(np.array_equal(fused[k], seq[k]) for k in fused) and all(
        np.array_equal(np.asarray(host.values[k]), fused[k]) for k in fused)
    f_ms, s_ms = frozen_dag_makespans(build_dag_tables(low.dag, 1, techs), costs)
    gain = (s_ms - f_ms) / s_ms * 100
    row("device_dag_linreg", dt_fused * 1e6,
        f"equal={1 if equal else -1} wall_stagewise={dt_seq * 1e6:.1f}us "
        f"sim_fused={f_ms * 1e6:.1f}us sim_seq={s_ms * 1e6:.1f}us "
        f"techs={'/'.join(techs[s] for s in low.dag.stage_names)} "
        f"sim_gain={gain:.4f}%")


def bench_device_cache(quick: bool = False) -> None:
    """Relower-cache row (§16): repeat jobs skip lowering + table transfer.

    ``device_dag_relower_cache`` is the CI-gated row: a stream of jobs
    sharing one DAG shape (the front door's recurring batch_signature
    case — operand values differ, schedule doesn't) must hit both the
    host lowering memo (``build_dag_tables_cached``), the walker's
    device-resident table cache and its compiled call memo
    (``walker_call_cache_stats``) on every job after the first
    (hit_margin >= 0 asserts a >= 50% hit rate on each; the 6-job stream
    yields exactly 5/6), and the cached run must stay bit-equal to a cold
    run (equal=1).
    """
    from repro.core import clear_dag_table_cache, dag_table_cache_stats
    from repro.kernels.dag_walk import (clear_device_table_cache,
                                        clear_walker_call_cache,
                                        device_table_cache_stats,
                                        walker_call_cache_stats)
    from repro.vee.apps import linreg_device_lowering, run_device_dag

    n_jobs = 6
    lows = [linreg_device_lowering(256, 9, tile=64, seed=s)
            for s in range(1, n_jobs + 1)]  # same shape, different values
    clear_dag_table_cache()
    clear_device_table_cache()
    clear_walker_call_cache()
    t0 = time.perf_counter()
    run_device_dag(lows[0], "GSS")
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for low in lows[1:]:
        run_device_dag(low, "GSS")
    warm = (time.perf_counter() - t0) / (n_jobs - 1)
    lstats = dag_table_cache_stats()
    tstats = device_table_cache_stats()
    wstats = walker_call_cache_stats()
    hit_rate = min(st["hits"] / max(1, st["hits"] + st["misses"])
                   for st in (lstats, tstats, wstats)) * 100

    warm_vals, _ = run_device_dag(lows[0], "GSS")   # fully cached
    clear_dag_table_cache()
    clear_device_table_cache()
    clear_walker_call_cache()
    cold_vals, _ = run_device_dag(lows[0], "GSS")   # cold relower
    equal = int(all(np.array_equal(warm_vals[k], cold_vals[k])
                    for k in cold_vals))
    row("device_dag_relower_cache", warm * 1e6,
        f"cold={cold * 1e6:.1f}us warm={warm * 1e6:.1f}us "
        f"lower_hits={lstats['hits']} lower_misses={lstats['misses']} "
        f"table_hits={tstats['hits']} table_misses={tstats['misses']} "
        f"walker_hits={wstats['hits']} walker_misses={wstats['misses']} "
        f"jobs={n_jobs} hit_margin={hit_rate - 50.0:.2f}% equal={equal}")


def bench_pipeline_server(quick: bool = False) -> None:
    """Multi-tenant serving rows (§10): p50/p99 job latency and makespan for
    a mixed workload of concurrent heterogeneous jobs, weighted-fair vs
    head-of-line FIFO.

    ``pipeline_server_mixed_load`` is the CI-gated row: FIFO serializes
    jobs and idles workers at stage barriers and straggler tails, so
    weighted-fair sharing must achieve p99 <= FIFO on this workload.
    """
    import numpy as np

    from repro.core import Job, PipelineDAG, Stage, StageDep, simulate_server

    def mixed_job(name, n, scale, arrival, tenant, weight, seed):
        rng = np.random.default_rng(seed)
        m = max(8, n // 64)
        dag = PipelineDAG([
            Stage("prop", n, lambda i, s, z: None),
            Stage("check", n, lambda i, s, z: None, combine="sum",
                  deps=(StageDep("prop", "elementwise"),)),
            Stage("reduce", m, lambda i, s, z: None, combine="sum",
                  deps=(StageDep("prop", "full"),)),
        ])
        costs = {"prop": rng.pareto(1.2, n) * scale + scale * 0.1,
                 "check": np.full(n, scale * 0.01),
                 "reduce": np.full(m, scale * 2.0)}
        return Job(name, dag, tenant=tenant, weight=weight,
                   arrival_s=arrival, stage_costs=costs)

    n_batch = 2000 if quick else 8000
    n_small = n_batch // 10
    jobs = [
        mixed_job("batch", n_batch, 1e-5, 0.0, "analytics", 1.0, 0),
        mixed_job("inter1", n_small, 1e-5, 0.002, "interactive", 4.0, 1),
        mixed_job("inter2", n_small, 1e-5, 0.004, "interactive", 4.0, 2),
    ]
    if not quick:
        jobs.append(mixed_job("inter3", n_small, 1e-5, 0.006,
                              "interactive", 4.0, 3))

    fifo = simulate_server(jobs, n_workers=20, arbiter="fifo")
    fair = simulate_server(jobs, n_workers=20, arbiter="fair")
    p = {f"{tag}_{q}": r.latency_percentile(q) * 1e6
         for tag, r in (("fair", fair), ("fifo", fifo)) for q in (50, 99)}
    row("pipeline_server_mixed_load", p["fair_99"],
        f"p50_fair={p['fair_50']:.1f}us p99_fair={p['fair_99']:.1f}us "
        f"p50_fifo={p['fifo_50']:.1f}us p99_fifo={p['fifo_99']:.1f}us "
        f"makespan_fair={fair.makespan * 1e6:.1f}us "
        f"makespan_fifo={fifo.makespan * 1e6:.1f}us "
        f"jobs={len(jobs)} p99_gain={(p['fifo_99'] - p['fair_99']) / p['fifo_99'] * 100:.2f}%")


def bench_openloop(quick: bool = False) -> None:
    """Serving front-door row (§14): open-loop heavy-tailed trace replay.

    ``pipeline_server_openloop`` is the CI-gated row. On an overloaded
    (load 1.5) Pareto-interarrival trace, the admission+batching front
    door (deadline-slack shedding, per-tenant token bucket on the
    deadline-free tenant, same-shape coalescing, FeedbackLog-informed
    service estimates) must achieve p99.9 completed-job latency <= the
    no-admission FIFO baseline (p999_gain >= 0) AND a deadline hit-rate
    >= baseline, counting every shed deadline job as a miss
    (hit_gain >= 0) — shedding is only allowed to win by keeping the
    jobs it admits fast. equal=1 asserts the batching primitive itself:
    same-shape device lowerings merged into ONE super-table launch
    produce bit-identical member results to unbatched launches.
    """
    import numpy as np

    from repro.core import (AdmissionController, BatchPolicy, TokenBucket,
                            heavy_tailed_trace, replay_open_loop)
    from repro.core.online import FeedbackLog
    from repro.vee.apps import (linreg_device_lowering,
                                merge_device_lowerings, run_device_dag,
                                split_device_values)

    n_jobs = 800 if quick else 2000
    trace = heavy_tailed_trace(n_jobs, seed=3, load=1.5, n_workers=8)
    base = replay_open_loop(trace, n_workers=8, arbiter="fifo")
    fb = FeedbackLog()
    adm = AdmissionController(
        buckets={"etl": TokenBucket(rate=400.0, capacity=20)}, feedback=fb)
    front = replay_open_loop(trace, n_workers=8, arbiter="fair",
                             admission=adm, batching=BatchPolicy(2e-3, 8),
                             feedback=fb)

    lows = [linreg_device_lowering(256, 9, tile=64, seed=s) for s in (1, 2, 3)]
    singles = [run_device_dag(low, "SS")[0] for low in lows]
    merged_vals, _ = run_device_dag(merge_device_lowerings(lows), "SS")
    members = split_device_values(merged_vals, len(lows))
    equal = int(all(np.array_equal(members[j][k], singles[j][k])
                    for j in range(len(lows)) for k in singles[j]))

    p999_base = base.latency_percentile(99.9) * 1e6
    p999_front = front.latency_percentile(99.9) * 1e6
    hit_base = base.deadline_hit_rate()
    hit_front = front.deadline_hit_rate()
    row("pipeline_server_openloop", p999_front,
        f"p50={front.latency_percentile(50) * 1e6:.1f}us "
        f"p99={front.latency_percentile(99) * 1e6:.1f}us "
        f"p999={p999_front:.1f}us p999_fifo={p999_base:.1f}us "
        f"hit={hit_front:.3f} hit_fifo={hit_base:.3f} "
        f"shed={front.shed_rate * 100:.1f}% batches={front.n_batches} "
        f"jobs={n_jobs} "
        f"p999_gain={(p999_base - p999_front) / p999_base * 100:.2f}% "
        f"hit_gain={(hit_front - hit_base) * 100:.2f}% equal={equal}")


def bench_preemptive(quick: bool = False) -> None:
    """Preemptive multi-tenancy row (§15): chunk-boundary preemption on a
    pressured open-loop trace, plus mid-flight migration bit-equality.

    ``pipeline_server_preemptive`` is the CI-gated row. On a deeply
    overloaded (load 5.0) heavy-tailed trace whose deadlines scale with
    pool capacity, the ``preemptive`` arbiter (deadline-pressure slack
    test wrapped around weighted-fair, victims = deadline-free or
    already-expired jobs at the pressured jobs' priority) must achieve a
    deadline hit-rate >= plain non-preemptive weighted-fair
    (hit_gain >= 0). equal=1 asserts the migration protocol itself:
    checkpoint a host run at a chunk boundary, re-lower the remainder
    onto the device walker (and the reverse: freeze a device prefix,
    resume on the host pool) and land bit-identical to never-preempted
    runs — for BOTH the linreg and the recommendation lowerings.
    """
    import numpy as np

    from repro.core import (PipelineExecutor, PreemptiveRunner,
                            SchedulerConfig, heavy_tailed_trace,
                            migrate_to_device, replay_open_loop,
                            resume_on_host, run_device_prefix)
    from repro.vee.apps import (linreg_device_lowering,
                                recommendation_device_lowering,
                                run_device_dag)

    n_jobs = 800 if quick else 2000
    trace = heavy_tailed_trace(n_jobs, seed=3, load=5.0, n_workers=8)
    base = replay_open_loop(trace, n_workers=8, arbiter="fair")
    pre = replay_open_loop(trace, n_workers=8, arbiter="preemptive",
                           arbiter_kwargs={"inner": "fair", "n_workers": 8,
                                           "slack_s": 0.5})

    cfg = SchedulerConfig(technique="SS", queue_layout="CENTRALIZED",
                          n_workers=1)
    equal = 1
    for low in (linreg_device_lowering(256, 9, tile=64),
                recommendation_device_lowering(128, 192, tile=64)):
        host_ref = PipelineExecutor(low.dag, cfg).run()
        dev_ref, _ = run_device_dag(low, "SS")
        _, ck = PreemptiveRunner(low.dag, cfg, preempt_after=2).run()
        vals = migrate_to_device(ck, low)
        equal &= int(all(np.array_equal(vals[k], dev_ref[k])
                         for k in dev_ref))
        ck2, _ = run_device_prefix(low, 2)
        fin = resume_on_host(ck2, low.dag, cfg)
        equal &= int(all(np.array_equal(np.asarray(fin.values[k]),
                                        np.asarray(host_ref.values[k]))
                         for k in host_ref.values))

    hit_base = base.deadline_hit_rate()
    hit_pre = pre.deadline_hit_rate()
    row("pipeline_server_preemptive", pre.latency_percentile(99.9) * 1e6,
        f"hit={hit_pre:.3f} hit_fair={hit_base:.3f} "
        f"preemptions={len(pre.preemptions)} jobs={n_jobs} "
        f"hit_gain={(hit_pre - hit_base) * 100:.2f}% equal={equal}")


def bench_online(quick: bool = False) -> None:
    """Runtime feedback-loop rows (§12): the online bandit vs the offline
    search and the static techniques, in deterministic virtual time.

    ``online_linreg_adaptive`` is CI-gated: the online-tuned makespan must
    land within 1.10x of the ``select_offline_dag``-tuned makespan on the
    same workload (margin110 >= 0) and strictly beat the median static
    technique (vs_median >= 0). ``online_resize_merge`` is also gated:
    coalescing observed-uniform chunk dust (SS over a uniform stage) must
    never lose to leaving the dust in place (resize_gain >= 0).
    """
    from repro.core import (OnlineScheduler, PipelineDAG, Stage,
                            select_offline_dag, simulate_dag, tune_online_dag)
    from repro.vee.apps import linreg_dag, recommendation_dag

    n = 2048 if quick else 8192
    dag, _ = linreg_dag(n, 9, seed=3)
    rng = np.random.default_rng(11)
    stage_costs = {"moments": rng.pareto(1.5, n) * 1e-7 + 2e-8,
                   "syrk_gemv": np.full(n, 3e-7)}
    _, offline_ms, uniform = select_offline_dag(
        dag, stage_costs, n_workers=20, passes=1)
    statics = sorted(uniform.values())
    med_s = statics[len(statics) // 2]
    rounds = 40
    res = tune_online_dag(dag, stage_costs, n_workers=20, rounds=rounds, seed=0)
    margin110 = (1.10 * offline_ms - res.makespan) / offline_ms * 100
    vs_median = (med_s - res.makespan) / med_s * 100
    tag = " ".join(f"{s}={'/'.join(c)}" for s, c in res.assign.items())
    row("online_linreg_adaptive", res.makespan * 1e6,
        f"offline={offline_ms * 1e6:.1f}us best_static={statics[0] * 1e6:.1f}us "
        f"median_static={med_s * 1e6:.1f}us worst_static={statics[-1] * 1e6:.1f}us "
        f"rounds={rounds} tuned {tag} "
        f"margin110={margin110:.2f}% vs_median={vs_median:.2f}%")

    # the same loop over the two-branch recommendation DAG (not gated on
    # the offline margin: baseline.json tracks it instead)
    rdag = recommendation_dag(1024 if quick else 4096, 16, seed=5)
    rcosts = {"item_norms": np.full(rdag.stages["item_norms"].n_rows, 2e-7),
              "user_bias": np.full(rdag.stages["user_bias"].n_rows, 5e-8),
              "scores": rng.pareto(1.3, rdag.stages["scores"].n_rows) * 3e-7
                        + 5e-8}
    _, r_off, _ = select_offline_dag(rdag, rcosts, n_workers=20, passes=1)
    r_on = tune_online_dag(rdag, rcosts, n_workers=20, rounds=rounds, seed=0)
    row("online_recommendation_adaptive", r_on.makespan * 1e6,
        f"offline={r_off * 1e6:.1f}us rounds={rounds} "
        f"ratio={r_on.makespan / r_off:.4f}")

    # moldable-resize rescue: SS chunk dust over a uniform stage is the
    # paper's P5 pathology; the feedback loop must coalesce it
    n2 = 2048
    dust_dag = PipelineDAG([Stage("hot", n2, lambda i, s, z: None)])
    dust = {"hot": np.full(n2, 1e-7)}
    combo = ("SS", "CENTRALIZED", "SEQ")
    static_ms = simulate_dag(dust_dag, dust, combo, n_workers=8).makespan
    on = OnlineScheduler(seed=0, min_observe=2)
    resized_ms = simulate_dag(dust_dag, dust, combo, n_workers=8,
                              online=on).makespan
    row("online_resize_merge", resized_ms * 1e6,
        f"static={static_ms * 1e6:.1f}us resizes={on.resizes.get('hot', 0)} "
        f"resize_gain={(static_ms - resized_ms) / static_ms * 100:.2f}%")


def bench_hetero(quick: bool = False) -> None:
    """Heterogeneous placement rows (§13): the transfer-aware solver vs the
    homogeneous substrates, plus real co-execution bit-equality.

    ``hetero_linreg_placement`` is the CI-gated row: ``equal=1`` asserts a
    real HeteroExecutor run of the linreg lowering (host chunk workers +
    a device walker lane, SPLIT placement) reproduces the host-only
    PipelineExecutor bit-wise; ``vs_best`` asserts the solver's simulated
    makespan never exceeds min(all-HOST, all-DEVICE) (it starts from the
    better homogeneous placement and only accepts improvements); and
    ``mixed_gain`` asserts the solved MIXED placement strictly beats BOTH
    homogeneous placements on a transfer-heavy synthetic DAG whose
    branches have opposite substrate affinities.
    """
    from repro.core import (HeteroExecutor, PipelineExecutor, Placement,
                            SchedulerConfig, StagePlacement, select_placement)
    from repro.vee.apps import hetero_affinity_dag, linreg_device_lowering

    # real co-execution: linreg split across both substrates, bit-equal
    low = linreg_device_lowering(512, 9, tile=64, seed=1)
    host = PipelineExecutor(low.dag, SchedulerConfig(
        technique="SS", n_workers=1)).run()
    split = Placement({n: StagePlacement("split", 0.5)
                       for n in low.dag.stage_names})
    t0 = time.perf_counter()
    het = HeteroExecutor(low.dag, SchedulerConfig(technique="SS",
                                                  n_workers=2), split).run()
    dt_real = time.perf_counter() - t0
    equal = all(np.array_equal(np.asarray(host.values[k]),
                               np.asarray(het.values[k]))
                for k in host.values)

    # transfer-heavy synthetic DAG with opposite per-branch affinities
    # (shared with examples/hetero_pipeline.py and tests/test_placement.py)
    dag, costs = hetero_affinity_dag(2048 if quick else 8192)
    placement, het_ms, base = select_placement(dag, costs, n_workers=8,
                                               passes=1 if quick else 2)
    host_ms, dev_ms = base["host"], base["device"]
    best = min(host_ms, dev_ms)
    vs_best = (best - het_ms) / best * 100
    mixed_gain = min((host_ms - het_ms) / host_ms,
                     (dev_ms - het_ms) / dev_ms) * 100
    row("hetero_linreg_placement", het_ms * 1e6,
        f"equal={1 if equal else -1} wall_coexec={dt_real * 1e6:.1f}us "
        f"host={host_ms * 1e6:.1f}us device={dev_ms * 1e6:.1f}us "
        f"placement=[{placement.describe()}] "
        f"vs_best={vs_best:.2f}% mixed_gain={mixed_gain:.2f}%")


def bench_model_zoo(quick: bool = False) -> None:
    """Model-zoo rows (§17): real transformer/MoE step graphs lowered
    onto the scheduler via ``core.lower`` / ``vee.ml_apps``.

    ``moe_dispatch_adaptive`` is CI-gated twice: on a Zipf-skewed router
    the §12 online-adaptive makespan must never exceed the best static
    uniform partition (vs_best_static >= 0 — the expert fan-out's
    data-dependent chunk costs are exactly what the bandits + moldable
    resizer exploit), and a real-pool run of the lowered dispatch must be
    bit-equal to the direct (unscheduled) call (equal = 1).
    ``model_zoo_pipeline`` is gated on equal only: the streamed
    transformer step chain AND the two-model §14 serving pair (with §13
    placements solved on real activation byte sizes) must both reproduce
    their direct oracles bit-wise; us_per_call tracks the real pipelined
    step wall time.
    """
    from repro.core import select_offline_dag, tune_online_dag
    from repro.vee.ml_apps import (moe_dispatch_lowering, serving_pair,
                                   transformer_step_lowering)

    # skewed MoE expert fan-out, deterministic virtual time (§12)
    n_tok = 384 if quick else 768
    low = moe_dispatch_lowering(n_tokens=n_tok, skew=1.6, seed=0,
                                n_experts=32, capacity_factor=6.0)
    # lowering costs are unit-per-token; scale to ~us so the virtual
    # makespan reads like the other online_* rows
    costs = {k: v * 1e-6 for k, v in low.stage_costs.items()}
    _, _, uniform = select_offline_dag(low.dag, costs, n_workers=4, passes=1)
    statics = sorted(uniform.values())
    rounds = 40
    res = tune_online_dag(low.dag, costs, n_workers=4,
                          rounds=rounds, seed=0)
    vs_best_static = (statics[0] - res.makespan) / statics[0] * 100
    # real-pool bit-equality of the same lowering at real-run scale
    small = moe_dispatch_lowering(n_tokens=96, skew=1.6, seed=0)
    equal = np.array_equal(small.run_direct(),
                           small.run("gss/percore", n_workers=2)[0])
    row("moe_dispatch_adaptive", res.makespan * 1e6,
        f"equal={1 if equal else -1} best_static={statics[0] * 1e6:.1f}us "
        f"median_static={statics[len(statics) // 2] * 1e6:.1f}us "
        f"rounds={rounds} experts=32 "
        f"hot_expert_tokens={int(low.meta['expert_tokens'].max())} "
        f"vs_best_static={vs_best_static:.2f}%")

    # streamed transformer step + the §14 two-model serving pair
    b, s = (6, 8) if quick else (8, 12)
    tlow = transformer_step_lowering(batch=b, seq=s, seed=0)
    tdirect = tlow.run_direct()
    tlow.run("gss/percore", n_workers=2)  # warm the per-stage jits
    t0 = time.perf_counter()
    tsched, _ = tlow.run("gss/percore", n_workers=2)
    dt = time.perf_counter() - t0
    t_equal = np.array_equal(tdirect, tsched)
    presults, _, pplace, plows = serving_pair(batch=4, seq=8, n_workers=2)
    p_equal = all(np.array_equal(presults[a], pl.run_direct())
                  for a, pl in zip(("qwen2-0.5b", "granite-8b"), plows))
    row("model_zoo_pipeline", dt * 1e6,
        f"equal={1 if t_equal and p_equal else -1} arch=qwen2-0.5b "
        f"stages={len(tlow.dag.stage_names)} batch={b} seq={s} "
        f"pair_equal={1 if p_equal else -1} "
        f"pair_placements=[{' | '.join(p.describe() for p in pplace.values())}]")


def bench_telemetry(quick: bool = False) -> None:
    """§18 tracer overhead + the sample observability artifacts.

    ``telemetry_overhead`` is CI-gated three ways: tracing adds at most
    a 5% margin over the NullTracer run (overhead_margin5 >= 0), traced
    values stay bit-equal to untraced (equal=1), and
    ``analyze_critical_path`` telescopes to the traced run's measured
    makespan and reconciles against the independent DagStats accounting
    (recon=1). The overhead estimate is paired rather than a raw
    wall-clock ratio: single-vCPU CI runners see multi-second hypervisor
    steal bursts that swing whole-run wall time 2x either way, so we
    measure the flat-tuple ``record_raw`` hot path directly (min-of-reps
    tight loop, which converges even on a noisy core), multiply by the
    events a traced run actually records, and express that added work
    against the NullTracer run's min-of-reps wall time. Raw traced/base
    walls stay in the row as informational detail. Also drops non-blocking sample artifacts:
    artifacts/trace_sample.json (a traced FrontDoor / preemptive
    PipelineServer run plus the devdag.* phases of one small
    run_device_dag call) and artifacts/metrics_sample.json/.prom.
    """
    from repro.core import (DEP_ELEMENTWISE, AdmissionController, BatchPolicy,
                            FrontDoor, MetricsRegistry, PipelineDAG,
                            PipelineExecutor, Stage, StageDep, Submission,
                            TokenBucket, Tracer, analyze_critical_path,
                            collect_cache_metrics, collect_compile_metrics,
                            validate_chrome_trace)

    n, width = (24_000, 96) if quick else (96_000, 96)
    basis = np.ones(width)
    dag = PipelineDAG([
        Stage("src", n,
              lambda i, s, z: np.sqrt(
                  np.arange(s, s + z, dtype=np.float64)[:, None]
                  * basis).sum(axis=1),
              combine="concat"),
        Stage("scale", n, lambda i, s, z: i["src"][s:s + z] * 2.0 + 1.0,
              combine="concat", deps=(StageDep("src", DEP_ELEMENTWISE),)),
    ])
    cfg = SchedulerConfig(technique="GSS", queue_layout="PERCORE",
                          n_workers=8)
    reps = 5

    def timed(make_tracer):
        best = res = tr = None
        for _ in range(reps):
            t = make_tracer()
            ex = PipelineExecutor(dag, cfg, tracer=t)
            t0 = time.perf_counter()
            r = ex.run()
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best, res, tr = dt, r, t
        return best, res, tr

    base_s, base_res, _ = timed(lambda: None)       # NullTracer path
    traced_s, traced_res, tracer = timed(lambda: Tracer(job="bench"))
    equal = all(np.array_equal(np.asarray(traced_res.values[k]),
                               np.asarray(base_res.values[k]))
                for k in base_res.values)
    rep = analyze_critical_path(tracer, makespan=traced_res.wall_time_s)
    try:
        rep.reconcile(traced_res.stats, traced_res.wall_time_s,
                      rel_tol=0.05, abs_tol=1e-6)
        recon = 1
    except ValueError:
        recon = -1
    n_chunks = traced_res.stats.total_chunks

    # paired overhead: per-event record_raw cost (min-of-reps tight
    # loop) x events the traced run recorded, vs the base min wall
    k_loop = 20_000
    per_event_s = None
    for _ in range(reps):
        probe = Tracer()
        rec = probe.record_raw
        t0 = time.perf_counter()
        for i in range(k_loop):
            rec("exec", "bench", "src", i, 0, 0.0, 1.0, wait_s=0.1)
        dt = (time.perf_counter() - t0) / k_loop
        if per_event_s is None or dt < per_event_s:
            per_event_s = dt
    overhead_pct = per_event_s * len(tracer) / base_s * 100
    margin5 = 5.0 - overhead_pct
    row("telemetry_overhead", traced_s / max(1, n_chunks) * 1e6,
        f"traced={traced_s * 1e6:.1f}us base={base_s * 1e6:.1f}us "
        f"chunks={n_chunks} spans={len(tracer)} reps={reps} "
        f"record_ns={per_event_s * 1e9:.0f} overhead_pct={overhead_pct:.3f}% "
        f"overhead_margin5={margin5:.2f}% equal={1 if equal else -1} "
        f"recon={recon}")

    # -- sample artifacts (non-blocking; uploaded by bench-quick) ----------
    from repro.vee.apps import linreg_device_lowering, run_device_dag

    sample = Tracer()
    reg = MetricsRegistry()
    fd = FrontDoor(cfg, arbiter="preemptive",
                   arbiter_kwargs={"inner": "fair",
                                   "n_workers": cfg.n_workers,
                                   "slack_s": 10.0},
                   admission=AdmissionController(
                       buckets={"etl": TokenBucket(rate=50.0, capacity=2)}),
                   batching=BatchPolicy(2e-3, 4),
                   tracer=sample, metrics=reg)
    for j in range(6):
        # distinct shapes: only the last two coalesce into a §14 batch,
        # the rest arbitrate (and preempt) as separate jobs
        small = 2_000 + 512 * min(j, 4)
        d = PipelineDAG([
            Stage("work", small,
                  lambda i, s, z: np.sqrt(np.arange(s, s + z,
                                                    dtype=np.float64)),
                  combine="concat")])
        # tight deadlines on the rt tenant keep the preemptive arbiter
        # pressured, parking the deadline-free etl jobs mid-flight;
        # declared costs keep admission's fluid estimate realistic
        fd.submit(Submission(d, f"job{j}", tenant="etl" if j % 2 else "rt",
                             arrival_s=j * 1e-4,
                             deadline_s=None if j % 2 else 0.05,
                             stage_costs={"work": np.full(small, 1e-7)}))
    fd.serve()
    # device-DAG path: the devdag.* phases of one small walk, same stream
    low = linreg_device_lowering(128, 5, tile=32)
    run_device_dag(low, "SS", n_workers=2, tracer=sample, job="device_job")
    obj = sample.to_chrome_trace()
    assert validate_chrome_trace(obj) == [], "sample trace must be valid"
    (ART / "trace_sample.json").write_text(json.dumps(obj, indent=1) + "\n")
    collect_cache_metrics(reg)
    collect_compile_metrics(reg)
    (ART / "metrics_sample.json").write_text(reg.to_json() + "\n")
    (ART / "metrics_sample.prom").write_text(reg.to_prometheus())


def paper_figures() -> None:
    import paper_repro
    claims = paper_repro.main(scale=16)
    confirmed = sum("CONFIRMED" in c for c in claims)
    row("paper_claims_confirmed", float(confirmed), f"of {len(claims)}")


def roofline_summary() -> None:
    p = ART / "roofline.json"
    if not p.exists():
        print("# roofline.json missing - run launch.dryrun --all then "
              "benchmarks/roofline.py", flush=True)
        return
    for r in json.loads(p.read_text()):
        row(f"roofline_{r['arch']}_{r['shape']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
            f"dominant={r['dominant']} ratio={r['useful_ratio']:.2f} "
            f"frac={r['roofline_fraction']:.4f}")


def main(quick: bool = False, run_id: str | None = None) -> None:
    ART.mkdir(exist_ok=True)
    print("name,us_per_call,derived")
    bench_partitioners()
    bench_queue_ops()
    bench_sched_overhead(quick=quick)
    bench_executor()
    bench_pipeline_dag(quick=quick)
    bench_device_dag(quick=quick)
    bench_device_cache(quick=quick)
    bench_pipeline_server(quick=quick)
    bench_openloop(quick=quick)
    bench_preemptive(quick=quick)
    bench_online(quick=quick)
    bench_hetero(quick=quick)
    bench_model_zoo(quick=quick)
    bench_telemetry(quick=quick)
    if not quick:
        bench_cc_vee()
        bench_schedule_quality()
        paper_figures()
        roofline_summary()
    with (ART / "bench.csv").open("w") as f:
        f.write("name,us_per_call,derived\n")
        for n, u, d in ROWS:
            f.write(f"{n},{u:.3f},{d}\n")
    payload = [{"name": n, "us_per_call": u, "derived": d} for n, u, d in ROWS]
    (ART / "bench.json").write_text(json.dumps(payload, indent=2) + "\n")
    # bench-history stamp: one immutable JSON per run, keyed by the CI run
    # id (or a local timestamp), uploaded as an artifact so regressions can
    # be traced back through run history and baseline.json re-accepted
    # from any past run's numbers.
    rid = run_id or os.environ.get("GITHUB_RUN_ID") \
        or time.strftime("local-%Y%m%d-%H%M%S")
    rid = re.sub(r"[^A-Za-z0-9._-]", "_", str(rid))
    substrate = substrate_provenance()
    (ART / f"BENCH_{rid}.json").write_text(json.dumps(
        {"run_id": rid, "quick": quick, "substrate": substrate,
         "rows": payload}, indent=2) + "\n")
    # provenance marker read by check_gates.py: baselines accepted from a
    # full run must not gate quick CI runs (different row sets and sizes),
    # and numbers accepted on one substrate must not gate another machine
    (ART / "bench_meta.json").write_text(json.dumps(
        {"run_id": rid, "mode": "quick" if quick else "full",
         "substrate": substrate}) + "\n")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="sub-minute smoke subset (CI perf rows)")
    ap.add_argument("--run-id", default=None,
                    help="bench-history stamp id (default: $GITHUB_RUN_ID "
                         "or a local timestamp)")
    args = ap.parse_args()
    main(quick=args.quick, run_id=args.run_id)
