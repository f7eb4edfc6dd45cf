#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print one result line.

    python3 bench/run.py --workload higgs-linreg.solo --seed 7 --seconds 20 --trace 0
    JAX_PLATFORMS=cpu python3 bench/run.py --workload higgs-linreg.solo --seed 7 \\
        --seconds 2 --trace 0 --rehearse     # tiny sizes, any backend, no numbers

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; everything else is found by name:

* ``bench/configs/<config>.json``: the deployment's sizes, tile, per-stage
  techniques and the pipeline that runs it (``bench/pipelines/<pipeline>.py``,
  below);
* ``bench/traffic/<traffic>.json``: how jobs are formed: ``tenants`` (1
  if absent) coalesced per call, whether they ``split_rows`` of the
  configuration among them, ``warmup_jobs`` before the window, and any
  parameter a pipeline's own job reads;
* ``bench/limits/<cell>.json``: the limit of each number compared;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(measured)``,
  which returns None where it finds nothing to read.

A pipeline module gives ``build(cfg, rows, seed)``, one tenant's
lowering (or whatever its job takes); ``Checker(cfg, rows, seed)``, whose
call ``checker(answer, request)`` judges that tenant's answer to one
request against the plain reference and returns the numbers compared;
``control(cfg, rows, seed)``, request 0's answer by the reference one
precision step below (``bench/readings.py``); and ``work(cfg, rows,
traffic)``, the least flops and bytes of one answer: a call's least work
over the answers it owes, the unit that ``jobs_per_s``, ``job_mfu`` and
``walker_roofline`` count. It may also give:

* ``make_job(lowerings, cfg, traffic)``: its own job. Without it a job is
  ``vee.apps.run_device_dag`` and ``answer(lowering, values)`` for one
  tenant; ``merge_device_lowerings``, ``run_device_dag``,
  ``split_device_values`` and each member's answer for several;
* ``owed(cfg, traffic)``: the keys ``(tenant, request)`` of the answers
  one call owes; by default ``(k, 0)`` for each tenant ``k``.

A job is a call ``job() -> [(key, answer, t_answered[, t_arrived]),
...]``: ``key`` is an owed ``(tenant, request)``, ``tenant`` indexes the
call's lowerings, and that tenant's Checker judges the answer as the
answer to ``request``. The times are ``time.perf_counter()`` readings
that lie in order within the call: its start, the arrival, the answer,
its return. A latency runs from ``t_arrived``, or from the call's start
where there is none, to ``t_answered``. Every answer is judged. A call
that raises, or returns a key twice or a key it does not owe, fails all
it owes; each owed key it does not return fails, as does each answer
whose times are out of order. ``attempted`` is calls times answers owed.

The window is a closed loop: each call starts when the previous one has
returned. With ``--trace 1`` the same untraced window runs first, and
the metrics taken by the host's clock and the program's counters come
from it; a traced window of at most ``TRACE_SECONDS`` follows, and the
metrics read from the trace (device operations and the program's host
spans) come from that one, so the profiler's cost on the host moves no
number timed by the host. After the windows every answer is compared
with the plain reference (``bench/reference``), once the program's state
is freed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (``--trace 1``), ``work`` (the least work of one answer and
the bound that sets its least time), ``window`` (answer count, compiles,
answers owed and not soundly returned, the program's counters over the untraced
window, latency median and maximum with the slowest answer's place, the
first errors) and, last, ``compared``: each number compared with its
limit, which also end standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
``--rehearse`` runs the configuration's tiny sizes on any backend and
prints every metric's value as null.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 10.0  # the traced window of a --trace 1 run


@dataclass
class Measured:
    """What one window measured; the metric readers take their numbers from it."""

    setup_s: float
    window_s: float          # host clock, first call to last answer
    jobs: int                # answers returned (tenants count one each)
    latencies_s: list
    compiles: int            # backend compiles inside the window
    least_s: float           # least time of one answer's work on this chip
    trace: object = None     # bench.trace.Reduction of the traced window
    trace_jobs: int = 0      # answers returned in the traced window
    counters: dict = field(default_factory=dict)  # program counters' deltas over the window


def checkout_cache() -> None:
    """Keep JAX's persistent compile cache inside the checkout, with every program in it.

    The directory is fixed, whatever the machine sets: a cache shared
    between checkouts would carry one side's compiles to the other. Every
    program is written to it, however short its compile: under JAX's
    default of 1 s, a walker compile that a stall pushes past 1 s is
    written and every later job of the checkout loads it, which changes
    what the cell measures from one run to the next. Called before JAX is
    imported, which reads both variables; the program's
    ``enable_compile_cache()`` then takes the directory.
    """
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def load_cell(name: str, rehearse: bool = False) -> dict:
    """The cell's entry, configuration, traffic mix, limits and metric entries."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    if rehearse:
        cfg.update(cfg.get("rehearse", {}))
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": cfg, "traffic": traffic, "limits": limits,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold '-' or '.')."""
    mod_spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def pipeline_of(cfg: dict):
    return load_module(BENCH / "pipelines" / f"{cfg['pipeline']}.py")


def tenant_count(traffic: dict) -> int:
    """Tenants coalesced per call: the traffic's ``tenants``, 1 if absent."""
    return traffic.get("tenants", 1)


def tenants_of(cfg: dict, traffic: dict, seed: int) -> tuple[int, list[int]]:
    """Rows per tenant and each tenant's data seed, derived from ``seed``."""
    n = tenant_count(traffic)
    rows = cfg.get("rows")
    if traffic.get("split_rows", False):
        rows //= n
    return rows, [seed * n + k for k in range(n)]


def job_of(pipeline, lowerings: list, cfg: dict, traffic: dict):
    """The cell's job: the pipeline's own ``make_job`` where it has one, else ``make_job``."""
    if hasattr(pipeline, "make_job"):
        return pipeline.make_job(lowerings, cfg, traffic)
    return make_job(pipeline, lowerings, cfg["techniques"])


def owed_of(pipeline, cfg: dict, traffic: dict) -> list[tuple[int, int]]:
    """Keys ``(tenant, request)`` a call owes: the pipeline's ``owed``, else ``(k, 0)`` a tenant.

    Refuses keys that repeat or name no tenant of the call.
    """
    n = tenant_count(traffic)
    if not hasattr(pipeline, "owed"):
        return [(k, 0) for k in range(n)]
    owed = [tuple(key) for key in pipeline.owed(cfg, traffic)]
    if not owed or len(set(owed)) < len(owed) or any(t not in range(n) for t, _ in owed):
        raise SystemExit(f"bench: owed keys {owed[:8]} repeat, name no tenant of {n}, "
                         "or are none")
    return owed


def make_job(pipeline, lowerings: list, techniques):
    """``job() -> [((tenant, 0), answer, seconds when it reached the host)]``, one per tenant."""
    from jax.profiler import TraceAnnotation

    from repro.core.admission import BATCH_SEP
    from repro.vee import apps

    if len(lowerings) == 1:
        low = lowerings[0]

        def job():
            with TraceAnnotation("bench.run_device_dag"):
                values, _ = apps.run_device_dag(low, techniques)
            with TraceAnnotation("bench.answer"):
                return [((0, 0), pipeline.answer(low, values), time.perf_counter())]

        return job

    n = len(lowerings)
    if isinstance(techniques, dict):
        techniques = {f"{stage}{BATCH_SEP}{j}": t for stage, t in techniques.items()
                      for j in range(n)}

    def job():
        with TraceAnnotation("bench.merge"):
            merged = apps.merge_device_lowerings(lowerings)
        with TraceAnnotation("bench.run_device_dag"):
            values, _ = apps.run_device_dag(merged, techniques)
        with TraceAnnotation("bench.answer"):
            out = []
            for k, (low, vals) in enumerate(zip(lowerings,
                                                apps.split_device_values(values, n))):
                out.append(((k, 0), pipeline.answer(low, vals), time.perf_counter()))
            return out

    return job


def run_window(job, seconds: float, compiles: list, owed: list) -> dict:
    """Closed loop for ``seconds``: answers, latencies, failures and compiles.

    ``answers`` holds ``(key, answer)`` of each answer returned soundly;
    ``unanswered`` counts the ``owed`` answers that were not: all a call
    owes where it raises or returns a key twice or one it does not owe,
    each owed key it does not return, and each answer whose times are out
    of order (call, arrival, answer, return). ``errors`` has one message
    per such call or answer.
    """
    from jax.profiler import TraceAnnotation

    owed_keys = set(owed)
    answers, latencies, errors = [], [], []
    started = unanswered = 0
    c0 = compiles[0]
    t_start = time.perf_counter()
    deadline = t_start + seconds
    with TraceAnnotation("bench.window"):
        while (t0 := time.perf_counter()) < deadline:
            started += 1
            with TraceAnnotation("bench.job"):
                try:
                    out = job()
                except Exception as e:  # a call that raises fails all it owes
                    errors.append(f"{type(e).__name__}: {e}")
                    unanswered += len(owed)
                    continue
            t_returned = time.perf_counter()
            keys = [a[0] for a in out]
            if len(set(keys)) < len(keys) or not owed_keys.issuperset(keys):
                errors.append(f"a call returned keys {keys[:8]} and owes {owed[:8]}")
                unanswered += len(owed)
                continue
            unanswered += len(owed) - len(keys)
            for key, answer, t_answered, *arrived in out:
                t_from = arrived[0] if arrived else t0
                if not t0 <= t_from <= t_answered <= t_returned:
                    errors.append(f"answer {key}: arrived {t_from - t0:+.6f} s, answered "
                                  f"{t_answered - t0:+.6f} s, returned "
                                  f"{t_returned - t0:+.6f} s from the call's start")
                    unanswered += 1
                    continue
                answers.append((key, answer))
                latencies.append(t_answered - t_from)
    t_end = time.perf_counter()
    return {"answers": answers, "latencies_s": latencies, "errors": errors,
            "unanswered": unanswered, "started": started, "window_s": t_end - t_start,
            "compiles": compiles[0] - c0}


def check_answers(pipeline, cfg: dict, rows: int, seeds: list, answers: list,
                  limits: dict, control: bool = False) -> tuple[int, dict, dict]:
    """Failed answers, and per number compared the worst reading over all answers.

    Each answer ``((tenant, request), answer)`` is judged by the Checker of
    ``seeds[tenant]`` as the answer to ``request``. With ``control``, each
    tenant's control is judged by the same Checker too; the third item
    holds its worst readings.
    """
    worst: dict[str, float] = {}
    worst_control: dict[str, float] = {}
    failed = 0
    for k, seed in enumerate(seeds):
        checker = pipeline.Checker(cfg, rows, seed)  # one reference for both sides
        for (tenant, request), answer in answers:
            if tenant != k:
                continue
            numbers = checker(answer, request)
            failed += any(not numbers[n] <= limits[n] for n in limits)
            for n, v in numbers.items():
                worst[n] = max(worst.get(n, 0.0), v)
        if control:
            for n, v in checker(pipeline.control(cfg, rows, seed), 0).items():
                worst_control[n] = max(worst_control.get(n, 0.0), v)
        del checker
        gc.collect()
    return failed, worst, worst_control


def program_counters() -> dict:
    """The program's exported counters now, by name (``repro.core.telemetry``).

    The caches', the walker's and the fixed-point loop's counters
    (``collect_cache_metrics``, ``loop_counts`` among them) and the
    compile path's (``collect_compile_metrics``, from ``compile_counts``),
    as a fresh registry's snapshot holds them.
    """
    from repro.core.telemetry import (MetricsRegistry, collect_cache_metrics,
                                      collect_compile_metrics)

    reg = MetricsRegistry()
    collect_cache_metrics(reg)
    collect_compile_metrics(reg)
    return reg.snapshot()["counters"]


def start_trace(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python function events would swamp the host lines
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def run(args) -> dict:
    """One run of one cell; returns the result object (its last key is ``compared``)."""
    checkout_cache()
    import jax

    spec = load_cell(args.workload, args.rehearse)
    cell, cfg, traffic, limits = (spec[k] for k in ("cell", "config", "traffic", "limits"))
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", file=sys.stderr, flush=True)
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise SystemExit(f"bench: {cell['name']} needs {cell['chips']} TPU chip(s), "
                         f"found {len(devices)} {dev.platform} device(s)")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = [0]

    def count_compile(event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    from repro.core.telemetry import watch_compiles

    watch_compiles()

    pipeline = pipeline_of(cfg)
    rows, seeds = tenants_of(cfg, traffic, args.seed)
    owed = owed_of(pipeline, cfg, traffic)
    lowerings = [pipeline.build(cfg, rows, s) for s in seeds]
    job = job_of(pipeline, lowerings, cfg, traffic)
    for _ in range(traffic["warmup_jobs"]):
        job()
    setup_s = time.perf_counter() - T_START

    before = program_counters()
    win = run_window(job, args.seconds, compiles, owed)
    after = program_counters()
    counters = {k: v - before.get(k, 0.0) for k, v in after.items()}
    traced = {"answers": [], "errors": [], "unanswered": 0, "started": 0}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if trace_dir:
        start_trace(trace_dir)
        traced = run_window(job, min(args.seconds, TRACE_SECONDS), compiles, owed)
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    del job, lowerings
    gc.collect()

    from bench import peaks, trace

    work = pipeline.work(cfg, rows, traffic)
    if args.rehearse:
        least_s, bound = None, None
    else:
        least_s, bound = peaks.least_seconds(work["flops"], work["bytes"], dev.device_kind)
    reduction = None
    if trace_dir:
        xplane = trace.find_xplane(trace_dir)
        if args.keep_trace:
            Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
            shutil.copy(xplane, Path(args.keep_trace) / f"{args.workload}.{args.seed}.xplane.pb")
        if not args.rehearse:
            reduction = trace.reduce_file(xplane)
            device["busy_s"] = reduction.busy_s
            device["window_s"] = reduction.window_s
        shutil.rmtree(trace_dir, ignore_errors=True)

    failed, worst, _ = check_answers(pipeline, cfg, rows, seeds,
                                     win["answers"] + traced["answers"], limits)
    errors = win["errors"] + traced["errors"]
    unanswered = win["unanswered"] + traced["unanswered"]
    failed += unanswered
    attempted = (win["started"] + traced["started"]) * len(owed)
    measured = Measured(setup_s=setup_s, window_s=win["window_s"],
                        jobs=len(win["answers"]), latencies_s=win["latencies_s"],
                        compiles=win["compiles"], least_s=least_s, trace=reduction,
                        trace_jobs=len(traced["answers"]), counters=counters)
    metrics = {}
    for entry in spec["per_layer" if args.trace else "end_to_end"]:
        value = None if args.rehearse else \
            load_module(BENCH / "metrics" / f"{entry['name']}.py").read(measured)
        if value is not None or args.rehearse:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result = {"correct": attempted > 0 and failed == 0, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.ops,
                               "idle_gaps": reduction.idle_gaps}
    result["work"] = {"flops_per_job": work["flops"], "bytes_per_job": work["bytes"],
                      "least_ms_per_job": None if least_s is None else least_s * 1e3,
                      "bound": bound}
    lat = sorted(measured.latencies_s)
    result["window"] = {"jobs": measured.jobs, "compiles": measured.compiles,
                        "traced_jobs": measured.trace_jobs, "unanswered": unanswered,
                        "counters": {k: v for k, v in counters.items() if v},
                        "errors": errors[:3]}
    if lat and not args.rehearse:
        result["window"].update(seconds=measured.window_s,
                                latency_p50_ms=lat[len(lat) // 2] * 1e3,
                                latency_max_ms=lat[-1] * 1e3,
                                latency_max_at=measured.latencies_s.index(lat[-1]))
    result["compared"] = {n: {"value": worst.get(n), "limit": limits[n]} for n in limits}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of the cell's data")
    ap.add_argument("--seconds", type=float, required=True, help="length of the window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: add a traced window; report the per-layer metrics")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; every metric's value is null")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the window's .xplane.pb into DIR")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    result = run(parse_args(argv))
    for name, c in result["compared"].items():
        print(f"[compared] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
