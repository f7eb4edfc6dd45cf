#!/usr/bin/env python3
"""The device-DAG path's phases in one cell: where a job's host time and the device's idle time go.

    python3 bench/phases.py --workload higgs-linreg.solo --seed 7 --seconds 20
    python3 bench/phases.py ... --keep-trace chiprun_out/traces   # keep the .xplane.pb
    JAX_PLATFORMS=cpu python3 bench/phases.py ... --rehearse      # tiny sizes, any backend

Reads what the program's own telemetry records (DESIGN.md §18) and
``bench/run.py`` does not read: the ``devdag.*`` phases and the compile
counters. It builds the cell as ``bench/run.py`` does (configuration,
traffic, warm-up jobs; job ``n`` is the program's ``job`` identity
``str(n)``) and then runs, in one process, ``--rounds`` pairs of
untraced windows of ``--seconds`` in alternating order (off, on, on,
off, ...) and one traced window:

* ``off``: no tracer, as a ``--trace 0`` run;
* ``on``: the program's ``Tracer`` on: the seconds per job under each
  phase, the compile counters' deltas per job, the launches that ran a
  backend compile, and the phases of the slowest job
  (``latency_max_spans``); ``off`` against ``on`` is the tracer's cost;
* ``traced``: at most ``TRACE_SECONDS`` under the profiler, Tracer on:
  the device's idle time under each phase (``idle_spans``, the innermost
  phase wins; ``(outside the program)`` for idle time under none).

Per-job numbers are over answered jobs, a tenant of a coalesced launch
counting one, as in ``bench/run.py``. The last line of standard output
is one JSON object; its ``proposed`` key holds the numbers under the
names of the per-layer metrics that would read them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run as harness  # noqa: E402

PHASE_PREFIX = "devdag."
OUTSIDE = "(outside the program)"
CHILDREN = ("devdag.tables", "devdag.table_put", "devdag.launch",
            "devdag.readback", "devdag.combine")


def innermost_segments(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """``[lo, hi)`` cut into ``(start, end, name)`` by the innermost span covering each part.

    ``spans`` are ``(name, start, end)`` of one thread, so they nest; a
    part under none is named ``OUTSIDE``.
    """
    segments: list[tuple[int, int, str]] = []
    t = lo
    stack: list[tuple[str, int, int]] = []

    def emit(upto: int) -> None:
        nonlocal t
        if upto > t:
            segments.append((t, upto, stack[-1][0] if stack else OUTSIDE))
            t = upto

    clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in spans if min(e, hi) > max(s, lo)]
    for name, s, e in sorted(clipped, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            emit(stack[-1][2])
            stack.pop()
        emit(s)
        stack.append((name, s, e))
    while stack:
        emit(stack[-1][2])
        stack.pop()
    emit(hi)
    return segments


def idle_spans(devices: dict, host: list, window: tuple[int, int]) -> dict[str, float]:
    """Device-idle seconds of ``window`` (ns) under each ``devdag.*`` host span, mean over devices used."""
    from bench import trace

    lo, hi = window
    segments = innermost_segments(
        [ev for ev in host if ev[0].startswith(PHASE_PREFIX)], lo, hi)
    idle_ns: dict[str, int] = defaultdict(int)
    used = 0
    for events in devices.values():
        events = list(trace._clip(events, lo, hi))
        if not events:
            continue
        used += 1
        gaps = trace._gaps(trace._union((s, e) for _, s, e in events), lo, hi)
        k = 0
        for gs, ge in gaps:  # both lists sorted and disjoint: one merge
            while k < len(segments) and segments[k][1] <= gs:
                k += 1
            j = k
            while j < len(segments) and segments[j][0] < ge:
                s, e, name = segments[j]
                idle_ns[name] += min(e, ge) - max(s, gs)
                j += 1
    if not used:
        raise ValueError("no device operation ran in the window")
    return {name: ns / used / 1e9 for name, ns in sorted(idle_ns.items(), key=lambda kv: -kv[1])}


def make_traced_job(pipeline, lowerings: list, techniques, tracer_box: list):
    """``bench.run.make_job`` with the program's tracer (``tracer_box[0]``, or None) and job ids.

    Also returns the list it appends to per launch: ``(job id, seconds in
    run_device_dag, backend compiles during the call)``.
    """
    from jax.profiler import TraceAnnotation

    from repro.core import compile_counts
    from repro.core.admission import BATCH_SEP
    from repro.vee import apps

    n = len(lowerings)
    if n > 1 and isinstance(techniques, dict):
        techniques = {f"{stage}{BATCH_SEP}{j}": t for stage, t in techniques.items()
                      for j in range(n)}
    calls = []

    def job():
        tracer, jid = tracer_box[0], str(len(calls))
        low = lowerings[0]
        if n > 1:
            with TraceAnnotation("bench.merge"):
                low = apps.merge_device_lowerings(lowerings, tracer=tracer, job=jid)
        backend = compile_counts()["backend_compiles"]
        t0 = time.perf_counter()
        with TraceAnnotation("bench.run_device_dag"):
            values, _ = apps.run_device_dag(low, techniques, tracer=tracer, job=jid)
        calls.append((jid, time.perf_counter() - t0,
                      compile_counts()["backend_compiles"] - backend))
        with TraceAnnotation("bench.answer"):
            parts = [values] if n == 1 else apps.split_device_values(values, n)
            return [(pipeline.answer(one, vals), time.perf_counter())
                    for one, vals in zip(lowerings, parts)]

    return job, calls


def phase_seconds(tracer) -> dict[str, dict[str, float]]:
    """``{job id: {phase: seconds}}`` from the tracer's ``devdag.*`` slices."""
    out: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for s in tracer.spans():
        if s.kind.startswith(PHASE_PREFIX):
            out[s.job][s.kind] += s.dur
    return out


def run(args) -> dict:
    harness.checkout_cache()
    import jax

    from repro.core import Tracer, compile_counts
    from repro.launch.compile_cache import enable_compile_cache

    spec = harness.load_cell(args.workload, args.rehearse)
    cfg, traffic = spec["config"], spec["traffic"]
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"phases: {args.workload} needs a TPU chip, found {dev.platform}")
    enable_compile_cache()
    compiles = [0]

    def count_compile(event: str, duration: float, **kwargs) -> None:
        if event == harness.COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count_compile)

    pipeline = harness.pipeline_of(cfg)
    rows, seeds = harness.tenants_of(cfg, traffic, args.seed)
    lowerings = [pipeline.build(cfg, rows, s) for s in seeds]
    tracer_box = [None]
    job, calls = make_traced_job(pipeline, lowerings, cfg["techniques"], tracer_box)
    for _ in range(traffic["warmup_jobs"]):
        job()
    result = {"workload": args.workload, "seed": args.seed,
              "device": {"platform": dev.platform, "kind": dev.device_kind},
              "setup_s": time.perf_counter() - T_START}

    # off and on windows alternate (off, on, on, off, ...), so drift over
    # the run moves both sides alike
    tracer = Tracer()
    windows: dict[str, list] = {"off": [], "on": []}
    counts: dict[str, float] = defaultdict(float)
    for r in range(args.rounds):
        for mode in ("off", "on") if r % 2 == 0 else ("on", "off"):
            tracer_box[0] = tracer if mode == "on" else None
            first, before = len(calls), compile_counts()
            win = harness.run_window(job, args.seconds, compiles)
            after = compile_counts()  # watching from the first on job's start
            win["calls"] = calls[first:]
            windows[mode].append(win)
            if mode == "on":
                for k in after:
                    if k != "watching":
                        counts[k] += after[k] - before[k]
    for mode, wins in windows.items():
        jobs = sum(len(w["answers"]) for w in wins)
        seconds = sum(w["window_s"] for w in wins)
        result[mode] = {"jobs": jobs, "window_s": seconds, "jobs_per_s": jobs / seconds,
                        "jobs_per_s_by_window": [len(w["answers"]) / w["window_s"] for w in wins]}

    on_calls = [c for w in windows["on"] for c in w["calls"]]
    jobs = result["on"]["jobs"]
    per_job = phase_seconds(tracer)
    totals: dict[str, float] = defaultdict(float)
    for phases in per_job.values():
        for name, sec in phases.items():
            totals[name] += sec
    slowest = max((lat, w["calls"][k // len(lowerings)][0])
                  for w in windows["on"] for k, lat in enumerate(w["latencies_s"]))
    result["on"].update(
        phase_ms_per_job={k: v / jobs * 1e3 for k, v in sorted(totals.items())},
        children_over_calls=sum(totals[k] for k in CHILDREN) / sum(c[1] for c in on_calls),
        compiles_per_job=sum(w["compiles"] for w in windows["on"]) / jobs,
        counters_per_job={k: v / jobs for k, v in counts.items()},
        counter_split_exact=counts["cache_loads"] + counts["backend_compiles"]
        == sum(w["compiles"] for w in windows["on"]),
        # (window, launch in window, ms of the call) of each backend compile
        backend_compile_at=[(i, k, w["calls"][k][1] * 1e3) for i, w in enumerate(windows["on"])
                            for k in range(len(w["calls"])) if w["calls"][k][2]],
        latency_max_ms=slowest[0] * 1e3,
        latency_max_spans=dict(sorted(per_job[slowest[1]].items())))

    trace_dir = tempfile.mkdtemp(prefix="phases-trace-")
    tracer_box[0] = Tracer()
    harness.start_trace(trace_dir)
    traced = harness.run_window(job, min(args.seconds, harness.TRACE_SECONDS), compiles)
    jax.profiler.stop_trace()

    from bench import trace

    xplane = trace.find_xplane(trace_dir)
    if args.keep_trace:
        Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
        shutil.copy(xplane, Path(args.keep_trace) / f"{args.workload}.{args.seed}.phases.xplane.pb")
    devices, host = trace.load(xplane)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if args.rehearse:  # no device plane off the chip
        result["traced"] = {"jobs": len(traced["answers"]),
                            "phases": sorted({ev[0] for ev in host if ev[0].startswith(PHASE_PREFIX)})}
        return result
    window = trace.window_of(host)
    reduction = trace.reduce(devices, host, window)
    spans = idle_spans(devices, host, window)
    walker_s, _ = reduction.matching(r'custom_call_target="tpu_custom_call"')
    t_jobs = len(traced["answers"])
    t_calls = calls[-traced["started"]:]
    result["traced"] = {"jobs": t_jobs, "window_s": reduction.window_s,
                        "backend_compile_at": [(k, c[1] * 1e3) for k, c in enumerate(t_calls) if c[2]],
                        "idle_share": reduction.idle_share,
                        "walker_ms_per_job": walker_s / t_jobs * 1e3 if t_jobs else None,
                        "idle_spans": spans, "device_ops": reduction.ops[:4]}
    result["proposed"] = {
        "launch_ms_per_job": result["on"]["phase_ms_per_job"].get("devdag.launch"),
        "lower_ms_per_job": (counts["trace_s"] + counts["lower_s"]) / jobs * 1e3,
        "cache_loads_per_job": counts["cache_loads"] / jobs,
        "backend_compiles_per_job": counts["backend_compiles"] / jobs,
        "readback_ms_per_job": result["on"]["phase_ms_per_job"].get("devdag.readback"),
        "idle_launch_share": 100.0 * spans.get("devdag.launch", 0.0) / reduction.window_s,
        "idle_outside_share_of_idle": spans.get(OUTSIDE, 0.0) / sum(spans.values()),
    }
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of the cell's data")
    ap.add_argument("--seconds", type=float, required=True, help="length of each untraced window")
    ap.add_argument("--rounds", type=int, default=1,
                    help="pairs of untraced windows, tracer off and on, in alternating order")
    ap.add_argument("--rehearse", action="store_true", help="tiny sizes on any backend")
    ap.add_argument("--keep-trace", metavar="DIR", help="copy the traced window's .xplane.pb into DIR")
    return ap.parse_args(argv)


if __name__ == "__main__":
    print(json.dumps(run(parse_args())), flush=True)
