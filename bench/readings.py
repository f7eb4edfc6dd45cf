#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, in one process.

    python3 bench/readings.py --workload higgs-linreg.solo --seeds 1 2 3 \\
        --control-seeds 1 2 3 --seconds 10 --out readings.jsonl

For each seed: the cell's set-up and a window of ``--seconds`` as
``bench/run.py`` drives them, with the same job (the pipeline's own
``make_job`` where it has one), then every answer of the window compared
with the plain reference by its tenant's Checker, through
``bench/run.py``'s own ``run_window`` and ``check_answers`` (the
program's reading, the worst over the window's answers). For each
control seed: the control, the reference put in the program's place one
precision step below what the configuration states, compared with the
same reference. Prints one JSON line per seed
and a last line with the lower reading (the largest of the program's) and
the upper reading (the smallest of the control's) of each number. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402


def distinct(answers: list) -> int:
    """How many different answers the window gave, over all tenants."""
    import numpy as np

    def key(owed, answer):
        parts = answer.values() if isinstance(answer, dict) else [answer]
        return (owed,) + tuple(np.asarray(p).tobytes() for p in parts)

    return len({key(k, a) for k, a in answers})


def readings(workload: str, seeds: list[int], control_seeds: list[int],
             seconds: float, rehearse: bool = False, out=None) -> dict:
    harness.checkout_cache()
    import jax

    spec = harness.load_cell(workload, rehearse)
    cfg, traffic, limits = spec["config"], spec["traffic"], spec["limits"]
    if not rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("readings: no TPU found")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    pipeline = harness.pipeline_of(cfg)
    program: dict[str, list] = {}
    control: dict[str, list] = {}
    for seed in sorted(set(seeds) | set(control_seeds)):
        rows, tenant_seeds = harness.tenants_of(cfg, traffic, seed)
        line = {"workload": workload, "seed": seed}
        answers = []
        unanswered = 0
        if seed in seeds:
            lows = [pipeline.build(cfg, rows, s) for s in tenant_seeds]
            job = harness.job_of(pipeline, lows, cfg, traffic)
            job()
            win = harness.run_window(job, seconds, [0], harness.owed_of(pipeline, cfg, traffic))
            del job, lows
            gc.collect()
            answers, unanswered = win["answers"], win["unanswered"]
            line.update(jobs=len(answers), errors=win["errors"][:3], unanswered=unanswered,
                        distinct=distinct(answers))
        failed, worst_program, worst_control = harness.check_answers(
            pipeline, cfg, rows, tenant_seeds, answers, limits,
            control=seed in control_seeds)
        failed += unanswered
        if seed in seeds:
            line.update(program=worst_program, failed=failed)
            for n, v in worst_program.items():
                program.setdefault(n, []).append(v)
        if seed in control_seeds:
            line["control"] = worst_control
            for n, v in worst_control.items():
                control.setdefault(n, []).append(v)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    summary = {"workload": workload, "limits": limits,
               "lower": {n: max(v) for n, v in program.items() if v},
               "upper": {n: min(v) for n, v in control.items() if v},
               "program_seeds": len(seeds), "control_seeds": len(control_seeds)}
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="append every line to this JSON-lines file")
    args = ap.parse_args(argv)
    out = None
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        out = open(args.out, "a")
    readings(args.workload, args.seeds, args.control_seeds, args.seconds,
             args.rehearse, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
