"""MoE expert dispatch as an irregular DaphneSched pipeline (DESIGN.md §17).

Lowers a skewed-router MoE layer into a route -> experts -> combine
PipelineDAG where the fan-out stage's rows are EXPERTS and each row's
cost is the router's token count for that expert — the canonical
irregular workload from the paper. The demo then:

  1. runs the dag under several DLS techniques and checks every one is
     bit-equal to the direct (unscheduled) oracle;
  2. replays the skewed costs in the deterministic simulator with the
     §12 online bandit, showing ``rechunk_pending`` moldable resizes and
     the adaptive-vs-best-static-uniform makespan gap;
  3. optionally re-runs the expert stage through the device walker
     (``--device``) and checks the token-side combine is still bit-equal.

    PYTHONPATH=src python examples/moe_pipeline.py --tokens 384
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import (OnlineScheduler, Tracer, select_offline_dag,
                        simulate_dag)
from repro.core.autotune import tune_online_dag
from repro.vee.ml_apps import moe_device_lowering, moe_dispatch_lowering


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=384)
    ap.add_argument("--experts", type=int, default=32)
    ap.add_argument("--skew", type=float, default=1.6)
    ap.add_argument("--capacity-factor", type=float, default=6.0)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--device", action="store_true",
                    help="also run the expert stage through the device walker")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace of the online-bandit "
                         "replay, including the moldable `resize` marks "
                         "(docs/OBSERVABILITY.md)")
    args = ap.parse_args()

    low = moe_dispatch_lowering(n_tokens=args.tokens, skew=args.skew, seed=0,
                                n_experts=args.experts,
                                capacity_factor=args.capacity_factor)
    kept = low.meta["expert_tokens"]
    print(f"router load (tokens/expert): max={kept.max()} min={kept.min()} "
          f"mean={kept.mean():.1f} cv={kept.std() / kept.mean():.2f}")

    # 1. scheduled == direct, bit-for-bit, whatever the technique
    direct = low.run_direct()
    for spec in ("static", "gss/percore", "fac2", "tss/pergroup"):
        t0 = time.perf_counter()
        sched, res = low.run(spec, n_workers=args.workers)
        dt = (time.perf_counter() - t0) * 1e3
        ok = np.array_equal(direct, sched)
        chunks = len(res.stages["experts"].schedule)
        print(f"  {spec:<14} expert_chunks={chunks:<3} steals={res.steals:<3} "
              f"{dt:6.1f}ms  bit-equal={'yes' if ok else 'NO'}")
        assert ok, f"{spec}: scheduled != direct"

    # 2. §12 online adaptation over the skewed per-expert costs
    assign, best, uniform = select_offline_dag(
        low.dag, low.stage_costs, n_workers=args.workers, passes=1)
    statics = sorted(uniform.values())
    on = OnlineScheduler(seed=0)
    tuned = tune_online_dag(low.dag, low.stage_costs,
                            n_workers=args.workers, rounds=40, seed=0)
    tracer = Tracer(job="moe") if args.trace_out else None
    simulate_dag(low.dag, low.stage_costs, n_workers=args.workers, online=on,
                 tracer=tracer)
    gain = (statics[0] - tuned.makespan) / statics[0] * 100
    print(f"offline oracle: {assign['experts']} makespan={best:.0f}")
    print(f"online bandit:  makespan={tuned.makespan:.0f} "
          f"({gain:+.1f}% vs best static uniform {statics[0]:.0f}); "
          f"moldable resizes={on.resizes}")
    if tracer is not None:
        n_resize = sum(1 for s in tracer.spans() if s.kind == "resize")
        tracer.write_chrome_trace(args.trace_out)
        print(f"trace: {len(tracer)} events ({n_resize} resize marks) "
              f"-> {args.trace_out}")
    if args.tokens >= 384 and args.experts >= 32:
        assert on.resizes.get("experts", 0) >= 1, "skew should force a resize"

    # 3. device walker path (Mosaic on a TPU, the Pallas interpreter elsewhere)
    if args.device:
        dlow = moe_device_lowering(low)
        from repro.vee.apps import run_device_dag
        t0 = time.perf_counter()
        vals, _ = run_device_dag(dlow, "GSS")
        dt = (time.perf_counter() - t0) * 1e3
        err = float(np.abs(dlow.finalize(vals) - direct).max())
        print(f"device walker:  {dt:.1f}ms  max |device - direct| = {err:.2e}")
        assert err <= 1e-5, "device combine != direct"


if __name__ == "__main__":
    main()
