"""Heterogeneous placement & co-execution (DESIGN.md §13) end-to-end:
calibrate per-substrate stage costs, solve a transfer-aware placement,
replay it in virtual time against the homogeneous baselines, then run the
real HeteroExecutor — host chunk workers + device walker lanes — and check
bit-equality with the host-only path.

    PYTHONPATH=src python examples/hetero_pipeline.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import (PipelineExecutor, SchedulerConfig, select_placement,
                        simulate_hetero_dag, tune_online_hetero)
from repro.vee import hetero_affinity_dag, linear_regression_hetero
from repro.vee.apps import linear_regression_oracle, linreg_device_lowering

# --- 1. a transfer-heavy synthetic DAG with opposite substrate affinities --
# ingest feeds two independent branches: `featurize` is host-friendly,
# `embed` wants the accelerator; `join` consumes both elementwise. The
# transfer term makes naive per-stage greedy ping-pong expensive — the
# solver keeps branches substrate-resident and overlaps them. (This is the
# same workload tests/test_placement.py scores.)
dag, costs = hetero_affinity_dag(4096)

placement, hetero_ms, base = select_placement(dag, costs, n_workers=8)
host_ms, dev_ms = base["host"], base["device"]
print("— transfer-aware placement solver —")
print(f"all-HOST   makespan: {host_ms * 1e6:10.1f} us")
print(f"all-DEVICE makespan: {dev_ms * 1e6:10.1f} us")
print(f"solved placement:    {hetero_ms * 1e6:10.1f} us  "
      f"({(min(host_ms, dev_ms) - hetero_ms) / min(host_ms, dev_ms) * 100:.1f}% "
      f"under the best homogeneous run)")
print(f"  {placement.describe()}")
res = simulate_hetero_dag(dag, costs, placement, n_workers=8)
print(f"  transfers={sum(res.stats.transfers.values())} "
      f"({res.transfer_s * 1e6:.1f} us on the link), "
      f"branch overlap featurize/embed = "
      f"{res.overlap_s('featurize', 'embed') * 1e6:.1f} us")

# --- 2. the online counterpart: bandit arms carry the substrate choice ----
# one focus stage explores per round (DagTuner discipline), so 160 rounds
# lets each stage's bandit play its full 40-arm hetero set once
tuned = tune_online_hetero(dag, costs, n_workers=8, rounds=160, seed=0)
print("\n— online substrate bandit (160 virtual rounds) —")
for name, arm in tuned.assign.items():
    print(f"  {name}: {'/'.join(arm[:3])} on {arm[3]}")
print(f"  converged makespan: {tuned.makespan * 1e6:.1f} us")

# --- 3. real co-execution: linreg split across both substrates ------------
cfg = SchedulerConfig(n_workers=2)
beta, hres, used = linear_regression_hetero(512, 9, cfg, device_speedup=4.0)
host_only = PipelineExecutor(
    linreg_device_lowering(512, 9, tile=64).dag,
    SchedulerConfig(technique="SS", n_workers=1)).run()
equal = all(np.array_equal(np.asarray(host_only.values[k]),
                           np.asarray(hres.values[k]))
            for k in host_only.values)
print("\n— real HeteroExecutor (linreg, host pool + device walker lane) —")
print(f"  placement: {used.describe()}")
print(f"  bit-equal to host-only PipelineExecutor: {equal}")
print(f"  beta matches oracle: "
      f"{np.allclose(beta, linear_regression_oracle(512, 9), atol=1e-4)}")
print(f"  absorbed by host/device: {hres.absorbed_by_host}/"
      f"{hres.absorbed_by_device}, cross-substrate consumptions: "
      f"{sum(hres.cross_consumptions.values())}")

# --- 4. the §14 unified surface: placement rides on the Submission --------
from repro.core import HeteroExecutor, Submission, make_placement

low = linreg_device_lowering(512, 9, tile=64)
pool = HeteroExecutor(low.dag, SchedulerConfig(technique="SS", n_workers=1),
                      make_placement("host", low.dag.stage_names))
sub = Submission(placement=make_placement("moments=device,syrk_gemv=split:0.5"))
hres2 = pool.run(sub)
equal2 = all(np.array_equal(np.asarray(host_only.values[k]),
                            np.asarray(hres2.values[k]))
             for k in host_only.values)
print("\n— §14 Submission-scoped placement on the same pool —")
print("  spec: moments=device,syrk_gemv=split:0.5 "
      f"(bit-equal to host-only: {equal2})")
