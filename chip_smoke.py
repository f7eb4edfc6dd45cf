#!/usr/bin/env python3
"""Bring-up check on one TPU chip: the pipeline walker, the CC kernel and
full-width qwen2-0.5b serving, each through the entry point a user calls.

    python chip_smoke.py                                 # one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse    # any backend, tiny sizes

Phases, in order, in this one process (it imports JAX once and starts no
child process):

* ``walker`` — the paper's IDA pipelines on the fused Pallas walker
  (``vee.apps.run_device_dag``): linear regression on 2,097,152 x 101
  against the float64 oracle, recommendation on 65,536 users x 4,096
  items (density 0.3) against the float64 oracle, and a §14 batch of three
  262,144-row regressions in one launch against each member run alone.
* ``cc`` — ``kernels.ops.cc_step`` on Graph500's Kronecker graph (scale 14,
  edge factor 16) as a dense 16,384 x 16,384 adjacency under the STATIC,
  MFSC and GSS schedules, against ``kernels.ref.cc_propagate_ref``.
* ``lm`` — ``launch/serve.py``'s LM path (``main``, no ``--smoke``) at
  qwen2-0.5b's published width: 8 requests, prompt 32, 16 new tokens, 4
  slots, GSS admission chunks. The logits of each request's first decode
  step are compared with a no-cache forward over prompt + first token.

Each phase prints its sizes and the bytes it keeps on the device, the time
of its first call (set-up, compile included), the time of its second call,
the compilations during the second call and the device's peak memory so
far. These are bring-up facts, not metrics. A failed check exits non-zero
with the reason. The last line, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase, unless
``--rehearse`` runs the same phases at tiny sizes on whatever backend
there is; a rehearsal never prints the ``ok`` line.

Tolerances (float32 walker and float64 oracles; bf16 serving):

* linreg: max |beta - beta_ref| <= 1e-4 * max |beta_ref| over all
  coefficients, and the slope coefficients alone within 1e-2 of their
  norm (they are small next to the intercept);
* recommendation: each user's item is the oracle's, or one whose float64
  score is within 1e-6 of the best (a near-tie), for at most 1% of users;
* §14 batch: every member's stage values equal its solo run exactly
  (members are disjoint and run on one backend);
* cc: exact equality (a max over labels has no rounding);
* lm: max |logits - ref| <= 0.05 * max |ref| per request, and the same
  top token for at least 7 of 8 requests.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models.model import param_shapes  # noqa: E402
from repro.vee import rmat_graph  # noqa: E402
from repro.vee.apps import (  # noqa: E402
    linear_regression_oracle, linreg_device_lowering, merge_device_lowerings,
    recommendation_device_lowering, recommendation_oracle,
    recommendation_regret, run_device_dag, split_device_values)

LINREG_RTOL = 1e-4
LINREG_SLOPE_RTOL = 1e-2
REC_REGRET = 1e-6
REC_MAX_FLIPS = 0.01
LM_RTOL = 0.05
LM_MIN_TOP1 = 7 / 8

FULL = dict(linreg=(2_097_152, 101, 2048), rec=(65_536, 4_096, 128),
            batch=(262_144, 101, 2048, 3), cc=(14, 16, 256, 1024),
            lm=("qwen2-0.5b", False))
TINY = dict(linreg=(4_096, 101, 512), rec=(512, 256, 128),
            batch=(1_024, 101, 512, 3), cc=(9, 16, 128, 256),
            lm=("qwen2-0.5b", True))
LM_ARGS = ["--mode", "lm", "--requests", "8", "--slots", "4",
           "--prompt-len", "32", "--gen-len", "16", "--technique", "GSS"]

_COMPILES = [0]


def _count_compile(event: str, duration: float, **kwargs) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1


def check(ok: bool, what: str) -> None:
    """Print one check; stop the run with the reason when it failed."""
    print(f"[check] {what}: {'pass' if ok else 'FAIL'}", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: FAILED {what}")


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def twice(label: str, sizes: str, resident: int, call):
    """Run ``call`` twice; print set-up (first) and second-call facts."""
    t0 = time.perf_counter()
    first = call()
    setup = time.perf_counter() - t0
    before = _COMPILES[0]
    t0 = time.perf_counter()
    second = call()
    again = time.perf_counter() - t0
    print(f"[{label}] {sizes}; resident on device {resident} B; "
          f"set-up (first call, compile included) {setup:.3f} s; "
          f"second call {again:.3f} s; compilations in second call "
          f"{_COMPILES[0] - before}; peak device memory {peak_bytes()}",
          flush=True)
    return first, second


def phase_walker(sz) -> None:
    n, cols, tile = sz["linreg"]
    low = linreg_device_lowering(n, cols, tile=tile)
    techs = {"moments": "GSS", "syrk_gemv": "FAC2"}
    (vals, _), _ = twice(
        "walker:linreg", f"{n} rows x {cols} columns, tile {tile}",
        low.values["W"].nbytes, lambda: run_device_dag(low, techs))
    beta = low.finalize(vals).reshape(-1)
    del low, vals
    want = linear_regression_oracle(n, cols).reshape(-1)
    err = np.abs(beta - want).max() / np.abs(want).max()
    slope_err = (np.linalg.norm(beta[:-1] - want[:-1])
                 / np.linalg.norm(want[:-1]))
    print(f"[walker:linreg] max |beta - ref| / max |ref| = {err:.3e}; "
          f"slope error / slope norm = {slope_err:.3e}", flush=True)
    check(bool(np.isfinite(beta).all()) and err <= LINREG_RTOL
          and slope_err <= LINREG_SLOPE_RTOL,
          f"linreg beta within {LINREG_RTOL} (slopes {LINREG_SLOPE_RTOL}) "
          "of the float64 oracle")

    users, items, tile = sz["rec"]
    low = recommendation_device_lowering(users, items, tile=tile)
    (vals, _), _ = twice(
        "walker:recommendation",
        f"{users} users x {items} items, density 0.3, tile {tile}",
        low.values["R"].nbytes, lambda: run_device_dag(low, "MFSC"))
    got = vals["scores"].reshape(-1)
    del low, vals
    gc.collect()
    flips = got != recommendation_oracle(users, items)
    regret = recommendation_regret(got, users, items)
    print(f"[walker:recommendation] items differing from the oracle: "
          f"{int(flips.sum())} of {users}; largest score regret "
          f"{regret.max():.3e}", flush=True)
    check(regret.max() <= REC_REGRET and flips.mean() <= REC_MAX_FLIPS,
          f"top items match the float64 oracle up to near-ties "
          f"(regret <= {REC_REGRET})")

    n, cols, tile, members = sz["batch"]
    lows = [linreg_device_lowering(n, cols, tile=tile, seed=s)
            for s in range(1, members + 1)]
    merged = merge_device_lowerings(lows)
    (vals, _), _ = twice(
        "walker:batch", f"{members} x {n} rows x {cols} columns in one "
        f"launch, tile {tile}",
        sum(v.nbytes for v in merged.values.values()),
        lambda: run_device_dag(merged, "SS"))
    singles = [run_device_dag(low, "SS")[0] for low in lows]
    exact = all(np.array_equal(got[k], want[k])
                for got, want in zip(split_device_values(vals, members),
                                     singles) for k in want)
    check(exact, "batched launch equals each member run alone, exactly")
    worst = 0.0
    for seed, beta in enumerate(merged.finalize(vals), start=1):
        want = linear_regression_oracle(n, cols, seed=seed).reshape(-1)
        worst = max(worst, np.abs(beta.reshape(-1) - want).max()
                    / np.abs(want).max())
    check(worst <= LINREG_RTOL,
          f"batched betas within {LINREG_RTOL} of the float64 oracle")


def phase_cc(sz) -> None:
    scale, edge_factor, tile_r, tile_c = sz["cc"]
    G = rmat_graph(scale=scale, edge_factor=edge_factor, seed=0)
    n = G.n_rows
    Gd = jax.device_put(G.to_dense())
    c = jnp.arange(1, n + 1, dtype=jnp.float32)
    want = np.asarray(ref.cc_propagate_ref(Gd, c))
    for technique in ("STATIC", "MFSC", "GSS"):
        got, _ = twice(
            f"cc:{technique}", f"{n} x {n} dense adjacency of "
            f"{G.nnz} edges (scale {scale}, edge factor {edge_factor}), "
            f"tiles {tile_r} x {tile_c}", Gd.nbytes + c.nbytes,
            lambda: np.asarray(ops.cc_step(Gd, c, technique=technique,
                                           tile_r=tile_r, tile_c=tile_c)))
        check(np.array_equal(got, want),
              f"cc_step under {technique} equals cc_propagate_ref exactly")


def phase_lm(sz) -> None:
    arch, smoke = sz["lm"]
    argv = LM_ARGS + ["--arch", arch] + (["--smoke"] if smoke else [])
    cfg = get_config(arch)
    cfg = cfg.reduced() if smoke else cfg
    params_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for s in jax.tree.leaves(param_shapes(cfg)))
    out, again = twice(
        "lm", f"{arch} ({cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
        f"{cfg.vocab_size}), 8 requests x (32 prompt + 16 new) tokens, "
        "4 slots, GSS", params_bytes, lambda: serve.main(argv))
    tokens, logits = out["tokens"], out["first_decode_logits"]
    check(tokens.shape == (8, 16) and logits.shape[0] == 8
          and bool(np.isfinite(logits).all()),
          "serving returns 16 tokens and finite first-decode logits "
          "for 8 requests")
    print(f"[lm] second call generated the same tokens: "
          f"{bool(np.array_equal(tokens, again['tokens']))}", flush=True)

    model = Model(cfg)
    params = model.init_params(jax.random.key(0))  # serve_lm's weights

    @jax.jit
    def forward(params, seq):
        positions = jnp.arange(seq.shape[1])
        x = model._embed_inputs(params, {"tokens": seq}, positions)
        x, _, _ = model._trunk(params, x, positions)
        return model._logits(params, x[:, -1:])[:, 0].astype(jnp.float32)

    seq = np.concatenate([out["prompts"], tokens[:, :1]], axis=1)
    want = np.asarray(forward(params, jnp.asarray(seq)))
    err = (np.abs(logits - want).max(axis=1) / np.abs(want).max(axis=1)).max()
    top1 = (logits.argmax(axis=1) == want.argmax(axis=1)).mean()
    print(f"[lm] first decode step vs no-cache forward: max relative error "
          f"{err:.3e}; same top token for {top1 * 8:.0f} of 8", flush=True)
    check(err <= LM_RTOL and top1 >= LM_MIN_TOP1,
          f"first-decode logits within {LM_RTOL} of the no-cache forward")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase at tiny sizes on any backend; "
                         "never prints the ok line")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    print(f"[device] platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu" and not args.rehearse:
        sys.exit("chip_smoke: no TPU found (use --rehearse to run the "
                 "phases at tiny sizes on this backend)")
    cache = Path(enable_compile_cache())
    warm = any(f.is_file() for f in cache.rglob("*"))
    print(f"[setup] persistent compile cache at {cache} "
          f"({'warm' if warm else 'cold'})", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    sizes = TINY if args.rehearse else FULL
    for name, phase in (("walker", phase_walker), ("cc", phase_cc),
                        ("lm", phase_lm)):
        t0 = time.perf_counter()
        phase(sizes)
        gc.collect()
        print(f"[{name}] phase passed in {time.perf_counter() - t0:.1f} s",
              flush=True)
    if args.rehearse:
        print("[rehearse] every phase passed at tiny sizes; no ok line",
              flush=True)
        return
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
