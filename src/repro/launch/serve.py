"""Production serving launcher: LM continuous batching with DaphneSched
admission (DESIGN.md §6.2) and multi-tenant IDA pipeline serving through
the §10 PipelineServer.

    # LM token serving (admission chunks follow a DLS technique)
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch granite-8b \
        --smoke --requests 32 --slots 4 --technique GSS

    # concurrent IDA pipelines from three tenants on one worker pool
    PYTHONPATH=src python -m repro.launch.serve --mode pipelines \
        --arbiter fair --workers 4 --compare

LM serving params use the TP-only policy (`serve_no_fsdp`) measured in
EXPERIMENTS.md §Perf (collective term -98% on decode).
"""

from __future__ import annotations

import argparse
import time


def _pipeline_submissions(scale: int = 11):
    """A mixed multi-tenant submission set: graph analytics + ML training +
    interactive recommendations (heterogeneous stage costs, staggered
    arrivals)."""
    import numpy as np

    from ..core import Submission
    from ..vee import linreg_dag, recommendation_dag, rmat_graph
    from ..vee.apps import cc_iteration_dag

    G = rmat_graph(scale=scale, edge_factor=8, seed=5, relabel="blocks")
    labels = np.arange(1, G.n_rows + 1, dtype=np.int64)
    nnz = G.row_nnz().astype(float)
    cc_costs = {"propagate": nnz * 2e-7 + 5e-8,
                "changed": np.full(G.n_rows, 2e-8)}
    lr_dag, _ = linreg_dag(20_000, 21)
    return [
        Submission(name="cc_batch", dag=cc_iteration_dag(G, labels),
                   tenant="graph", weight=1.0, priority=0,
                   stage_costs=cc_costs),
        Submission(name="linreg_train", dag=lr_dag, tenant="ml", weight=2.0,
                   priority=1, arrival_s=0.005),
        Submission(name="recommend_1", dag=recommendation_dag(4096, 64, seed=1),
                   tenant="interactive", weight=4.0, priority=2,
                   arrival_s=0.01, deadline_s=2.0),
        Submission(name="recommend_2", dag=recommendation_dag(4096, 64, seed=2),
                   tenant="interactive", weight=4.0, priority=2,
                   arrival_s=0.02, deadline_s=2.0),
    ]


def _telemetry(args):
    """Build the (tracer, metrics) pair requested by ``--trace-out`` /
    ``--metrics-out``; either is None when its flag is absent, which the
    runtimes treat as the zero-overhead NullTracer path (docs/OBSERVABILITY.md)."""
    from ..core import MetricsRegistry, Tracer

    tracer = Tracer() if args.trace_out else None
    metrics = MetricsRegistry() if args.metrics_out else None
    return tracer, metrics


def _dump_telemetry(args, tracer, metrics) -> None:
    """Write the Chrome trace and the metrics snapshot (JSON + a ``.prom``
    Prometheus-text sibling, with the compile counters when a traced
    device-DAG call started them) after a traced run."""
    from pathlib import Path

    from ..core import collect_compile_metrics

    if tracer is not None:
        tracer.write_chrome_trace(args.trace_out)
        print(f"[serve] trace: {len(tracer)} events -> {args.trace_out}",
              flush=True)
    if metrics is not None:
        collect_compile_metrics(metrics)
        out = Path(args.metrics_out)
        out.write_text(metrics.to_json() + "\n")
        prom = out.with_suffix(".prom")
        prom.write_text(metrics.to_prometheus())
        print(f"[serve] metrics -> {out} (+ {prom})", flush=True)


def _make_serving_arbiter(spec: str, args):
    """Resolve an --arbiter spec; ``preemptive`` wraps weighted-fair with
    the pool size and slack from the command line (DESIGN.md §15)."""
    from ..core import make_arbiter

    if spec == "preemptive":
        return make_arbiter("preemptive", inner="fair",
                            n_workers=args.workers, slack_s=args.slack)
    return make_arbiter(spec)


def serve_pipelines(args) -> None:
    """Serve the mixed submission set on one shared pool per arbiter."""
    from ..core import PipelineServer, analyze_critical_path, make

    cfg = make("config", args.config, n_workers=args.workers)
    arbiters = (("fifo", "priority", "fair", "preemptive") if args.compare
                else (args.arbiter,))
    tracer = metrics = None
    for arb in arbiters:
        # fresh tracer per arbiter: job names repeat across compare runs and
        # would otherwise merge into one misleading job hull
        tracer, metrics = _telemetry(args)
        subs = _pipeline_submissions()
        tenant_of = {s.name: s.tenant for s in subs}
        server = PipelineServer(cfg, arbiter=_make_serving_arbiter(arb, args),
                                tracer=tracer, metrics=metrics)
        for s in subs:
            server.submit(s)
        res = server.serve()
        preempt = (f" preemptions={len(res.preemptions)}"
                   if arb == "preemptive" else "")
        print(f"[serve:pipelines] arbiter={arb} jobs={len(res.jobs)}{preempt} "
              f"makespan={res.makespan_s * 1e3:.1f}ms "
              f"p50={res.latency_percentile(50) * 1e3:.1f}ms "
              f"p99={res.latency_percentile(99) * 1e3:.1f}ms", flush=True)
        for name, r in sorted(res.jobs.items()):
            dl = ("" if r.deadline_met is None
                  else f" deadline_met={r.deadline_met}")
            print(f"  {name:>14} tenant={tenant_of[name]:<12} "
                  f"latency={r.latency_s * 1e3:8.1f}ms "
                  f"service={r.service_s * 1e3:7.1f}ms "
                  f"tasks={r.n_tasks}{dl}", flush=True)
        if tracer is not None:
            cp = analyze_critical_path(tracer, makespan=res.makespan_s)
            print(f"  critical path ({arb}): {cp.describe()}", flush=True)
    _dump_telemetry(args, tracer, metrics)


def serve_openloop(args) -> None:
    """Replay a heavy-tailed open-loop trace through the §14 front door."""
    from ..core import (
        AdmissionController, BatchPolicy, TokenBucket, heavy_tailed_trace,
        replay_open_loop)
    from ..core.online import FeedbackLog

    trace = heavy_tailed_trace(args.requests, seed=3, load=args.load,
                               n_workers=args.workers)
    base = replay_open_loop(trace, n_workers=args.workers, arbiter="fifo")
    fb = FeedbackLog()
    adm = AdmissionController(
        buckets={"etl": TokenBucket(rate=400.0, capacity=20)}, feedback=fb)
    kwargs = ({"inner": "fair", "n_workers": args.workers,
               "slack_s": args.slack}
              if args.arbiter == "preemptive" else None)
    tracer, metrics = _telemetry(args)
    front = replay_open_loop(trace, n_workers=args.workers,
                             arbiter=args.arbiter, arbiter_kwargs=kwargs,
                             admission=adm,
                             batching=BatchPolicy(2e-3, 8), feedback=fb,
                             tracer=tracer, metrics=metrics)
    for tag, r in (("fifo baseline", base), ("front door", front)):
        preempt = f" preemptions={len(r.preemptions)}" if r.preemptions else ""
        print(f"[serve:openloop] {tag}: p50={r.latency_percentile(50) * 1e3:.2f}ms "
              f"p99={r.latency_percentile(99) * 1e3:.2f}ms "
              f"p99.9={r.latency_percentile(99.9) * 1e3:.2f}ms "
              f"hit={r.deadline_hit_rate():.3f} shed={r.shed_rate:.3f} "
              f"batches={r.n_batches}{preempt}", flush=True)
    _dump_telemetry(args, tracer, metrics)


def serve_lm(args) -> dict:
    """LM continuous batching with DLS-technique admission chunks.

    Returns what was generated: the seeded ``prompts`` ``(requests,
    prompt_len)``, ``tokens`` ``(requests, gen_len)`` (the prefill's pick,
    then one per decode step) and ``first_decode_logits`` ``(requests,
    padded_vocab)`` float32, the logits of each request's first decode step
    (None when ``gen_len`` is 1 and nothing is decoded).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..configs import get_config
    from ..core import make_partitioner
    from ..models import Model

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    s_max = args.prompt_len + args.gen_len
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))

    rng = np.random.default_rng(0)
    backlog = [rng.integers(0, cfg.vocab_size, args.prompt_len,
                            dtype=np.int32) for _ in range(args.requests)]
    part = make_partitioner(args.technique, args.requests, args.slots)

    tokens, first_logits = [], []
    served, t0 = 0, time.perf_counter()
    while served < args.requests:
        n = min(part.next_chunk() or 1, args.requests - served)
        reqs = backlog[served:served + n]
        served += n
        pad = (-len(reqs)) % args.slots
        toks = np.stack(reqs + [reqs[-1]] * pad)
        for i in range(0, len(toks), args.slots):
            real = min(args.slots, len(reqs) - i)  # pad rows come last
            sl = jnp.asarray(toks[i:i + args.slots])
            cache = model.init_cache(sl.shape[0], s_max)
            logits, cache = prefill(params, {"tokens": sl}, cache)
            tok = jnp.argmax(logits[:, -1], -1)[:, None]
            out = [tok[:real]]
            for t in range(args.gen_len - 1):
                logits, cache = decode(params, tok, cache,
                                       jnp.int32(args.prompt_len + t))
                if t == 0:
                    first_logits.append(logits[:real, 0])
                tok = jnp.argmax(logits[:, 0], -1)[:, None]
                out.append(tok[:real])
            tokens.append(jnp.concatenate(out, axis=1))
    dt = time.perf_counter() - t0
    print(f"[serve] {args.requests} requests x {args.gen_len} tokens in "
          f"{dt:.1f}s ({args.requests * args.gen_len / dt:.1f} tok/s)",
          flush=True)
    return {"prompts": np.stack(backlog),
            "tokens": np.concatenate([np.asarray(t) for t in tokens]),
            "first_decode_logits": np.concatenate(
                [np.asarray(x, np.float32) for x in first_logits])
            if first_logits else None}


def main(argv: list[str] | None = None):
    """Entry point: dispatch to LM serving or multi-tenant pipeline serving.

    ``argv`` defaults to the command line; returns what ``serve_lm``
    generated in ``--mode lm``.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "pipelines", "openloop"],
                    default="lm")
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--technique", default="GSS",
                    help="admission-chunk technique for --mode lm (11 options)")
    ap.add_argument("--config", default="gss/percore",
                    help="technique[/layout[/victim]] registry spec for "
                         "--mode pipelines (core.make_config)")
    ap.add_argument("--load", type=float, default=1.5,
                    help="offered-load factor for --mode openloop")
    ap.add_argument("--arbiter", default="fair",
                    choices=["fifo", "priority", "fair", "preemptive"],
                    help="inter-job policy for --mode pipelines/openloop")
    ap.add_argument("--slack", type=float, default=0.5,
                    help="deadline-pressure slack (s) for --arbiter preemptive")
    ap.add_argument("--workers", type=int, default=4,
                    help="shared pool size for --mode pipelines")
    ap.add_argument("--compare", action="store_true",
                    help="pipelines mode: run all four arbiters")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="write a Chrome/Perfetto trace of the run "
                         "(pipelines/openloop modes; docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-out", default=None, metavar="METRICS.json",
                    help="write a metrics snapshot as JSON plus a .prom "
                         "Prometheus-text sibling (pipelines/openloop modes)")
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.mode == "pipelines":
        serve_pipelines(args)
    elif args.mode == "openloop":
        serve_openloop(args)
    else:
        return serve_lm(args)


if __name__ == "__main__":
    main()
