"""Model-zoo lowerings: transformer and MoE step graphs on DaphneSched.

DESIGN.md §17. Three workloads from ``src/repro/models`` lowered with
``core.lower`` so the 11 partitioners, §12 online adaptation, §13 hetero
placement, and the §14 front door run on hardware-shaped work instead of
synthetic pipelines:

  ``transformer_step_lowering``  one inference step of a dense LM from
      ``configs/`` as an embed -> N x block -> head chain over the batch
      dimension, streamed stage-to-stage with elementwise edges.
  ``moe_dispatch_lowering``      MoE expert dispatch as an irregular
      fan-out: route (per token) -> experts (one row per expert, sized
      by the router's token counts — the skew that drives the §12
      bandits and moldable resizing) -> combine (per token).
  ``serving_pair``               two models from ``configs/`` submitted
      together through the §14 Submission API with measured stage costs
      and real activation byte sizes, so ``select_placement`` splits
      them across substrates on real transfer costs.

Bit-equality contract: every stage is a concat row/group stage whose
per-row function wraps a fixed-shape jitted (or eager fusion-stable)
JAX callable — scheduled and direct paths call the SAME functions on
the SAME inputs, so outputs match bit-wise under any technique, layout,
worker count, or resize (see core.lower module docstring). The MoE
expert FFN uses broadcast-multiply + reduce so the device walker body
computes the same bits as the eager host op (DESIGN.md §11).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core.dag import DEP_FULL, PipelineDAG, Stage, StageDep
from ..core.lower import (
    Lowered, chain_dag, costs_from_sizes, fanout_stage, measure_stage_costs,
)
from ..models import blocks
from ..models.model import Model
from ..models.moe import init_moe
from .apps import DeviceLowering

__all__ = [
    "transformer_step_lowering", "moe_dispatch_lowering",
    "moe_device_lowering", "skewed_tokens", "serving_pair",
]


# ---------------------------------------------------------------------------
# (a) transformer inference step: embed -> N x block -> head over the batch
# ---------------------------------------------------------------------------

def transformer_step_lowering(
    arch: str = "qwen2-0.5b",
    batch: int = 8,
    seq: int = 12,
    seed: int = 0,
) -> Lowered:
    """Lower one inference step of a dense LM into a streamed stage chain.

    Rows are batch elements. Stage ``embed`` turns a token row into
    ``(seq, d)`` activations, ``block{l}`` applies layer ``l``, ``head``
    produces last-position logits ``(vocab,)``. Activations cross stage
    boundaries as float32 (bf16 -> f32 -> bf16 round-trips exactly), and
    every per-row function is a fixed batch-1 jit of the real model
    components — so the lowered step is bit-equal to the direct
    (unscheduled) composition of the same functions.
    """
    cfg = get_config(arch).reduced()
    if cfg.family != "dense":
        raise ValueError(f"transformer_step_lowering needs a dense arch, "
                         f"got {arch!r} ({cfg.family})")
    model = Model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init_params(key)
    tokens = np.asarray(
        jax.random.randint(jax.random.fold_in(key, 1), (batch, seq), 0,
                           cfg.vocab_size, jnp.int32))
    positions = jnp.arange(seq)

    @jax.jit
    def _embed1(tok):
        x = model._embed_inputs(params, {"tokens": tok[None]}, positions)
        return x[0].astype(jnp.float32)

    def _make_block(layer):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])

        @jax.jit
        def _block1(x):
            y, _, _ = blocks.apply_dense_layer(
                lp, x.astype(jnp.bfloat16)[None], cfg, positions=positions,
                impl="full", cache=None, cache_index=None)
            return y[0].astype(jnp.float32)
        return _block1

    @jax.jit
    def _head1(x):
        logits = model._logits(params, x.astype(jnp.bfloat16)[None, -1:])
        return logits[0, 0].astype(jnp.float32)

    block_fns = [_make_block(layer) for layer in range(cfg.n_layers)]
    steps = [("embed", lambda _prev, r: _embed1(jnp.asarray(tokens[r])))]
    for layer, bf in enumerate(block_fns):
        steps.append((f"block{layer}",
                      lambda prev, _r, _bf=bf: _bf(jnp.asarray(prev))))
    steps.append(("head", lambda prev, _r: _head1(jnp.asarray(prev))))

    dag = chain_dag(batch, steps)
    stage_costs = {"embed": np.full(batch, 1.0), "head": np.full(batch, 2.0)}
    for layer in range(cfg.n_layers):
        stage_costs[f"block{layer}"] = np.full(batch, 4.0)

    def finalize(values):
        return np.asarray(values["head"])  # (batch, vocab_padded) f32

    return Lowered(dag, stage_costs, finalize,
                   meta={"model": model, "params": params, "tokens": tokens,
                         "cfg": cfg, "arch": arch, "seq": seq})


# ---------------------------------------------------------------------------
# (b) MoE expert dispatch: route -> experts (irregular fan-out) -> combine
# ---------------------------------------------------------------------------

def skewed_tokens(router_w: np.ndarray, n_tokens: int, skew: float = 1.2,
                  seed: int = 0) -> np.ndarray:
    """Token activations whose router logits prefer a Zipf-skewed expert.

    Each token is a noisy multiple of the router column of its target
    expert, with targets drawn from ``p_e ∝ 1/(e+1)^skew`` — the
    imbalanced token-to-expert distribution that makes expert chunk
    costs non-uniform (the irregular workload the paper's
    self-scheduling family targets).
    """
    rng = np.random.default_rng(seed)
    d, e = router_w.shape
    p = 1.0 / np.arange(1, e + 1, dtype=np.float64) ** skew
    p /= p.sum()
    targets = rng.choice(e, size=n_tokens, p=p)
    cols = router_w[:, targets].T                      # (T, d)
    norms = np.linalg.norm(cols, axis=1, keepdims=True)
    cols = cols / np.maximum(norms, 1e-6)
    x = 3.0 * cols + 0.1 * rng.standard_normal((n_tokens, d))
    return x.astype(np.float32)


def _dispatch_plan(route_out: np.ndarray, n_experts: int, capacity: int):
    """Routing plan from packed route rows ``[idx_k..., w_k...]``.

    Replicates models/moe.py's capacity semantics exactly: position
    within an expert counts over the flattened ``(T*k)`` t-major order,
    and a slot is kept iff its position is below capacity. Returns
    ``(idx (T,k) int, w (T,k) f32, pos (T,k) int, kept (E,) int)`` with
    ``pos = -1`` for dropped slots.
    """
    k = route_out.shape[1] // 2
    idx = route_out[:, :k].astype(np.int64)
    w = route_out[:, k:].astype(np.float32)
    flat = idx.reshape(-1)
    pos = np.zeros(flat.size, np.int64)
    for e in range(n_experts):
        m = flat == e
        pos[m] = np.arange(m.sum())
    keep = pos < capacity
    pos = np.where(keep, pos, -1).reshape(idx.shape)
    kept = np.bincount(flat[keep], minlength=n_experts)
    return idx, w, pos, kept


def _expert_tile(buf, wi, wo):
    """Gated expert FFN on a fixed-capacity slab (fusion-stable math).

    ``buf (C, d)``, ``wi (d, 2f)``, ``wo (f, d)``. Matrix products are
    broadcast-multiply + ``sum(axis=1)`` — not ``dot``/``einsum`` — so
    the device walker body computes the same bits as this function run
    eagerly on the host (DESIGN.md §11 discipline).
    """
    h = (buf[:, :, None] * wi[None]).sum(axis=1)        # (C, 2f)
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    return (h[:, :, None] * wo[None]).sum(axis=1)       # (C, d)


def moe_dispatch_lowering(
    arch: str = "qwen2-moe-a2.7b",
    n_tokens: int = 96,
    skew: float = 1.2,
    seed: int = 0,
    n_experts: int | None = None,
    capacity_factor: float | None = None,
) -> Lowered:
    """Lower MoE expert dispatch into an irregular fan-out pipeline.

    Stages: ``route`` (rows = tokens; per-token top-k over the router,
    packed as ``[idx..., w...]`` f32), ``experts`` (rows = experts; each
    row scatters its kept tokens into a fixed-capacity slab and runs the
    gated FFN — ``cost_of_range`` sums the router's per-expert token
    counts, so chunk costs carry the skew), ``combine`` (rows = tokens;
    weighted gather honouring capacity drops). ``meta['expert_tokens']``
    holds the kept counts; ``stage_costs['experts']`` is the matching
    per-row cost vector for the simulator/tuner.
    """
    cfg = get_config(arch).reduced()
    moe = cfg.moe
    if moe is None:
        raise ValueError(f"{arch!r} has no MoE config")
    if n_experts is not None:
        moe = dataclasses.replace(moe, n_routed=n_experts, n_routed_padded=0)
    if capacity_factor is not None:
        moe = dataclasses.replace(moe, capacity_factor=capacity_factor)
    d = cfg.d_model
    e = moe.n_routed_padded or moe.n_routed
    k = moe.top_k
    params = init_moe(jax.random.PRNGKey(seed), d, moe)
    router_w = np.asarray(params["router"], np.float32)
    x_flat = skewed_tokens(router_w, n_tokens, skew=skew, seed=seed)
    cap = max(1, int(math.ceil(k * n_tokens * moe.capacity_factor / e)))

    router_j = jnp.asarray(router_w)
    neg_inf = jnp.float32(-1e30)
    routed = moe.n_routed

    @jax.jit
    def _route1(xt):
        logits = (xt[:, None] * router_j).sum(axis=0)   # (e,) mul-reduce
        if e > routed:
            logits = jnp.where(jnp.arange(e) >= routed, neg_inf, logits)
        p = jax.nn.softmax(logits)
        w, idx = jax.lax.top_k(p, k)
        w = w / jnp.maximum(w.sum(), 1e-9)
        return jnp.concatenate([idx.astype(jnp.float32), w])

    wi = [jnp.asarray(params["experts"]["wi"][g]) for g in range(e)]
    wo = [jnp.asarray(params["experts"]["wo"][g]) for g in range(e)]

    def route_fn(_ins, r):
        return _route1(jnp.asarray(x_flat[r]))

    def expert_fn(ins, g):
        idx, _w, pos, _kept = _dispatch_plan(np.asarray(ins["route"]), e, cap)
        buf = np.zeros((cap, d), np.float32)
        t_sel, k_sel = np.nonzero((idx == g) & (pos >= 0))
        buf[pos[t_sel, k_sel]] = x_flat[t_sel]
        return _expert_tile(jnp.asarray(buf), wi[g], wo[g])

    def combine_fn(ins, t):
        idx, w, pos, _kept = _dispatch_plan(np.asarray(ins["route"]), e, cap)
        out = np.asarray(ins["experts"])                # (e, cap, d)
        y = np.zeros(d, np.float32)
        for j in range(k):
            if pos[t, j] >= 0:
                y = y + w[t, j] * out[idx[t, j], pos[t, j]]
        return y

    # routing is known at build time (the same per-token function the
    # scheduled route stage runs) — per-expert counts size the fan-out
    route_build = np.stack([np.asarray(_route1(jnp.asarray(x_flat[t])))
                            for t in range(n_tokens)])
    _, _, _, kept = _dispatch_plan(route_build, e, cap)

    route = Stage("route", n_tokens,
                  _rows_op(route_fn), combine="concat")
    experts = fanout_stage("experts", expert_fn, kept,
                           deps=(StageDep("route", DEP_FULL),))
    combine = Stage("combine", n_tokens,
                    _rows_op(combine_fn),
                    combine="concat",
                    deps=(StageDep("route", DEP_FULL),
                          StageDep("experts", DEP_FULL)))
    dag = PipelineDAG([route, experts, combine])

    stage_costs = {
        "route": np.full(n_tokens, 1.0),
        "experts": costs_from_sizes(kept, per_unit=1.0, base=1.0),
        "combine": np.full(n_tokens, 1.0),
    }

    def finalize(values):
        return np.asarray(values["combine"])            # (T, d) f32

    return Lowered(dag, stage_costs, finalize,
                   meta={"params": params, "moe": moe, "cfg": cfg,
                         "x_flat": x_flat, "capacity": cap, "n_experts": e,
                         "expert_tokens": kept, "route_build": route_build,
                         "wi": wi, "wo": wo, "d_model": d})


def _rows_op(fn):
    """Chunk op mapping ``fn(inputs, r)`` over rows (deps pass through)."""
    def op(inputs, s, z):
        return np.stack([np.asarray(fn(inputs, r)) for r in range(s, s + z)])
    return op


def _experts_body(ctx, ins, out):
    """The ``experts`` walker body: one expert's slab per slot. Module-level,
    so lowerings of one shape share one compiled walker."""
    out[...] = _expert_tile(ins["xdisp"][...], ins["wi"][...][0],
                            ins["wo"][...][0])


def moe_device_lowering(low: Lowered) -> DeviceLowering:
    """The MoE ``experts`` fan-out lowered for the fused device walker.

    One WalkStage over ``E * capacity`` rows with ``tile = capacity``:
    each slot is one expert's slab. The dispatch buffer is precomputed
    host-side from the build-time routing plan; per-expert weights are
    repeated along the row axis so ``row`` block indexing selects expert
    ``start // capacity`` (dag_walk operand blocks index by row tile).
    The body runs the SAME ``_expert_tile`` as the host op, so device
    output ``(E*C, d)`` equals the host stage value ``(E, C, d)``
    reshaped — bit-wise. ``finalize`` applies the host token-side
    combine to the device expert slabs.
    """
    from ..kernels.dag_walk import WalkOperand, WalkStage

    meta = low.meta
    e, cap, d = meta["n_experts"], meta["capacity"], meta["d_model"]
    x_flat = meta["x_flat"]
    route_build = meta["route_build"]
    idx, w, pos, _kept = _dispatch_plan(route_build, e, cap)

    xdisp = np.zeros((e * cap, d), np.float32)
    for g in range(e):
        t_sel, k_sel = np.nonzero((idx == g) & (pos >= 0))
        xdisp[g * cap + pos[t_sel, k_sel]] = x_flat[t_sel]

    wi_rep = np.repeat(np.stack([np.asarray(a) for a in meta["wi"]]),
                       cap, axis=0)                     # (E*C, d, 2f)
    wo_rep = np.repeat(np.stack([np.asarray(a) for a in meta["wo"]]),
                       cap, axis=0)                     # (E*C, f, d)
    f = wo_rep.shape[1]

    def experts_tile_op(inputs, s, z):
        rows = [np.asarray(_expert_tile(jnp.asarray(xdisp[g * cap:(g + 1) * cap]),
                                        meta["wi"][g], meta["wo"][g]))
                for g in range(s, s + z)]
        return np.stack(rows)                           # (z, cap, d)

    dag = PipelineDAG([Stage("experts", e, experts_tile_op, combine="concat")])

    stages = [WalkStage("experts", e * cap, (e * cap, d), jnp.float32,
                        "concat", _experts_body,
                        operands=("xdisp", "wi", "wo"))]
    operands = [
        WalkOperand("xdisp", (cap, d), ("row", "zero")),
        WalkOperand("wi", (cap, d, 2 * f), ("row", "zero", "zero")),
        WalkOperand("wo", (cap, f, d), ("row", "zero", "zero")),
    ]
    values = {"xdisp": jnp.asarray(xdisp), "wi": jnp.asarray(wi_rep),
              "wo": jnp.asarray(wo_rep)}

    def finalize(stage_values):
        out = np.asarray(stage_values["experts"]).reshape(e, cap, d)
        k = idx.shape[1]
        y = np.zeros((x_flat.shape[0], d), np.float32)
        for t in range(x_flat.shape[0]):
            for j in range(k):
                if pos[t, j] >= 0:
                    y[t] = y[t] + w[t, j] * out[idx[t, j], pos[t, j]]
        return y

    return DeviceLowering(dag, stages, operands, values, cap, finalize)


# ---------------------------------------------------------------------------
# (c) two-model serving pair: §14 submissions + §13 placement on real costs
# ---------------------------------------------------------------------------

def serving_pair(
    archs: tuple[str, str] = ("qwen2-0.5b", "granite-8b"),
    batch: int = 4,
    seq: int = 8,
    seed: int = 0,
    n_workers: int = 2,
    n_device: int = 1,
    device_speedup: float = 4.0,
    measured: bool = False,
):
    """Serve two models from ``configs/`` through the §14 front door.

    Builds a transformer lowering per arch, derives §13 hetero cost
    models — host costs measured from the real stage ops when
    ``measured`` (virtual otherwise), device costs scaled by
    ``device_speedup``, and a ``TransferModel`` fed the REAL activation
    byte sizes each edge moves (``seq * d_model * 4`` bytes per row;
    ``vocab * 4`` for the head) — solves placement per model, and serves
    both submissions on one ``PipelineServer`` pool. Returns
    ``(results, subs, placements, lows)`` where ``results[name]`` is the
    finalized logits, asserted bit-equal to each model's direct oracle
    by the caller (tests/bench).
    """
    from ..core.placement import HeteroCostModel, TransferModel, select_placement
    from ..core.registry import make_config
    from ..core.server import PipelineServer

    lows, subs, placements = [], [], {}
    for i, arch in enumerate(archs):
        low = transformer_step_lowering(arch, batch=batch, seq=seq,
                                        seed=seed + i)
        cfg = low.meta["cfg"]
        host = (measure_stage_costs(low.dag, sample=2) if measured
                else {k: v.astype(np.float64) for k, v in low.stage_costs.items()})
        device = {k: v / device_speedup for k, v in host.items()}
        bytes_per_row = {name: float(seq * cfg.d_model * 4)
                         for name in low.dag.stage_names}
        bytes_per_row["head"] = float(cfg.vocab_size * 4)
        costs = HeteroCostModel(host=host, device=device,
                                transfer=TransferModel(bytes_per_row=bytes_per_row))
        pl, _het_ms, _pure = select_placement(low.dag, costs, n_workers)
        placements[arch] = pl
        lows.append(low)
        subs.append(low.submission(name=arch, tenant=arch, placement=pl,
                                   stage_costs=host))

    server = PipelineServer(make_config("gss/percore", n_workers=n_workers),
                            arbiter="fair", n_device=n_device)
    served = server.serve(subs)
    results = {arch: low.value(served.jobs[arch].values)
               for arch, low in zip(archs, lows)}
    return results, subs, placements, lows
