"""Capacity-based MoE with shared + routed experts (DeepSeek/Qwen style).

Dispatch is scatter/gather (NOT the GShard (T,E,C) einsum — that dispatch
tensor is quadratic in tokens and would wreck both memory and the useful-
FLOPs ratio; DESIGN.md §5):

  1. router top-k over (padded) experts; padding experts masked to -inf
  2. position-in-expert via cumsum over one-hot; tokens beyond capacity drop
  3. scatter tokens into an (E_loc, C, d) buffer (single scatter-add with a
     trash row), batched expert FFN, gather back weighted.

Expert parallelism: routed experts are sharded over the mesh 'model' axis.
When sharding rules are active the block runs under shard_map: tokens stay
on their data shard, each model shard computes its local experts, outputs
psum over 'model'. The scheduler connection (DESIGN.md §6.4): capacity is a
work-assignment knob; `capacity_factor` is the STATIC baseline and the
load-model hook scales it from measured expert loads (PLS-style).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..runtime.pspec import current_rules, shard
from .layers import Params, dense, he_init, mlp, init_mlp

NEG_INF = -1e30


def init_moe(key, d_model: int, moe, dtype=jnp.float32) -> Params:
    e = moe.n_routed_padded or moe.n_routed
    ks = jax.random.split(key, 4)
    p = {
        "router": he_init(ks[0], (d_model, e), d_model, dtype),
        "experts": {
            "wi": he_init(ks[1], (e, d_model, 2 * moe.d_ff_expert), d_model, dtype),
            "wo": he_init(ks[2], (e, moe.d_ff_expert, d_model), moe.d_ff_expert, dtype),
        },
    }
    if moe.n_shared:
        p["shared"] = init_mlp(ks[3], d_model, moe.n_shared * moe.d_ff_expert,
                               gated=True, dtype=dtype)
    return p


def _route(router_w, x_flat, moe):
    """Returns (expert_idx (T,k), weights (T,k), probs (T,E)) fp32."""
    logits = (x_flat @ router_w.astype(x_flat.dtype)).astype(jnp.float32)
    e_pad = logits.shape[-1]
    if e_pad > moe.n_routed:  # mask padding experts (router never routes there)
        pad_mask = jnp.arange(e_pad) >= moe.n_routed
        logits = jnp.where(pad_mask[None, :], NEG_INF, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, moe.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)  # renormalize top-k
    return idx, w, probs


def _dispatch_compute_combine(params, x_flat, idx, w, capacity, moe):
    """Local (per model shard) scatter -> expert FFN -> weighted gather.

    x_flat: (T, d); idx/w: (T, k) GLOBAL expert ids + weights;
    params['experts'] holds this shard's E_loc experts covering global ids
    [e_lo, e_lo + E_loc). Returns (T, d) partial output (sum over shards
    gives the full combine).
    """
    e_loc = params["experts"]["wi"].shape[0]
    e_lo = params.get("_e_lo", 0)
    t, d = x_flat.shape
    k = idx.shape[1]
    c = capacity

    local = (idx >= e_lo) & (idx < e_lo + e_loc)            # (T,k)
    lidx = jnp.where(local, idx - e_lo, e_loc)              # e_loc = trash expert
    # position of each (t, slot) within its expert, counted over flattened (T*k)
    onehot = jax.nn.one_hot(lidx.reshape(-1), e_loc + 1, dtype=jnp.int32)  # (T*k, E+1)
    pos = jnp.cumsum(onehot, axis=0) - 1                    # running count per expert
    pos = jnp.take_along_axis(pos, lidx.reshape(-1, 1), axis=1)[:, 0]      # (T*k,)
    keep = local.reshape(-1) & (pos < c)
    slot = jnp.where(keep, lidx.reshape(-1) * c + pos, e_loc * c)          # trash slot

    buf = jnp.zeros((e_loc * c + 1, d), x_flat.dtype)
    src = jnp.repeat(x_flat, k, axis=0)                     # (T*k, d)
    buf = buf.at[slot].add(src * keep[:, None].astype(x_flat.dtype))
    eb = buf[:-1].reshape(e_loc, c, d)

    wi = params["experts"]["wi"].astype(x_flat.dtype)       # (E,d,2f)
    wo = params["experts"]["wo"].astype(x_flat.dtype)       # (E,f,d)
    h = jnp.einsum("ecd,edf->ecf", eb, wi)
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, wo)                 # (E,C,d)

    out_flat = jnp.concatenate([out.reshape(e_loc * c, d),
                                jnp.zeros((1, d), x_flat.dtype)], axis=0)
    gathered = out_flat[slot]                               # (T*k, d)
    wk = (w.reshape(-1, 1).astype(x_flat.dtype) * keep[:, None].astype(x_flat.dtype))
    y = (gathered * wk).reshape(t, k, d).sum(axis=1)
    return y


def aux_load_balance_loss(probs, idx, moe) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e over routed experts."""
    e = moe.n_routed
    counts = jnp.zeros((probs.shape[0], e), probs.dtype)
    hits = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype).sum(1)[:, :e]
    f = hits.mean(0) / moe.top_k
    p = probs[:, :e].mean(0)
    return e * jnp.sum(f * p)


def moe_block(params: Params, x: jax.Array, cfg: Any) -> tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y, aux_loss). Runs under shard_map when a mesh is active."""
    moe = cfg.moe
    b, s, d = x.shape
    rules = current_rules()

    def local_fn(p, xl):
        """Per-(data,model)-shard body; xl: (B_loc, S, d)."""
        bl = xl.shape[0]
        x_flat = xl.reshape(bl * s, d)
        idx, w, probs = _route(p["router"], x_flat, moe)
        e_for_cap = moe.n_routed_padded or moe.n_routed
        cap = max(1, int(math.ceil(moe.top_k * bl * s * moe.capacity_factor / e_for_cap)))
        y = _dispatch_compute_combine(p, x_flat, idx, w, cap, moe)
        aux = aux_load_balance_loss(probs, idx, moe)
        return y.reshape(bl, s, d), aux

    if rules is not None and rules.mesh is not None:
        mesh = rules.mesh
        n_model = mesh.shape["model"]
        e_pad = moe.n_routed_padded or moe.n_routed
        assert e_pad % n_model == 0, (e_pad, n_model)
        batch_axes = rules.resolve("batch")
        from jax.sharding import PartitionSpec as P

        param_specs = {
            "router": P(),
            "experts": {"wi": P("model", None, None), "wo": P("model", None, None)},
        }
        def body(p, xl):
            # recover this shard's expert offset from axis index
            e_loc = p["experts"]["wi"].shape[0]
            ax = jax.lax.axis_index("model")
            p = dict(p, _e_lo=ax * e_loc)
            y, aux = local_fn(p, xl)
            y = jax.lax.psum(y, "model")
            aux = jax.lax.pmean(aux, tuple(mesh.axis_names))  # replicate fully
            return y, aux

        routed_params = {"router": params["router"], "experts": params["experts"]}
        y, aux = jax.shard_map(
            body, mesh=mesh, check_vma=False,
            in_specs=(param_specs, P(batch_axes, None, None)),
            out_specs=(P(batch_axes, None, None), P()),
        )(routed_params, x)
    else:
        y, aux = local_fn({**params, "_e_lo": 0}, x)

    if "shared" in params:
        y = y + mlp(params["shared"], x, gated=True)
    return shard(y, "batch", None, "embed"), aux * moe.router_aux_weight
