"""RWKV6 chunked WKV kernel (data-dependent per-channel decay).

Grid (B*H, n_chunks); (dh, dh) state in VMEM scratch across the sequential
chunk dimension. Uses the FACTORED fast form

    A[t,s] = (r_t * exp(cum_{t-1} - cum_s_ref)) . (k_s * exp(cum_s_ref - cum_s))

with the chunk-local reference point cum_s_ref = cum at chunk end, keeping
every exponent <= 0 (no overflow; the jnp model path materializes the exact
per-channel (Q,Q,dh) tensor instead — this kernel is the TPU-fast variant,
validated against ref.py in interpret mode).

VMEM per step ≈ 4*Q*dh (r,k,v,decay) + Q*Q + dh*dh floats ≈ 0.2 MB at
Q=64, dh=64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import pallas_call


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scr, *, q):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)    # (Q, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # (Q, dh), <= 0
    u = u_ref[0].astype(jnp.float32)    # (1? dh) bonus row

    cum = jnp.cumsum(lw, axis=0)        # inclusive, decreasing
    cum_tm1 = cum - lw                  # exclusive (cum_{t-1}; row0 = 0)
    end = cum[-1]                       # (dh,) chunk-end reference (most negative)

    # intra-chunk attention, EXACT per-channel form. The factored
    # q'=r*exp(cum), k'=k*exp(-cum) version feeds the MXU but exp(-cum_s)
    # overflows under fast decay; the pairwise difference is always <= 0.
    # (Q,Q,dh) = 1 MB VMEM at the 64/64 defaults. MXU-friendly sub-tile
    # recentering is a documented future optimization (DESIGN.md).
    diff = cum_tm1[:, None, :] - cum[None, :, :]          # (Q,Q,dh), <= 0 for s<t
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >
           jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    gate = jnp.where(tri[..., None], jnp.exp(diff), 0.0)  # (Q,Q,dh)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * gate, axis=-1)  # (Q,Q)
    y = jnp.dot(a, v, preferred_element_type=jnp.float32)
    # diagonal bonus
    diag = jnp.sum(r * u * k, axis=1)   # (Q,)
    y = y + diag[:, None] * v
    # carry-in state
    state = state_scr[...]              # (dh, dh)
    y = y + jnp.dot(r * jnp.exp(cum_tm1), state,
                    preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)
    # state' = diag(exp(end)) state + sum_s exp(end - cum_s) k_s v_s^T
    kw = k * jnp.exp(end[None, :] - cum)
    state_scr[...] = state * jnp.exp(end)[:, None] + jnp.dot(
        kw.T, v, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
               u: jax.Array, chunk: int = 64) -> jax.Array:
    """r,k,v,logw: (Bt, H, S, dh); u: (H, dh). Returns (Bt, H, S, dh) fp32."""
    bt, h, s, dh = r.shape
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    rf = r.reshape(bt * h, s, dh)
    kf = k.reshape(bt * h, s, dh)
    vf = v.reshape(bt * h, s, dh)
    lwf = logw.reshape(bt * h, s, dh)
    uf = jnp.broadcast_to(u[None], (bt, h, dh)).reshape(bt * h, 1, dh)

    kernel = functools.partial(_kernel, q=q)
    out = pallas_call(
        kernel,
        grid=(bt * h, nc),
        in_specs=[
            pl.BlockSpec((1, q, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, q, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, q, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, q, dh), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, dh), lambda i, c: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, dh), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bt * h, s, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
    )(rf, kf, vf, lwf, uf)
    return out.reshape(bt, h, s, dh)
