"""Mamba2 (SSD) chunked-scan kernel.

Grid (B*H, n_chunks): the chunk dimension is sequential on a TPU core, so
the (dh, N) state lives in VMEM scratch across grid steps — a persistent-
worker pattern. Per chunk: intra-chunk quadratic form with scalar-per-head
decays + carry-in state contribution + state update. All decay exponents
are cumulative-sum differences (<= 0): numerically safe (DESIGN.md).

VMEM per step ≈ Q*dh + 2*Q*N + Q*Q + dh*N floats ≈ 0.3 MB at Q=128,
dh=64, N=64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import pallas_call


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, state_scr, *, q):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)        # (Q, dh)
    dt = dt_ref[0].astype(jnp.float32)      # (Q,)
    a = a_ref[0]                            # (1,) scalar A (negative)
    bmat = b_ref[0].astype(jnp.float32)     # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)     # (Q, N)

    da = dt * a[0]                          # (Q,) log-decay per step (<= 0)
    cum = jnp.cumsum(da)                    # inclusive
    # intra-chunk: gate[t, s] = exp(cum_t - cum_s) for s <= t
    diff = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    gate = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * gate
    y = jnp.dot(scores * dt[None, :], x, preferred_element_type=jnp.float32)
    # carry-in state: y_t += exp(cum_t) * C_t . state
    state = state_scr[...]                  # (dh, N)
    y = y + jnp.exp(cum)[:, None] * jnp.dot(cmat, state.T,
                                            preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)
    # state' = exp(cum_Q) state + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T
    w_s = jnp.exp(cum[-1] - cum) * dt       # (Q,)
    upd = jnp.dot((x * w_s[:, None]).T, bmat, preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(cum[-1]) + upd


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssm_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int = 128) -> jax.Array:
    """x: (Bt, S, H, dh); dt: (Bt, S, H); A,D: (H,); B,C: (Bt, S, N)."""
    bt, s, h, dh = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q

    # flatten (Bt, H) into the leading parallel grid dim
    xf = x.transpose(0, 2, 1, 3).reshape(bt * h, s, dh)
    dtf = dt.transpose(0, 2, 1).reshape(bt * h, s)
    af = jnp.broadcast_to(A[None, :], (bt, h)).reshape(bt * h, 1).astype(jnp.float32)
    bf = jnp.broadcast_to(B[:, None], (bt, h, s, n)).reshape(bt * h, s, n)
    cf = jnp.broadcast_to(C[:, None], (bt, h, s, n)).reshape(bt * h, s, n)

    kernel = functools.partial(_kernel, q=q)
    out = pallas_call(
        kernel,
        grid=(bt * h, nc),
        in_specs=[
            pl.BlockSpec((1, q, dh), lambda i, c_: (i, c_, 0)),
            pl.BlockSpec((1, q), lambda i, c_: (i, c_)),
            pl.BlockSpec((1, 1), lambda i, c_: (i, 0)),
            pl.BlockSpec((1, q, n), lambda i, c_: (i, c_, 0)),
            pl.BlockSpec((1, q, n), lambda i, c_: (i, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, dh), lambda i, c_: (i, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bt * h, s, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, n), jnp.float32)],
    )(xf, dtf, af, bf, cf)
    y = out.reshape(bt, h, s, dh).transpose(0, 2, 1, 3)
    return y + D[None, None, :, None] * x.astype(jnp.float32)
