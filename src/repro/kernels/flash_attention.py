"""Flash-attention forward kernel (TPU serving fast path).

Tiled online-softmax attention: grid (B*H, n_q_tiles, n_kv_tiles), running
(m, l, acc) in VMEM scratch persisted across the sequential kv dimension.
Causal masking by absolute position.

BlockSpec tiling: q (1, TILE_Q, dh), k/v (1, TILE_K, dh) — dh is kept whole
(<= 128 for every assigned arch), so VMEM per step ≈ TILE_Q*dh + 2*TILE_K*dh
+ TILE_Q*TILE_K floats ≈ 1.3 MB at the 256/512 defaults.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import pallas_call

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal,
            tile_q, tile_k, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (TILE_Q, dh)
    k = k_ref[0]                       # (TILE_K, dh)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * tile_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ki * tile_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "tile_q", "tile_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, tile_q: int = 256, tile_k: int = 512) -> jax.Array:
    """q,k,v: (B, H, S, dh) (same H — GQA is expanded by ops.py)."""
    b, h, s, dh = q.shape
    sk = k.shape[2]
    tile_q = min(tile_q, s)
    tile_k = min(tile_k, sk)
    assert s % tile_q == 0 and sk % tile_k == 0
    qf = q.reshape(b * h, s, dh)
    kf = k.reshape(b * h, sk, dh)
    vf = v.reshape(b * h, sk, dh)
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(_kernel, causal=causal, tile_q=tile_q,
                               tile_k=tile_k, scale=scale)
    out = pallas_call(
        kernel,
        grid=(b * h, s // tile_q, sk // tile_k),
        in_specs=[
            pl.BlockSpec((1, tile_q, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, tile_k, dh), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, tile_k, dh), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, dh), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q,), jnp.float32),
            pltpu.VMEM((tile_q,), jnp.float32),
            pltpu.VMEM((tile_q, dh), jnp.float32),
        ],
    )(qf, kf, vf)
    return out.reshape(b, h, s, dh)
