"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cc_propagate_ref(G: jax.Array, c: jax.Array) -> jax.Array:
    """u[i] = max(max_{j: G[i,j] != 0} c[j], c[i]).  G: (n, n) dense {0,1}."""
    neigh = jnp.where(G > 0, c[None, :], 0)
    return jnp.maximum(neigh.max(axis=1), c)


def connected_components_ref(G: jax.Array, c: jax.Array) -> tuple[jax.Array, int]:
    """Listing 1 to its fixed point: ``cc_propagate_ref`` until no label changes.

    Returns the labels and the number of rounds, counting the last one,
    which changes nothing.
    """
    rounds = 0
    while True:
        u = cc_propagate_ref(G, c)
        rounds += 1
        if bool(jnp.array_equal(u, c)):
            return u, rounds
        c = u


def flash_attention_ref(q, k, v, causal: bool = True):
    """q,k,v: (B, H, S, dh) (MHA; GQA expansion happens in ops)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w.astype(q.dtype), v)


def ssm_scan_ref(x, dt, A, B, C, D, chunk: int = 16):
    """Sequential Mamba2 (SSD) recurrence oracle.

    x: (Bt, S, H, dh); dt: (Bt, S, H); A: (H,) (negative); B,C: (Bt, S, N).
    Returns (Bt, S, H, dh).  State: (Bt, H, dh, N).
    """
    bt, s, h, dh = x.shape
    n = B.shape[-1]

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        dA = jnp.exp(dt_t * A[None, :])                       # (Bt,H)
        upd = (dt_t[..., None, None] * x_t[..., :, None]) * B_t[:, None, None, :]
        state = state * dA[..., None, None] + upd
        y = jnp.einsum("bhdn,bn->bhd", state, C_t)
        return state, y

    state0 = jnp.zeros((bt, h, dh, n), jnp.float32)
    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32),
          B.transpose(1, 0, 2).astype(jnp.float32),
          C.transpose(1, 0, 2).astype(jnp.float32))
    _, ys = jax.lax.scan(step, state0, xs)
    y = ys.transpose(1, 0, 2, 3)
    return y + D[None, None, :, None] * x.astype(jnp.float32)


def rwkv6_scan_ref(r, k, v, logw, u):
    """Sequential RWKV6 recurrence oracle.

    r,k,v: (Bt, H, S, dh); logw: (Bt, H, S, dh) (<=0); u: (H, dh).
    y_t = r_t (S_{t-1} + diag(u) k_t v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    bt, h, s, dh = r.shape

    def step(state, inp):
        r_t, k_t, v_t, lw_t = inp  # (Bt,H,dh)
        y = jnp.einsum("bhc,bhcd->bhd", r_t, state) \
            + jnp.einsum("bhc,bhc,bhd->bhd", r_t * u[None], k_t, v_t)
        state = state * jnp.exp(lw_t)[..., None] + k_t[..., :, None] * v_t[..., None, :]
        return state, y

    state0 = jnp.zeros((bt, h, dh, dh), jnp.float32)
    xs = tuple(a.transpose(2, 0, 1, 3).astype(jnp.float32) for a in (r, k, v, logw))
    _, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 2, 0, 3)
