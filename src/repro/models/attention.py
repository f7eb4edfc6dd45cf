"""Attention: GQA (full / chunked-flash / banded-flash / decode) and MLA.

Three training/prefill implementations, selectable per step (DESIGN.md §7,
§Perf):

  full     masked S x S softmax — smoke-test scale only
  chunked  flash-style lax.scan over (q-block, kv-block) with running
           (m, l, acc); computes all block pairs and masks — memory-optimal,
           but ~2x causal FLOPs (baseline)
  banded   scan over only the T(T+1)/2 lower-triangular block pairs —
           memory- AND FLOP-optimal causal attention (hillclimb)

Decode reads a (B, KV, S_max, dh) cache; softmax over the (possibly
seq-sharded) key axis partitions into partial max/sumexp + all-reduce under
SPMD — flash-decoding across devices for long_500k (DESIGN.md §5).

GQA never materializes expanded KV: q is reshaped to (B, KV, Hq, S, dh).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ..runtime.pspec import shard
from .layers import Params, apply_rope, dense, he_init, rms_norm

NEG_INF = -1e30


def cache_insert(cache_arr, new, index, axis):
    """Insert ``new`` (length L slice) into a cache at ``index`` along
    ``axis``. Full overwrite when shapes match; otherwise a where-mask update
    — unlike dynamic_update_slice this partitions cleanly when the cache's
    seq dim is sharded (no all-gather; measured in the first dry-run)."""
    if new.shape[axis] == cache_arr.shape[axis]:
        return new.astype(cache_arr.dtype)
    if new.shape[axis] == 1:
        pos = jax.lax.broadcasted_iota(jnp.int32, cache_arr.shape, axis)
        return jnp.where(pos == index, new.astype(cache_arr.dtype), cache_arr)
    # general slice insert: prefill writes at the cache head only
    assert index == 0 or index is None, "slice cache_insert supports index 0"
    pos = jax.lax.broadcasted_iota(jnp.int32, cache_arr.shape, axis)
    padded = jnp.zeros_like(cache_arr).at[
        tuple(slice(0, n) if a != axis else slice(0, new.shape[axis])
              for a, n in enumerate(cache_arr.shape))].set(new.astype(cache_arr.dtype))
    return jnp.where(pos < new.shape[axis], padded, cache_arr)


def pick_block(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (whisper's 1500-frame encoder
    and other non-power-of-two lengths must still tile exactly)."""
    b = min(target, s)
    while s % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
                   bias: bool = False, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": he_init(ks[0], (d_model, n_heads * d_head), d_model, dtype),
        "wk": he_init(ks[1], (d_model, n_kv * d_head), d_model, dtype),
        "wv": he_init(ks[2], (d_model, n_kv * d_head), d_model, dtype),
        "wo": he_init(ks[3], (n_heads * d_head, d_model), n_heads * d_head, dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), dtype)
        p["bk"] = jnp.zeros((n_kv * d_head,), dtype)
        p["bv"] = jnp.zeros((n_kv * d_head,), dtype)
    return p


def _split_heads(x, n, d):  # (B,S,n*d) -> (B,n,S,d)
    b, s, _ = x.shape
    return x.reshape(b, s, n, d).transpose(0, 2, 1, 3)


def _merge_heads(x):  # (B,n,S,d) -> (B,S,n*d)
    b, n, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, n * d)


def qkv_project(params: Params, x: jax.Array, n_heads: int, n_kv: int, d_head: int,
                positions: jax.Array | None, rope_theta: float):
    q = dense(x, params["wq"], params.get("bq"))
    k = dense(x, params["wk"], params.get("bk"))
    v = dense(x, params["wv"], params.get("bv"))
    q = _split_heads(q, n_heads, d_head)
    k = _split_heads(k, n_kv, d_head)
    v = _split_heads(v, n_kv, d_head)
    if positions is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    q = shard(q, "batch", "heads", None, None)
    k = shard(k, "batch", "kv_heads", None, None)
    v = shard(v, "batch", "kv_heads", None, None)
    return q, k, v


# ---------------------------------------------------------------------------
# core attention variants (q: (B,H,Sq,dh); k,v: (B,KV,Skv,dh))
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """(B,KV,G,Sq,Skv) scores without expanding KV."""
    b, h, sq, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, sq, dh)
    return jnp.einsum("bkgqd,bkvd->bkgqv", qg, k) / math.sqrt(dh)


def full_attention(q, k, v, causal: bool = True, kv_offset: int = 0):
    b, h, sq, dh = q.shape
    kv_heads, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    s = _gqa_scores(q, k).astype(jnp.float32)
    if causal:
        qi = jnp.arange(sq)[:, None] + kv_offset
        kj = jnp.arange(skv)[None, :]
        s = jnp.where(qi >= kj, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqv,bkvd->bkgqd", w, v)
    return o.reshape(b, h, sq, dv)


def chunked_attention(q, k, v, causal: bool = True, q_block: int = 512,
                      kv_block: int = 1024, kv_offset: int = 0):
    """Flash-style two-level scan; computes every (qb, kb) pair, masks."""
    b, h, sq, dh = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kvh
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    nq, nk = sq // q_block, skv // kv_block
    assert sq % q_block == 0 and skv % kv_block == 0, (sq, q_block, skv, kv_block)
    qg = q.reshape(b, kvh, g, nq, q_block, dh)
    kb = k.reshape(b, kvh, nk, kv_block, dh)
    vb = v.reshape(b, kvh, nk, kv_block, dv)
    scale = 1.0 / math.sqrt(dh)

    def q_step(_, qi):
        qblk = jax.lax.dynamic_index_in_dim(qg, qi, axis=3, keepdims=False)

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk = jax.lax.dynamic_index_in_dim(kb, ki, axis=2, keepdims=False)
            vblk = jax.lax.dynamic_index_in_dim(vb, ki, axis=2, keepdims=False)
            s = jnp.einsum("bkgqd,bkvd->bkgqv", qblk, kblk).astype(jnp.float32) * scale
            if causal:
                qpos = qi * q_block + jnp.arange(q_block)[:, None] + kv_offset
                kpos = ki * kv_block + jnp.arange(kv_block)[None, :]
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqv,bkvd->bkgqd", p.astype(q.dtype), vblk).astype(jnp.float32)
            return (m_new, l_new, acc_new), ()

        init = (
            jnp.full((b, kvh, g, q_block), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, q_block), jnp.float32),
            jnp.zeros((b, kvh, g, q_block, dv), jnp.float32),
        )
        # remat: backward recomputes the block scores (flash backward);
        # without this the scan saves every (qb,kb) probability block.
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), init, jnp.arange(nk))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, o.astype(q.dtype)

    _, ob = jax.lax.scan(jax.checkpoint(q_step), None, jnp.arange(nq))
    # ob: (nq, b, kvh, g, q_block, dv)
    o = jnp.moveaxis(ob, 0, 3).reshape(b, kvh, g, sq, dv)
    return o.reshape(b, h, sq, dv)


def banded_attention(q, k, v, q_block: int = 512, kv_block: int | None = None,
                     kv_offset: int = 0):
    """Causal flash over ONLY the lower-triangular block pairs.

    One scan over T(T+1)/2 (qi, ki) pairs (kv_block == q_block), carrying the
    full per-q-block (m, l, acc) state; ~0.5x the FLOPs of `chunked` on
    causal workloads (the §Perf iteration for compute-bound cells).
    """
    b, h, sq, dh = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    assert sq == skv and kv_offset == 0, "banded path is for self-attention prefill"
    g = h // kvh
    blk = min(q_block, sq)
    nt = sq // blk
    assert sq % blk == 0
    qg = q.reshape(b, kvh, g, nt, blk, dh)
    kb = k.reshape(b, kvh, nt, blk, dh)
    vb = v.reshape(b, kvh, nt, blk, dv)
    scale = 1.0 / math.sqrt(dh)

    pairs = [(qi, ki) for qi in range(nt) for ki in range(qi + 1)]
    qi_arr = jnp.array([p[0] for p in pairs], jnp.int32)
    ki_arr = jnp.array([p[1] for p in pairs], jnp.int32)

    def step(carry, pair):
        m, l, acc = carry  # (b,kvh,g,nt,blk[,dh])
        qi, ki = pair
        qblk = jax.lax.dynamic_index_in_dim(qg, qi, axis=3, keepdims=False)
        kblk = jax.lax.dynamic_index_in_dim(kb, ki, axis=2, keepdims=False)
        vblk = jax.lax.dynamic_index_in_dim(vb, ki, axis=2, keepdims=False)
        s = jnp.einsum("bkgqd,bkvd->bkgqv", qblk, kblk).astype(jnp.float32) * scale
        qpos = qi * blk + jnp.arange(blk)[:, None]
        kpos = ki * blk + jnp.arange(blk)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_q = jax.lax.dynamic_index_in_dim(m, qi, axis=3, keepdims=False)
        l_q = jax.lax.dynamic_index_in_dim(l, qi, axis=3, keepdims=False)
        a_q = jax.lax.dynamic_index_in_dim(acc, qi, axis=3, keepdims=False)
        m_new = jnp.maximum(m_q, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_q - m_new)
        l_new = l_q * corr + p.sum(-1)
        a_new = a_q * corr[..., None] + jnp.einsum(
            "bkgqv,bkvd->bkgqd", p.astype(q.dtype), vblk).astype(jnp.float32)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, axis=3)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, axis=3)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, axis=3)
        return (m, l, acc), ()

    init = (
        jnp.full((b, kvh, g, nt, blk), NEG_INF, jnp.float32),
        jnp.zeros((b, kvh, g, nt, blk), jnp.float32),
        jnp.zeros((b, kvh, g, nt, blk, dv), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), init, (qi_arr, ki_arr))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = o.reshape(b, kvh, g, sq, dv)
    return o.reshape(b, h, sq, dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len):
    """q: (B,H,1,dh); caches: (B,KV,S_max,dh); cache_len: int32 scalar =
    number of valid cache entries INCLUDING the current token."""
    b, h, _, dh = q.shape
    kvh, smax = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh)
    s = jnp.einsum("bkgd,bkvd->bkgv", qg, k_cache).astype(jnp.float32)
    s = s / math.sqrt(dh)
    mask = jnp.arange(smax)[None, None, None, :] < cache_len
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgv,bkvd->bkgd", w.astype(q.dtype), v_cache)
    return o.reshape(b, h, 1, dv)


def attention_fn(impl: str):
    return {"full": full_attention, "chunked": chunked_attention,
            "banded": banded_attention}[impl]


def _seq_sharded_attention(q, k, v, cfg, rules):
    """shard_map causal attention with q's sequence dim over 'model' (§Perf).

    Per shard: a q slice (S/n_model) against the full K/V with
    kv_offset = shard * S_loc; attention FLOPs divide by the axis size
    instead of being replicated (the baseline behaviour for archs whose
    head count doesn't divide the model axis; DESIGN.md §5)."""
    from jax.sharding import PartitionSpec as P
    mesh = rules.mesh
    n_model = mesh.shape["model"]
    b_axes = rules.resolve("batch")
    s = q.shape[2]
    s_loc = s // n_model
    qb = pick_block(s_loc, cfg.attn_chunk_q)
    kb = pick_block(k.shape[2], cfg.attn_chunk_kv)

    def body(q_loc, k_full, v_full):
        off = jax.lax.axis_index("model") * s_loc
        return chunked_attention(q_loc, k_full, v_full, causal=True,
                                 q_block=qb, kv_block=kb, kv_offset=off)

    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(b_axes, None, "model", None),
                  P(b_axes, None, None, None), P(b_axes, None, None, None)),
        out_specs=P(b_axes, None, "model", None),
    )(q, k, v)


# ---------------------------------------------------------------------------
# GQA block-level API (with KV cache plumbing)
# ---------------------------------------------------------------------------

def gqa_attention(params: Params, x: jax.Array, cfg: Any, *,
                  positions: jax.Array, impl: str = "chunked",
                  cache: Params | None = None, cache_index=None,
                  cross_kv: tuple | None = None, causal: bool = True):
    """Returns (y, new_cache). ``cache`` is {'k','v'} of (B,KV,S_max,dh)."""
    n_heads, n_kv, d_head = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rope_theta = getattr(cfg, "rope_theta", None)
    use_rope = rope_theta is not None and cross_kv is None

    if cross_kv is not None:
        q = _split_heads(dense(x, params["wq"], params.get("bq")), n_heads, d_head)
        k, v = cross_kv
        k, v = k.astype(x.dtype), v.astype(x.dtype)
        o = full_attention(q, k, v, causal=False) if impl == "full" else \
            chunked_attention(q, k, v, causal=False,
                              q_block=pick_block(q.shape[2], cfg.attn_chunk_q),
                              kv_block=pick_block(k.shape[2], cfg.attn_chunk_kv))
        y = dense(_merge_heads(o), params["wo"])
        return shard(y, "batch", None, "embed"), cache

    q, k, v = qkv_project(params, x, n_heads, n_kv, d_head,
                          positions if use_rope else None, rope_theta or 1e4)

    # §Perf: sequence-sharded attention when heads don't divide the model
    # axis (else attention compute is replicated over 'model').
    from ..runtime.pspec import current_rules
    _rules = current_rules()
    _seq_axis = _rules.resolve("seq") if _rules is not None else None
    if (_seq_axis is not None and _rules.resolve("heads") is None
            and q.shape[2] > 1 and causal
            and q.shape[2] % _rules.mesh.shape["model"] == 0):
        o = _seq_sharded_attention(q, k, v, cfg, _rules)
        new_cache = None
        if cache is not None:
            new_cache = {"k": cache_insert(cache["k"], k, 0, axis=2),
                         "v": cache_insert(cache["v"], v, 0, axis=2)}
        y = dense(_merge_heads(o), params["wo"])
        return shard(y, "batch", None, "embed"), new_cache

    if cache is not None and cache_index is not None and q.shape[2] == 1:
        # decode: insert new k,v at cache_index, attend over the cache
        k_cache = cache_insert(cache["k"], k, cache_index, axis=2)
        v_cache = cache_insert(cache["v"], v, cache_index, axis=2)
        o = decode_attention(q, k_cache, v_cache, cache_index + 1).astype(x.dtype)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        fn = attention_fn(impl)
        if impl == "chunked":
            o = fn(q, k, v, causal=causal,
                   q_block=pick_block(q.shape[2], cfg.attn_chunk_q),
                   kv_block=pick_block(k.shape[2], cfg.attn_chunk_kv))
        elif impl == "banded":
            o = fn(q, k, v, q_block=pick_block(q.shape[2], cfg.attn_chunk_q))
        else:
            o = fn(q, k, v, causal=causal)
        if cache is not None:
            k_cache = cache_insert(cache["k"], k, 0, axis=2)
            v_cache = cache_insert(cache["v"], v, 0, axis=2)
            new_cache = {"k": k_cache, "v": v_cache}
        else:
            new_cache = None
    y = dense(_merge_heads(o), params["wo"])
    return shard(y, "batch", None, "embed"), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, d_model: int, n_heads: int, mla, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    qk_head = mla.nope_head_dim + mla.rope_head_dim
    return {
        "wq": he_init(ks[0], (d_model, n_heads * qk_head), d_model, dtype),
        "wkv_a": he_init(ks[1], (d_model, mla.kv_lora_rank + mla.rope_head_dim), d_model, dtype),
        "kv_norm": jnp.ones((mla.kv_lora_rank,), dtype),
        "wkv_b": he_init(ks[2], (mla.kv_lora_rank,
                                 n_heads * (mla.nope_head_dim + mla.v_head_dim)),
                         mla.kv_lora_rank, dtype),
        "wo": he_init(ks[3], (n_heads * mla.v_head_dim, d_model), n_heads * mla.v_head_dim, dtype),
    }


def mla_attention(params: Params, x: jax.Array, cfg: Any, *, positions,
                  impl: str = "chunked", cache: Params | None = None,
                  cache_index=None):
    """MLA with compressed-KV cache {'ckv': (B,S,r), 'kpe': (B,1,S,dr)}.

    Prefill/train reconstructs K,V from the latent; decode uses the absorbed
    formulation (scores in latent space) so per-step work is O(S * (r + dr))
    per head — the paper's (DeepSeek's) KV-cache saving is structural.
    """
    mla, H = cfg.mla, cfg.n_heads
    dn, dr, dv, r = mla.nope_head_dim, mla.rope_head_dim, mla.v_head_dim, mla.kv_lora_rank
    b, sq, _ = x.shape

    q = dense(x, params["wq"])  # (B,S,H*(dn+dr))
    q = q.reshape(b, sq, H, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    kv_a = dense(x, params["wkv_a"])  # (B,S,r+dr)
    ckv = rms_norm(kv_a[..., :r], params["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv_a[..., None, :, r:], positions, cfg.rope_theta)  # (B,1,S,dr)

    wkv_b = params["wkv_b"].reshape(r, H, dn + dv).astype(x.dtype)

    if cache is not None and cache_index is not None and sq == 1:
        ckv_c = cache_insert(cache["ckv"], ckv, cache_index, axis=1)
        kpe_c = cache_insert(cache["kpe"], k_pe, cache_index, axis=2)
        # absorbed: q_lat[h] = W_uk[h]^T q_nope[h]  -> scores vs latent cache
        w_uk = wkv_b[..., :dn]                          # (r,H,dn)
        q_lat = jnp.einsum("bhqd,rhd->bhqr", q_nope, w_uk)  # (B,H,1,r)
        s_lat = jnp.einsum("bhqr,bsr->bhqs", q_lat, ckv_c)
        s_pe = jnp.einsum("bhqd,bzsd->bhqs", q_pe, kpe_c)
        s = (s_lat + s_pe).astype(jnp.float32) / math.sqrt(dn + dr)
        smax = ckv_c.shape[1]
        mask = jnp.arange(smax)[None, None, None, :] < (cache_index + 1)
        s = jnp.where(mask, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        ctx_lat = jnp.einsum("bhqs,bsr->bhqr", w, ckv_c)     # (B,H,1,r)
        w_uv = wkv_b[..., dn:]                               # (r,H,dv)
        o = jnp.einsum("bhqr,rhd->bhqd", ctx_lat, w_uv).astype(x.dtype)
        new_cache = {"ckv": ckv_c, "kpe": kpe_c}
    else:
        kv = jnp.einsum("bsr,rhd->bhsd", ckv, wkv_b)         # (B,H,S,dn+dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, H, sq, dr))], axis=-1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        qf = shard(qf, "batch", "heads", None, None)
        k = shard(k, "batch", "heads", None, None)
        v = shard(v, "batch", "heads", None, None)
        if impl == "full":
            o = full_attention(qf, k, v, causal=True)
        elif impl == "banded":
            o = banded_attention(qf, k, v, q_block=cfg.attn_chunk_q)
        else:
            o = chunked_attention(qf, k, v, causal=True,
                                  q_block=cfg.attn_chunk_q, kv_block=cfg.attn_chunk_kv)
        if cache is not None:
            ckv_c = cache_insert(cache["ckv"], ckv, 0, axis=1)
            kpe_c = cache_insert(cache["kpe"], k_pe, 0, axis=2)
            new_cache = {"ckv": ckv_c, "kpe": kpe_c}
        else:
            new_cache = None

    y = dense(_merge_heads(o), params["wo"])
    return shard(y, "batch", None, "embed"), new_cache
