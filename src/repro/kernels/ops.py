"""Public wrappers around the Pallas kernels.

Each kernel compiles through Mosaic on a TPU and runs in Pallas's TPU
interpret mode on any other backend (kernels/mode.py); the wrappers take
no mode argument.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.device_schedule import build_task_table
from .cc_propagate import cc_propagate
from .flash_attention import flash_attention
from .rwkv6_scan import rwkv6_scan
from .ssm_scan import ssm_scan

__all__ = ["cc_step", "attention", "mamba2_chunk_scan", "wkv6", "dls_tile_schedule"]


def dls_tile_schedule(technique: str, n_rows: int, tile_r: int,
                      n_workers: int = 8, seed: int = 0,
                      assignment: str = "roundrobin") -> np.ndarray:
    """Row-tile execution order from a DLS technique (DESIGN.md §3).

    Chunk sizes are quantized to tile multiples; the returned permutation of
    row-tile indices is the kernel's scalar-prefetch task table.
    """
    n_tiles = n_rows // tile_r
    table = build_task_table(technique, n_tiles, n_workers, seed=seed)
    order: list[int] = []
    for start, size in table:
        order.extend(range(int(start), int(start + size)))
    out = np.array(order, dtype=np.int32)
    assert len(out) == n_tiles and len(np.unique(out)) == n_tiles
    return out


def cc_step(G, c, technique: str = "MFSC", n_workers: int = 8,
            tile_r: int = 256, tile_c: int = 1024):
    """One scheduler-driven CC propagation step (paper Listing 1 kernel)."""
    schedule = jnp.asarray(dls_tile_schedule(technique, G.shape[0], tile_r,
                                             n_workers))
    return cc_propagate(G, c, schedule, tile_r=tile_r, tile_c=tile_c)


def attention(q, k, v, causal: bool = True, tile_q: int = 256,
              tile_k: int = 512):
    """GQA-aware wrapper: expands KV heads then calls the flash kernel."""
    b, h, s, dh = q.shape
    kv = k.shape[1]
    if kv != h:
        g = h // kv
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    return flash_attention(q, k, v, causal=causal, tile_q=tile_q,
                           tile_k=tile_k)


def mamba2_chunk_scan(x, dt, A, B, C, D, chunk: int = 128):
    return ssm_scan(x, dt, A, B, C, D, chunk=chunk)


def wkv6(r, k, v, logw, u, chunk: int = 64):
    return rwkv6_scan(r, k, v, logw, u, chunk=chunk)
