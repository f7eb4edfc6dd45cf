"""Plain reference of the connected-components pipeline (the paper's Listing 1).

The graph is Graph500's Kronecker graph (the benchmark specification's
Kernel 1 input): initiator A = 0.57, B = 0.19, C = 0.19, D = 0.05;
``edge_factor * 2**scale`` edges, each vertex id's bits drawn one level at
a time; ids randomly permuted; edges made undirected; self-loops dropped.
``kronecker`` is a copy of the program's generator (the same numpy calls
in the same order), so one seed gives one graph on both sides. Nothing
here imports the program.

The answer is each vertex's label at Listing 1's fixed point: the largest
1-based id in its component. ``components`` finds it by union-find over
the edge list, in int64, with no propagation at all; ``rounds`` counts
Listing 1's rounds to the fixed point by numpy max-propagation, the last
round (which changes nothing) included. ``control`` is that propagation
with the labels stored in bfloat16, one precision step below the float32
the configuration states: bfloat16 holds every integer only up to 256, so
larger ids collide.
"""

from __future__ import annotations

import numpy as np

INITIATOR = (0.57, 0.19, 0.19)  # A, B, C; D = 1 - A - B - C = 0.05


def kronecker(scale: int, edge_factor: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The seeded graph's directed edge list ``(src, dst)``, int64, both directions."""
    a, b, c = INITIATOR
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        r = rng.uniform(size=m)
        src_bit = (r >= a + b).astype(np.int64)
        r2 = rng.uniform(size=m)
        thr_dst = np.where(src_bit == 0, a / (a + b), c / (1.0 - a - b))
        dst_bit = (r2 >= thr_dst).astype(np.int64)
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    return src[keep], dst[keep]


def components(n: int, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Each vertex's largest 1-based id in its component, int64, by union-find."""
    parent = list(range(n))

    def find(v: int) -> int:
        while parent[v] != v:
            parent[v] = parent[parent[v]]
            v = parent[v]
        return v

    pairs = np.unique(np.minimum(src, dst) * n + np.maximum(src, dst))
    for u, v in zip((pairs // n).tolist(), (pairs % n).tolist()):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[ru] = rv
    roots = np.array([find(v) for v in range(n)], dtype=np.int64)
    best = np.zeros(n, dtype=np.int64)
    np.maximum.at(best, roots, np.arange(1, n + 1, dtype=np.int64))
    return best[roots]


def _propagate(c: np.ndarray, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    u = c.copy()
    np.maximum.at(u, src, c[dst])
    return u


def rounds(n: int, src: np.ndarray, dst: np.ndarray) -> int:
    """Listing 1's rounds from labels 1..n to the fixed point, the last one included."""
    c = np.arange(1, n + 1, dtype=np.int64)
    k = 0
    while True:
        u = _propagate(c, src, dst)
        k += 1
        if np.array_equal(u, c):
            return k
        c = u


def control(scale: int, edge_factor: int, seed: int) -> np.ndarray:
    """Listing 1 to its fixed point with the labels stored in bfloat16."""
    import jax.numpy as jnp

    def bf16(x):
        return np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)

    src, dst = kronecker(scale, edge_factor, seed)
    c = bf16(np.arange(1, (1 << scale) + 1, dtype=np.float32))
    while True:
        u = bf16(_propagate(c, src, dst))
        if np.array_equal(u, c):
            return u.astype(np.int64)
        c = u


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """The number compared for one answer: ``label_mismatch``, the vertices whose label differs."""
    got = np.asarray(got).reshape(-1)
    if got.shape != want.shape:
        return {"label_mismatch": float("inf")}
    return {"label_mismatch": float(np.count_nonzero(got != want))}
