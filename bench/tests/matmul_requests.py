"""A pipeline module that brings its own job, as the benchmark's tests use it.

Its job is no walker launch: one jitted matmul per tenant and call
answers the traffic's ``requests`` requests, each a row vector times the
tenant's matrix. Request ``r`` arrives ``r * arrival_gap_ms`` after the
call starts; the call waits for the last arrival, then answers all, each
under its key ``(tenant, r)``. It stands for a serving job (several
answers per call, each judged by the reference) in the tests of
``bench/run.py``'s pipeline contract; a test copies it to
``bench/pipelines/`` or loads it by its path.

Data comes from the seed alone: the tenant's matrix from ``seed``, request
``r``'s vector from ``(seed, r)``, so the Checker rebuilds any request
without the traffic.
"""

from __future__ import annotations

import time

import numpy as np


def matrix(width: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((width, width), dtype=np.float32)


def request(width: int, seed: int, r: int) -> np.ndarray:
    return np.random.default_rng([seed, r]).standard_normal(width, dtype=np.float32)


def build(cfg: dict, rows, seed: int) -> dict:
    """One tenant: its seed and its matrix on the device."""
    import jax.numpy as jnp

    return {"seed": seed, "w": jnp.asarray(matrix(cfg["width"], seed))}


def owed(cfg: dict, traffic: dict) -> list[tuple[int, int]]:
    """Every request of every tenant: ``(tenant, request)``."""
    return [(k, r) for k in range(traffic.get("tenants", 1)) for r in range(traffic["requests"])]


def make_job(lowerings: list, cfg: dict, traffic: dict):
    """``job()``: every tenant's requests through one jitted matmul each."""
    import jax
    import jax.numpy as jnp

    n, gap = traffic["requests"], traffic["arrival_gap_ms"] / 1e3
    matmul = jax.jit(lambda w, x: jnp.matmul(x, w, precision="highest"))
    xs = [jnp.asarray(np.stack([request(cfg["width"], low["seed"], r) for r in range(n)]))
          for low in lowerings]

    def job():
        t0 = time.perf_counter()
        arrived = [t0 + r * gap for r in range(n)]
        time.sleep(max(0.0, arrived[-1] - time.perf_counter()))
        out = []
        for k, (low, x) in enumerate(zip(lowerings, xs)):
            y = np.asarray(matmul(low["w"], x))
            t = time.perf_counter()
            out += [((k, r), y[r], t, arrived[r]) for r in range(n)]
        return out

    return job


class Checker:
    """One tenant's float64 reference; judges an answer as the answer to request ``r``."""

    def __init__(self, cfg: dict, rows, seed: int):
        self.width, self.seed = cfg["width"], seed
        self.w = matrix(cfg["width"], seed).astype(np.float64)

    def __call__(self, got: np.ndarray, r: int) -> dict:
        want = request(self.width, self.seed, r).astype(np.float64) @ self.w
        err = np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want)
        return {"rel_err": float(err)}


def control(cfg: dict, rows, seed: int) -> np.ndarray:
    """Request 0's answer with the matrix and the vector in bfloat16."""
    import jax.numpy as jnp

    w = jnp.asarray(matrix(cfg["width"], seed), jnp.bfloat16)
    x = jnp.asarray(request(cfg["width"], seed, 0), jnp.bfloat16)
    return np.asarray(jnp.matmul(x, w).astype(jnp.float32))


def work(cfg: dict, rows, traffic: dict) -> dict:
    """The least work of one answer: its product, and its share of the matrix
    that one call reads once for all of its tenant's requests."""
    width, n = cfg["width"], traffic["requests"]
    return {"flops": 2.0 * width * width, "bytes": 4.0 * width * width / n + 8.0 * width}
