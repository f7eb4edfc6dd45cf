"""Plain float64 reference of the linear-regression training pipeline.

The paper's Listing 2: X, y uniform on [0, 1) from a seed; X standardized
per column; X1 = [X, 1]; A = X1^T X1 + lam I; b = X1^T y; beta = A^-1 b.
The data generator is a copy of the one the program's lowering uses (a
numpy ``default_rng(seed).uniform`` draw of ``rows x cols``, the last
column being y), so the reference sees the same numbers the program does,
before the program rounds them to float32. Nothing here imports the
program.

``control`` is the same computation put in the program's place with its
operand stored one precision step below the float32 the configuration
states: bfloat16, which would halve the bytes of the memory-bound walk.
"""

from __future__ import annotations

import numpy as np


def data(rows: int, cols: int, seed: int) -> np.ndarray:
    """The seeded ``(rows, cols)`` float64 draw; column ``cols - 1`` is y."""
    return np.random.default_rng(seed).uniform(0.0, 1.0, size=(rows, cols))


def normal_equations(rows: int, cols: int, seed: int) -> np.ndarray:
    """``[X1^T X1 | X1^T y]``, ``(cols, cols + 1)`` float64, X1 = [standardized X, 1]."""
    return normal_equations_of(data(rows, cols, seed))


def normal_equations_of(XY: np.ndarray) -> np.ndarray:
    """``normal_equations`` of a given ``(rows, cols)`` draw; standardizes it in place."""
    rows, cols = XY.shape
    X, y = XY[:, :-1], XY[:, -1]
    mean, std = X.mean(0), X.std(0)
    std[std == 0] = 1.0
    X -= mean
    X /= std
    Ab = np.empty((cols, cols + 1))
    Ab[:-1, :-2] = X.T @ X
    Ab[:-1, -2] = Ab[-1, :-2] = X.sum(0)
    Ab[-1, -2] = rows
    Ab[:-1, -1] = X.T @ y
    Ab[-1, -1] = y.sum()
    return Ab


def solve(Ab: np.ndarray, lam: float) -> np.ndarray:
    """The ridge coefficients from ``[A | b]``: slopes, then the intercept."""
    cols = Ab.shape[0]
    return np.linalg.solve(Ab[:, :cols] + np.eye(cols, dtype=Ab.dtype) * lam, Ab[:, cols])


def control(rows: int, cols: int, lam: float, seed: int) -> dict:
    """The reference with ``[X, y]`` stored in bfloat16, computed in float32.

    On the default device: the data rounded to bfloat16, then standardized,
    multiplied (the Gram at ``Precision.HIGHEST``) and solved in float32,
    as the program computes from its float32 operand.
    """
    import jax
    import jax.numpy as jnp

    XY = jnp.asarray(data(rows, cols, seed).astype(np.float32))
    XY = XY.astype(jnp.bfloat16).astype(jnp.float32)
    X, y = XY[:, :-1], XY[:, -1:]
    std = X.std(0)
    X1 = jnp.concatenate([(X - X.mean(0)) / jnp.where(std == 0, 1.0, std),
                          jnp.ones((rows, 1), jnp.float32), y], axis=1)
    Ab = np.asarray(jnp.dot(X1.T, X1, precision=jax.lax.Precision.HIGHEST)[:cols])
    return {"beta": solve(Ab, np.float32(lam))}


def compare(got: dict, want: dict) -> dict:
    """The number compared for one answer: ``slope_err``, the error of its
    slopes over their norm.

    The slopes are small beside the intercept and would hide under it. A
    precision lost on the way to the Gram matrix shows in them, since
    they are the regression of y on features that y does not depend on.
    """
    beta = np.asarray(got["beta"], np.float64).reshape(-1)
    ref_beta = want["beta"]
    if beta.shape != ref_beta.shape or not np.isfinite(beta).all():
        return {"slope_err": float("inf")}
    return {"slope_err": float(np.linalg.norm(beta[:-1] - ref_beta[:-1])
                               / np.linalg.norm(ref_beta[:-1]))}
