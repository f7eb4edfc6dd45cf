"""The linear-regression pipeline as the benchmark drives it.

``build`` makes one tenant's lowering through the program's entry
(``vee.apps.linreg_device_lowering``); ``answer`` is the program's own
finalize (the solve) of what the walk returned; ``Checker`` holds the
float64 reference of one tenant; ``work`` counts the least operations and
bytes one job needs.
"""

from __future__ import annotations

import numpy as np

from bench.reference import linreg as ref


def build(cfg: dict, rows: int, seed: int):
    """One tenant's lowering: ``rows`` of the configuration's width, data from ``seed``."""
    from repro.vee.apps import linreg_device_lowering

    return linreg_device_lowering(rows, cfg["cols"], tile=cfg["tile"],
                                  lam=cfg["lam"], seed=seed)


def answer(lowering, values: dict) -> dict:
    """The coefficients the program's finalize solves for."""
    return {"beta": np.asarray(lowering.finalize(values)).reshape(-1)}


class Checker:
    """One tenant's float64 reference and the numbers an answer is judged by."""

    def __init__(self, cfg: dict, rows: int, seed: int):
        gram = ref.normal_equations(rows, cfg["cols"], seed)
        self.want = {"beta": ref.solve(gram, cfg["lam"])}

    def __call__(self, got: dict, request: int = 0) -> dict:
        """The numbers ``got`` is judged by; a job answers one request, 0."""
        return ref.compare(got, self.want)


def control(cfg: dict, rows: int, seed: int) -> dict:
    """The reference with its operand one precision step below the configuration's."""
    return ref.control(rows, cfg["cols"], cfg["lam"], seed)


def work(cfg: dict, rows: int, traffic: dict) -> dict:
    """The least work of one job, its one answer: read ``[X, 1, y]`` once, write beta once.

    The operand is ``(cols + 1) x rows`` float32 (the feature-major
    ``[X, 1, y]^T``). The operations are those of the Gram product that
    gives ``[X1^T X1 | X1^T y]``: ``2 * cols * (cols + 1) * rows``. The
    standardization, the moments and the solve are a few operations per
    element and are left out, so the count can only be low.
    """
    cols = cfg["cols"]
    return {"flops": 2.0 * cols * (cols + 1) * rows,
            "bytes": 4.0 * (cols + 1) * rows + 4.0 * cols}
