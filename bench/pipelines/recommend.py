"""The recommendation pipeline as the benchmark drives it.

``build`` makes one tenant's lowering through the program's entry
(``vee.apps.recommendation_device_lowering``); ``answer`` is the walk's
``scores`` stage, each user's top item; ``Checker`` holds the float64
reference; ``work`` counts the least operations and bytes one job needs.
"""

from __future__ import annotations

import numpy as np

from bench.reference import recommend as ref


def build(cfg: dict, rows: int, seed: int):
    """One tenant's lowering: ``rows`` users, data from ``seed``."""
    from repro.vee.apps import recommendation_device_lowering

    return recommendation_device_lowering(rows, cfg["items"], tile=cfg["tile"],
                                          density=cfg["density"], seed=seed)


def answer(lowering, values: dict) -> np.ndarray:
    """Each user's top item, in user order."""
    return np.asarray(values["scores"]).reshape(-1)


class Checker:
    """One tenant's float64 reference and the number an answer is judged by."""

    def __init__(self, cfg: dict, rows: int, seed: int):
        self.regret = ref.Regret(rows, cfg["items"], cfg["density"], seed)

    def __call__(self, got: np.ndarray, request: int = 0) -> dict:
        """The numbers ``got`` is judged by; a job answers one request, 0."""
        return ref.compare(self.regret(got))


def control(cfg: dict, rows: int, seed: int) -> np.ndarray:
    """The reference one precision step below the configuration's, in the program's place."""
    return ref.control(rows, cfg["items"], cfg["density"], seed)


def work(cfg: dict, rows: int, traffic: dict) -> dict:
    """The least work of one job, its one answer: read R once, write one item per user.

    R is ``rows x items`` float32. Per rating: a square and an add for the
    item norms, an add for the user mean, a divide, a subtract and a
    compare for the scores: six operations.
    """
    items = cfg["items"]
    return {"flops": 6.0 * rows * items,
            "bytes": 4.0 * rows * items + 4.0 * rows}
