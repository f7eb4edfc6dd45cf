"""Plain float64 reference of the recommendation pipeline.

Ratings R are uniform on [0, 1) from a seed, each kept with probability
``density`` (a second uniform draw of the same shape, taken after the
first), as the program's lowering draws them. A user's score for an item
is ``R / item_norm - user_mean``, with ``item_norm = sqrt(sum R^2) + 1e-9``
over users; the answer is each user's best item. Nothing here imports the
program.

A float32 path breaks near-ties the other way now and then, so an answer
is judged by its regret: how far the float64 score of the item it names
falls below the user's best. ``control`` is the reference put in the
program's place in bfloat16, one precision step below the float32 the
configuration states.
"""

from __future__ import annotations

import numpy as np


def ratings(users: int, items: int, density: float, seed: int) -> np.ndarray:
    """The seeded ``(users, items)`` float64 ratings matrix."""
    rng = np.random.default_rng(seed)
    R = rng.uniform(0.0, 1.0, size=(users, items))
    R *= rng.uniform(size=(users, items)) < density
    return R


class Regret:
    """Per-user regret of a list of top items, against float64 scores."""

    def __init__(self, users: int, items: int, density: float, seed: int):
        S = ratings(users, items, density, seed)
        S /= np.sqrt((S ** 2).sum(axis=0)) + 1e-9  # the bias is common to a row
        self.scores = S
        self.best = S.max(axis=1)

    def __call__(self, top: np.ndarray) -> np.ndarray:
        top = np.asarray(top).reshape(-1)
        users, items = self.scores.shape
        if top.shape != (users,) or top.min() < 0 or top.max() >= items:
            return np.full(users, np.inf)
        return self.best - self.scores[np.arange(users), top]

    def top_items(self) -> np.ndarray:
        """The float64 answer: each user's first best item."""
        return self.scores.argmax(axis=1)


def control(users: int, items: int, density: float, seed: int,
            block: int = 8192) -> np.ndarray:
    """The reference in bfloat16 on the default device, in blocks of users."""
    import jax.numpy as jnp

    R = jnp.asarray(ratings(users, items, density, seed), jnp.bfloat16)
    norms = jnp.sqrt((R * R).sum(axis=0, dtype=jnp.bfloat16)) + jnp.bfloat16(1e-9)
    top = []
    for s in range(0, users, block):
        Rb = R[s:s + block]
        S = Rb / norms - Rb.mean(axis=1, keepdims=True, dtype=jnp.bfloat16)
        top.append(np.asarray(S.argmax(axis=1)))
    return np.concatenate(top)


def compare(regret: np.ndarray) -> dict:
    """The number compared for one answer: the largest regret over users."""
    return {"max_regret": float(np.max(regret))}
