"""DLS-scheduled connected-components propagation kernel (the paper's VEE
hot spot, adapted to TPU).

One CC step: ``u[i] = max(max_{j in N(i)} c[j], c[i])`` over a blocked dense
adjacency. The DaphneSched connection is structural: the row-tile execution
ORDER is an input — a task table produced by any of the 11 partitioning
techniques (core/device_schedule.py), delivered via scalar prefetch. A
sequential TPU grid walking the table is exactly a worker draining its queue
in schedule order; cross-core assignment interleaves table slots
(DESIGN.md §3).

Grid: (n_slots, n_col_tiles); col tiles accumulate a running row-max in the
output tile (revisited across j — the output BlockSpec index_map pins the
row tile per slot). Labels travel as a ``(1, n)`` row (column-tile reads)
and an ``(n, 1)`` column (row-tile reads and the output), so every block is
2-D and the row max stays on the sublane axis it is reduced to. VMEM per
step = TILE_R x TILE_C adjacency tile + two label tiles — ~1 MiB at the
default 256x1024 in float32, double-buffered.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import pallas_call

DEFAULT_TILE_R = 256
DEFAULT_TILE_C = 1024


def propagate_body(j, G_ref, c_col_ref, c_row_ref, out_ref):
    """One (row-tile, col-tile) step of CC propagation on refs.

    ``G_ref`` is ``(tile_r, tile_c)``, ``c_col_ref`` the ``(1, tile_c)``
    labels of the column tile, ``c_row_ref`` and ``out_ref`` the
    ``(tile_r, 1)`` labels of the row tile. The single-stage kernel below
    and the multi-stage DAG walker (kernels/dag_walk.py) share this body:
    in the walker it is the ``propagate`` stage of the CC iteration
    super-table, with ``j`` the inner (column-tile) grid index.
    """

    @pl.when(j == 0)
    def _init():
        out_ref[...] = c_row_ref[...]

    # labels are >= 1; masked entries contribute 0 (never win the max)
    vals = jnp.where(G_ref[...] > 0, c_col_ref[...], 0.0)
    out_ref[...] = jnp.maximum(out_ref[...], vals.max(axis=1, keepdims=True))


def _kernel(table_ref, G_ref, c_col_ref, c_row_ref, out_ref):
    propagate_body(pl.program_id(1), G_ref, c_col_ref, c_row_ref, out_ref)


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_c"))
def cc_propagate(G: jax.Array, c: jax.Array, schedule: jax.Array,
                 tile_r: int = DEFAULT_TILE_R,
                 tile_c: int = DEFAULT_TILE_C) -> jax.Array:
    """One propagation step.

    G: (n, n) dense {0,1} (any numeric dtype); c: (n,) labels (float32 or
    int32); schedule: (n_row_tiles,) int32 — row-tile index per grid slot in
    DLS order (a permutation of arange(n_row_tiles)). Returns (n,) float32.
    """
    n = G.shape[0]
    assert n % tile_r == 0 and n % tile_c == 0, (n, tile_r, tile_c)
    n_slots = n // tile_r
    n_ct = n // tile_c
    assert schedule.shape == (n_slots,)
    c = c.astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots, n_ct),
        in_specs=[
            pl.BlockSpec((tile_r, tile_c), lambda i, j, tbl: (tbl[i], j)),
            pl.BlockSpec((1, tile_c), lambda i, j, tbl: (0, j)),
            pl.BlockSpec((tile_r, 1), lambda i, j, tbl: (tbl[i], 0)),
        ],
        out_specs=pl.BlockSpec((tile_r, 1), lambda i, j, tbl: (tbl[i], 0)),
    )
    out = pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        name="cc_propagate",
    )(schedule.astype(jnp.int32), G, c.reshape(1, n), c.reshape(n, 1))
    return out.reshape(n)
