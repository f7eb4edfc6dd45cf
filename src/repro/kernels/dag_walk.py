"""Pallas multi-stage DAG walker: one launch drains a whole super-table.

The single-stage device path (kernels/cc_propagate.py) freezes ONE
operator's chunk sequence and launches once per operator — every stage
boundary is a kernel launch, exactly the barrier the §9 host runtime
removed. This module executes a whole pipeline-DAG super-table
(core/device_schedule.py:build_dag_tables) in ONE launch per shard:

* the super-table ``(n_slots, 3) = (stage, start, size)`` arrives via
  scalar prefetch; the grid walks slots sequentially (a shard draining
  its frozen queue), with a second grid axis for stages that need an
  inner loop (e.g. CC propagation's column tiles);
* the prefetched stage id selects the stage body with ``pl.when`` — each
  ``WalkStage`` contributes a body over refs (cc_propagate's
  ``propagate_body`` is the single-stage special case);
* operand block index maps read the slot's row range from the table
  (clamped), so every input block follows the schedule; an operand on the
  inner axis that one stage reads holds its block at steps that do not
  read it, so e.g. a CC round's ``changed`` slots fetch no adjacency;
* a TPU never reads an output block back from HBM: it writes a block
  back when the block index moves on, and a block it has left must not
  be visited again. So a concat stage's output block follows its OWN
  slots only (the table carries, per slot, every stage's latest start),
  and another stage's slot neither moves it nor writes it back;
* a consumer stage reads its producer's OUTPUT ref directly, which holds
  the producer's latest tile: build_dag_tables lets a producer move to
  its next tile only after its elementwise consumers took the current
  one (``dag_walk`` checks this on the host) — the trace-time analogue
  of §9 inter-stage chunk streaming.

Supported edge reads: ``rows`` (elementwise dep on a ``concat`` producer
— the consumer's row tile of the producer's output) and ``full`` (full
dep on a ``sum`` producer — the whole accumulator; full deps on concat
producers need a launch split, see build_dag_tables). ``dag_walk_stagewise``
runs the same stages as one launch per stage (producer outputs re-fed as
plain operands) — the baseline the fused walker is benchmarked against
(``device_dag_linreg``); both paths execute identical per-tile ops in
identical per-stage order, so their results match bit-wise.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.telemetry import Tracer, as_tracer, phase
from .mode import pallas_call

__all__ = ["WalkOperand", "WalkStage", "WalkCtx", "walk_call", "dag_walk",
           "dag_walk_stagewise", "dag_walk_sharded",
           "device_table_cache_stats", "clear_device_table_cache",
           "walker_call_cache_stats", "clear_walker_call_cache",
           "walker_hold_stats"]


# ---------------------------------------------------------------------------
# device-resident super-table cache (DESIGN.md §16)
#
# The scalar-prefetch table is the one host->device transfer every launch
# pays even when the schedule is frozen (server jobs of a recurring
# batch_signature walk the SAME table for every job). Keyed entries keep
# the transferred table device-resident across launches; the content
# fingerprint (shape + bytes) makes a stale hit impossible even if a
# caller reuses a key for a rebalanced table.
# ---------------------------------------------------------------------------

_DEVICE_TABLE_CACHE: dict[tuple, tuple[jax.Array, np.ndarray]] = {}
_DEVICE_TABLE_STATS = {"hits": 0, "misses": 0}


def device_table_cache_stats() -> dict:
    """Device-table cache counters: ``{"hits", "misses", "size"}``."""
    return {**_DEVICE_TABLE_STATS, "size": len(_DEVICE_TABLE_CACHE)}


def clear_device_table_cache() -> None:
    """Drop device-resident tables and reset the hit/miss counters."""
    _DEVICE_TABLE_CACHE.clear()
    _DEVICE_TABLE_STATS["hits"] = 0
    _DEVICE_TABLE_STATS["misses"] = 0


# ---------------------------------------------------------------------------
# compiled walker call memo (DESIGN.md §16)
#
# ``walk_call`` builds a new kernel closure and ``pallas_call`` each time,
# so an eager call traces and lowers the walker anew on every launch: JAX's
# in-memory caches key on the fresh jaxpr and miss. ``dag_walk`` instead
# keeps one ``jax.jit`` of the call per structure (stages, operands, their
# shapes and dtypes, slot count, tile); a repeat launch of that structure
# is dispatched from jit's own caches, with no tracing, lowering or cache
# load. The table stays a runtime argument (scalar prefetch), so another
# schedule with the same slot count reuses the compiled walker. Stage
# bodies are keyed as they compare, so the lowerings build them from
# module-level functions or frozen dataclasses of their constants: two
# jobs of one shape with different data then share one entry. It memoizes
# a compiled program, never a result: the kernel runs on every launch.
# ---------------------------------------------------------------------------


def walker_call_cache_stats() -> dict:
    """Compiled walker call memo counters: ``{"hits", "misses", "size"}``."""
    info = _jitted_walker.cache_info()
    return {"hits": info.hits, "misses": info.misses, "size": info.currsize}


def clear_walker_call_cache() -> None:
    """Drop the memoized walker calls and reset the hit/miss counters."""
    _jitted_walker.cache_clear()


@functools.lru_cache(maxsize=64)
def _jitted_walker(stages: tuple[WalkStage, ...],
                   operands: tuple[WalkOperand, ...],
                   shapes: tuple[tuple[tuple[int, ...], Any], ...],
                   n_slots: int, tile: int) -> tuple[Callable, tuple]:
    """One ``jax.jit`` of ``walk_call`` per structure, with its hold plan.

    Stages and operands are frozen dataclasses, so the key compares them
    by value; a stage body compares as the body itself does (by identity
    for a plain function, by its fields for a frozen-dataclass body).
    ``shapes`` holds each operand's ``(shape, dtype)`` in operand order.
    The hold plan is ``(inner steps per slot, inner-indexed operands,
    ((reader stage, its inner), ...) of the held ones)``.
    """
    call = jax.jit(walk_call(list(stages), list(operands),
                             {op.name: shape for op, (shape, _) in zip(operands, shapes)},
                             n_slots, tile))
    plan = (max(s.inner for s in stages),
            sum("inner" in op.index for op in operands),
            tuple(_held_operands(list(stages), list(operands)).values()))
    return call, plan


# ---------------------------------------------------------------------------
# operand hold counters (DESIGN.md §11)
#
# Grid steps at which an inner-indexed operand's block was held
# (``_index_map``), and grid steps times inner-indexed operands, summed over
# launches. Counted from the launch's slot count and its table's live slots
# per stage, which ``_device_table`` counts once per distinct table.
# ---------------------------------------------------------------------------

_WALKER_HOLD_STATS = {"held_steps": 0, "inner_steps": 0}


def walker_hold_stats() -> dict:
    """Walker hold counters: ``{"held_steps", "inner_steps"}``."""
    return dict(_WALKER_HOLD_STATS)


def _count_held_steps(plan: tuple, live: np.ndarray, n_slots: int) -> None:
    """Add one launch's steps to ``walker_hold_stats``: a held operand's
    reader reads it at ``inner`` steps of each of its live slots."""
    n_inner, n_inner_ops, held = plan
    if not n_inner_ops:
        return
    steps = n_slots * n_inner
    _WALKER_HOLD_STATS["inner_steps"] += steps * n_inner_ops
    _WALKER_HOLD_STATS["held_steps"] += sum(steps - int(live[k]) * inner
                                            for k, inner in held)


def _walk_table(table: np.ndarray, stages: list[WalkStage]) -> np.ndarray:
    """The walker's prefetched table: ``(n_slots, 3 + n_stages) int32``.

    Columns 0-2 are the super-table's ``(stage, start, size)``; column
    ``3 + k`` is stage k's current start at each slot — the start of its
    latest slot so far, or of its first slot before it has run (0 for a
    stage without slots). Raises when a consumer's ``rows`` read of a
    walker stage would find the producer's output block on another tile.
    """
    n_slots = len(table)
    live = table[:, 2] > 0
    cur = np.zeros((n_slots, len(stages)), np.int32)
    for k in range(len(stages)):
        mine = np.flatnonzero(live & (table[:, 0] == k))
        if len(mine):
            latest = np.searchsorted(mine, np.arange(n_slots), side="right") - 1
            cur[:, k] = table[mine[np.maximum(latest, 0)], 1]
    ids = {s.name: k for k, s in enumerate(stages)}
    for k, s in enumerate(stages):
        mine = live & (table[:, 0] == k)
        for prod, kind in s.reads:
            if kind != "rows" or prod not in ids:
                continue
            stale = mine & (cur[:, ids[prod]] != table[:, 1])
            if stale.any():
                i = int(np.flatnonzero(stale)[0])
                raise ValueError(
                    f"stage {s.name!r} reads rows {int(table[i, 1])}.. of "
                    f"{prod!r} at slot {i}, after {prod!r} moved on to rows "
                    f"{int(cur[i, ids[prod]])}..; order each consumer tile "
                    "before its producer's next tile (build_dag_tables does)")
    return np.ascontiguousarray(np.concatenate([table, cur], axis=1))


def _device_table(table: np.ndarray, key: tuple | None
                  ) -> tuple[jax.Array, np.ndarray]:
    """Device-resident copy of a walker table (``_walk_table``), flattened,
    and the table's live slot count per stage (for ``walker_hold_stats``).

    Unkeyed: a plain ``jax.device_put`` — async dispatch, so issuing it
    for shard ``s+1`` before walking shard ``s`` double-buffers the
    transfer behind compute. Keyed: the put happens once per distinct
    table and later launches reuse the resident array (zero-copy
    handoff — the walker reads the cached buffer directly). The host
    array is never mutated afterwards (build_dag_tables_cached marks it
    read-only), so ``may_alias`` lets same-device backends alias the
    host buffer instead of copying. The live counts are counted with the
    put, so a keyed table counts them once.
    """
    flat = table.reshape(-1)

    def put():
        live = np.bincount(table[:, 0][table[:, 2] > 0],
                           minlength=table.shape[1] - 3)
        return jax.device_put(flat, may_alias=True), live

    if key is None:
        return put()
    ck = (key, flat.shape, flat.tobytes())
    hit = _DEVICE_TABLE_CACHE.get(ck)
    if hit is not None:
        _DEVICE_TABLE_STATS["hits"] += 1
        return hit
    _DEVICE_TABLE_STATS["misses"] += 1
    _DEVICE_TABLE_CACHE[ck] = put()
    return _DEVICE_TABLE_CACHE[ck]


@dataclass(frozen=True)
class WalkOperand:
    """One kernel input: a named array with per-axis block indexing.

    ``index`` kinds per axis: ``row`` (the slot's row tile — block index
    ``start // block``, clamped), ``inner`` (the inner grid index, for
    stages that loop over column tiles), ``zero`` (whole axis in one
    block).
    """

    name: str
    block: tuple[int, ...]
    index: tuple[str, ...]

    def __post_init__(self):
        if len(self.block) != len(self.index):
            raise ValueError(f"operand {self.name!r}: block/index rank mismatch")
        bad = set(self.index) - {"row", "inner", "zero"}
        if bad:
            raise ValueError(f"operand {self.name!r}: unknown index kinds {bad}")


@dataclass(frozen=True)
class WalkStage:
    """One DAG stage lowered to a device body.

    ``body(ctx, ins, out_ref)`` runs under ``pl.when(stage_id == k)``;
    ``ins`` maps operand names and producer stage names (``reads``) to
    refs, ``out_ref`` is this stage's output block. ``combine`` is
    ``concat`` (row-blocked ``(n_rows, ...)`` output, each tile written
    by its slot) or ``sum`` (one accumulator block, zero-initialized at
    the first slot, accumulated in slot order). ``reads`` entries are
    ``(producer, kind)`` with kind ``rows`` | ``full``. ``inner`` is how
    many inner grid steps the body uses (1 = only ``ctx.inner == 0``).
    """

    name: str
    n_rows: int
    out_shape: tuple[int, ...]
    out_dtype: Any
    combine: str
    body: Callable
    operands: tuple[str, ...] = ()
    reads: tuple[tuple[str, str], ...] = ()
    inner: int = 1

    def __post_init__(self):
        if self.combine not in ("concat", "sum"):
            raise ValueError(f"stage {self.name!r}: unknown combine {self.combine!r}")
        if self.combine == "concat" and self.out_shape[0] != self.n_rows:
            raise ValueError(
                f"stage {self.name!r}: concat out_shape {self.out_shape} must "
                f"lead with n_rows={self.n_rows}")
        for _, kind in self.reads:
            if kind not in ("rows", "full"):
                raise ValueError(f"stage {self.name!r}: unknown read kind {kind!r}")


@dataclass(frozen=True)
class WalkCtx:
    """Per-slot scalars handed to a stage body (traced values)."""

    slot: Any    # grid slot index
    inner: Any   # inner grid index (column tile)
    start: Any   # slot start row
    size: Any    # slot row count


def _index_map(block: tuple[int, ...], kinds: tuple[str, ...],
               shape: tuple[int, ...], width: int, start_col: int = 1,
               reader: tuple[int, int] | None = None):
    """Block index map for one buffer: row tile / inner / constant.

    ``row`` axes follow the row start in column ``start_col`` of the
    flattened ``width``-wide walker table: the slot's own start (1) for
    operands, the stage's current start (3 + k) for stage k's output.

    ``reader`` is ``(k, inner)`` of the one stage that reads an operand
    with an ``inner`` axis (``_held_operands``). A grid step reads the
    operand when its slot is one of stage k's, is not empty and ``j`` is
    below ``inner``; at any other step the block holds on stage k's latest
    reading block: its current start (column ``3 + k``) and its last inner
    block. A TPU pipeline copies an input block only when its index moves,
    so a held block costs no copy, and no body reads it there.
    """
    nb = [max(1, shape[a] // block[a]) for a in range(len(block))]

    def imap(i, j, tbl):
        col, inner_j = start_col, j
        if reader is not None:
            k, inner = reader
            reads = ((tbl[width * i] == k) & (j < inner)
                     & (tbl[width * i + 2] > 0))
            col = jnp.where(reads, start_col, 3 + k)
            inner_j = jnp.where(reads, j, inner - 1)
        out = []
        for a, kind in enumerate(kinds):
            if kind == "row":
                out.append(jnp.minimum(tbl[width * i + col] // block[a],
                                       nb[a] - 1))
            elif kind == "inner":
                out.append(jnp.minimum(inner_j, nb[a] - 1))
            else:
                out.append(0)
        return tuple(out)

    return imap


def _held_operands(stages: list[WalkStage], operands: list[WalkOperand]
                   ) -> dict[str, tuple[int, int]]:
    """``{operand: (reader stage id, its inner)}`` for each operand whose
    block holds at steps that do not read it (``_index_map``).

    Only an operand with an ``inner`` axis that exactly one stage reads is
    held: the other kinds move with the slot's row tile, which the stages'
    own slots step through anyway, so their maps stay as they are. An
    inner-indexed operand read by several stages keeps the plain map too
    (no lowering has one).
    """
    held = {}
    for op in operands:
        if "inner" not in op.index:
            continue
        readers = [k for k, s in enumerate(stages)
                   if op.name in s.operands or op.name in (p for p, _ in s.reads)]
        if len(readers) == 1:
            held[op.name] = (readers[0], stages[readers[0]].inner)
    return held


def _read_operand(stages_by_name: dict[str, WalkStage], prod: str, kind: str,
                  tile: int) -> WalkOperand:
    """Operand spec for reading producer ``prod``'s output as an input."""
    p = stages_by_name[prod]
    if kind == "rows":
        if p.combine != "concat":
            raise ValueError(f"rows-read of non-concat producer {prod!r}")
        block = (tile,) + tuple(p.out_shape[1:])
        index = ("row",) + ("zero",) * (len(p.out_shape) - 1)
    else:
        if p.combine != "sum":
            raise ValueError(
                f"full-read of concat producer {prod!r} needs a launch split "
                "(see build_dag_tables)")
        block = tuple(p.out_shape)
        index = ("zero",) * len(p.out_shape)
    return WalkOperand(prod, block, index)


def _out_spec(stage: WalkStage, tile: int) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """(block, index kinds) of a stage output buffer."""
    if stage.combine == "concat":
        return ((tile,) + tuple(stage.out_shape[1:]),
                ("row",) + ("zero",) * (len(stage.out_shape) - 1))
    return tuple(stage.out_shape), ("zero",) * len(stage.out_shape)


# the walker's kernel name: its operation in a profile and its HLO instruction
KERNEL_NAME = "devdag_walker"


def walk_call(
    stages: list[WalkStage],
    operands: list[WalkOperand],
    shapes: dict[str, tuple[int, ...]],
    n_slots: int,
    tile: int,
) -> Callable:
    """Build the fused walker's kernel call for a super-table of ``n_slots``.

    Needs only shapes (``shapes`` maps operand names to array shapes), so
    the kernel can be lowered for a described device that holds no data.
    Returns ``f(table, *operand_values)``, where ``table`` is the walker
    table (``_walk_table``) flattened to 1-D int32 (scalar memory pads a
    2-D table's rows to 128 lanes), giving one output per stage, in
    ``stages`` order. The kernel is named ``KERNEL_NAME``. An operand with
    an ``inner`` axis that one stage reads holds its block at the steps
    that do not read it (``_index_map``, ``_held_operands``).
    """
    n_inner = max(s.inner for s in stages)
    width = 3 + len(stages)
    held = _held_operands(stages, operands)
    in_specs = [pl.BlockSpec(op.block, _index_map(op.block, op.index,
                                                  shapes[op.name], width,
                                                  reader=held.get(op.name)))
                for op in operands]
    out_specs, out_shapes = [], []
    for k, s in enumerate(stages):
        block, kinds = _out_spec(s, tile)
        out_specs.append(pl.BlockSpec(block, _index_map(
            block, kinds, s.out_shape, width, start_col=3 + k)))
        out_shapes.append(jax.ShapeDtypeStruct(tuple(s.out_shape), s.out_dtype))

    n_ops = len(operands)

    def kernel(tbl_ref, *refs):
        ins = {op.name: refs[k] for k, op in enumerate(operands)}
        outs = {s.name: refs[n_ops + k] for k, s in enumerate(stages)}
        i = pl.program_id(0)
        j = pl.program_id(1)
        sid = tbl_ref[width * i]
        start = tbl_ref[width * i + 1]
        size = tbl_ref[width * i + 2]

        @pl.when((i == 0) & (j == 0))
        def _init_sums():
            for s in stages:
                if s.combine == "sum":
                    outs[s.name][...] = jnp.zeros(s.out_shape, s.out_dtype)

        for k, s in enumerate(stages):
            def run(s=s):
                stage_ins = {n: ins[n] for n in s.operands}
                for prod, _kind in s.reads:
                    stage_ins[prod] = outs[prod] if prod in outs else ins[prod]
                s.body(WalkCtx(i, j, start, size), stage_ins, outs[s.name])
            pl.when((sid == k) & (j < s.inner) & (size > 0))(run)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots, n_inner),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shapes,
                       name=KERNEL_NAME)


def dag_walk(
    stages: list[WalkStage],
    operands: list[WalkOperand],
    values: dict[str, Any],
    table: np.ndarray,
    tile: int,
    table_key: tuple | None = None,
    _dev_table: tuple[jax.Array, np.ndarray] | None = None,
    tracer: Tracer | None = None,
    job: str = "",
    shard: int = 0,
) -> dict[str, jax.Array]:
    """Drain one shard's super-table in a single Pallas launch.

    ``table`` is ``(n_slots, 3) int32`` (stage, start, size) from
    build_dag_tables (stage ids index ``stages``, which must be in the
    same topological order). Returns {stage name: output array}; on a
    multi-shard table a shard only fills the tiles it owns (combine with
    ``dag_walk_sharded``). ``table_key`` keeps the transferred table
    device-resident across launches (see ``_device_table``);
    ``_dev_table`` is a pre-transferred ``_device_table`` result from
    ``dag_walk_sharded``'s double-buffered prefetch.

    The kernel call is jitted once per structure (stages, operands, their
    shapes and dtypes, ``n_slots``, ``tile``) and kept in a bounded memo
    (``walker_call_cache_stats``); the table is a runtime argument, so a
    repeat launch of the same structure, whatever its schedule, neither
    traces nor lowers. The launch (the memo lookup and the dispatch, plus
    on a miss the trace, lowering and compile or cache load) is the
    ``devdag.launch`` phase of ``tracer`` under ``job``, tagged
    ``shard=<shard>`` (DESIGN.md §18). Each launch adds its steps to
    ``walker_hold_stats``.
    """
    table = np.ascontiguousarray(np.asarray(table, dtype=np.int32))
    if table.ndim != 2 or table.shape[1] != 3:
        raise ValueError(f"super-table must be (n_slots, 3), got {table.shape}")
    by_name = {s.name: s for s in stages}
    if len(by_name) != len(stages):
        raise ValueError("duplicate stage names")
    n_slots = len(table)
    if n_slots == 0:
        return {s.name: jnp.zeros(s.out_shape, s.out_dtype) for s in stages}

    tracer = as_tracer(tracer)
    tag = f"shard={shard}"
    if _dev_table is None:
        with phase(tracer, "devdag.table_put", job, tag):
            _dev_table = _device_table(_walk_table(table, stages), table_key)
    tbl_dev, live = _dev_table
    with phase(tracer, "devdag.launch", job, tag):
        call, plan = _jitted_walker(
            tuple(stages), tuple(operands),
            tuple((tuple(values[op.name].shape), values[op.name].dtype)
                  for op in operands),
            n_slots, tile)
        out = call(tbl_dev, *[values[op.name] for op in operands])
    _count_held_steps(plan, live, n_slots)
    return {s.name: o for s, o in zip(stages, out)}


def dag_walk_stagewise(
    stages: list[WalkStage],
    operands: list[WalkOperand],
    values: dict[str, Any],
    table: np.ndarray,
    tile: int,
) -> dict[str, jax.Array]:
    """One launch per stage: the pre-fusion baseline.

    Each stage drains only its own slots of the super-table; producer
    outputs from earlier launches are re-fed as plain operands. Identical
    per-tile ops in identical per-stage order as the fused walker, so the
    results match bit-wise — the fused path saves the launch boundaries,
    not arithmetic.
    """
    table = np.asarray(table, dtype=np.int32)
    ops_by_name = {o.name: o for o in operands}
    by_name = {s.name: s for s in stages}
    results: dict[str, jax.Array] = {}
    for k, s in enumerate(stages):
        sub = table[(table[:, 0] == k) & (table[:, 2] > 0)].copy()
        sub[:, 0] = 0
        stage_ops = [ops_by_name[n] for n in s.operands]
        stage_vals = {n: values[n] for n in s.operands}
        for prod, kind in s.reads:
            stage_ops.append(_read_operand(by_name, prod, kind, tile))
            stage_vals[prod] = results[prod]
        solo = dataclasses.replace(
            s, operands=s.operands + tuple(p for p, _ in s.reads), reads=())
        out = dag_walk([solo], stage_ops, stage_vals, sub, tile)
        results[s.name] = out[s.name]
    return results


def dag_walk_sharded(
    stages: list[WalkStage],
    operands: list[WalkOperand],
    values: dict[str, Any],
    tables: np.ndarray,
    tile: int,
    table_key: tuple | None = None,
    tracer: Tracer | None = None,
    job: str = "",
) -> dict[str, np.ndarray]:
    """Walk every shard's super-table and combine the per-shard outputs.

    ``tables`` is ``(n_shards, max_slots, 3)``. concat outputs merge by
    tile ownership; sum outputs add per-shard partials (ascending shard
    order — deterministic, but a different association than one shard, so
    bit-wise claims hold per shard count).

    Shard transfers are double-buffered: shard ``s+1``'s table is
    ``device_put`` (async dispatch) before shard ``s``'s launch, so the
    next transfer rides behind the current walk. With ``table_key``
    (e.g. the job's dag_signature) every shard table stays
    device-resident across calls — repeat jobs of the same shape skip
    the transfer entirely.

    Phases of ``tracer`` under ``job`` (DESIGN.md §18): per shard
    ``devdag.table_put`` and ``devdag.launch``; once every shard is
    enqueued, per shard ``devdag.readback`` (the one host copy of its
    outputs, where the host waits for the device); then one
    ``devdag.combine`` over the host copies.
    """
    tracer = as_tracer(tracer)
    tables = np.ascontiguousarray(np.asarray(tables, dtype=np.int32))
    n_shards = tables.shape[0]
    key = (lambda s: (table_key, s)) if table_key is not None \
        else (lambda s: None)

    def put(s):
        with phase(tracer, "devdag.table_put", job, f"shard={s}"):
            return _device_table(_walk_table(tables[s], stages), key(s))

    nxt = put(0) if n_shards else None
    shard_outs = []
    for s in range(n_shards):
        cur, nxt = nxt, (put(s + 1) if s + 1 < n_shards else None)
        shard_outs.append(dag_walk(stages, operands, values, tables[s], tile,
                                   _dev_table=cur, tracer=tracer, job=job,
                                   shard=s))
    host = []
    for s, outs in enumerate(shard_outs):
        with phase(tracer, "devdag.readback", job, f"shard={s}"):
            host.append({name: np.asarray(o) for name, o in outs.items()})
    with phase(tracer, "devdag.combine", job):
        combined: dict[str, np.ndarray] = {}
        for k, s in enumerate(stages):
            if s.combine == "sum":
                acc = host[0][s.name]
                for h in host[1:]:
                    acc = acc + h[s.name]
                combined[s.name] = acc
            else:
                buf = np.zeros(tuple(s.out_shape), host[0][s.name].dtype)
                for sh in range(n_shards):
                    out = host[sh][s.name]
                    for sid, start, size in tables[sh]:
                        if sid == k and size > 0:
                            buf[start:start + size] = out[start:start + size]
                combined[s.name] = buf
    return combined
