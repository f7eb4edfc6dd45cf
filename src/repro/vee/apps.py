"""The paper's two IDA pipelines (Listings 1 and 2), realized on the VEE.

Connected components (sparse, load-imbalanced — paper Fig 6a / Listing 1):

    c = seq(1, n)
    while diff > 0 and iter <= maxi:
        u = max(rowMaxs(G * t(c)), c)   # neighbour propagation
        diff = sum(u != c)
        c = u

Linear regression training (dense, balanced — paper Fig 6b / Listing 2):

    X, y <- random; standardize X; X = [X, 1]
    A = syrk(X) + lambda*I ; b = gemv(X, y) ; beta = solve(A, b)

Both are row-partitioned by DaphneSched: the CC propagation concatenates row
blocks; linreg's syrk/gemv are additive partial reductions over row blocks.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

from ..core.dag import (
    DEP_ELEMENTWISE,
    DEP_FULL,
    DagResult,
    PipelineDAG,
    PipelineExecutor,
    Stage,
    StageDep,
)
from ..core.executor import SchedulerConfig
from ..core.submit import Submission
from .engine import VEE, PipelineResult
from .sparse import CSRMatrix

__all__ = [
    "cc_step_numpy", "connected_components", "linear_regression",
    "cc_iteration_dag", "connected_components_dag", "linreg_dag",
    "linear_regression_dag", "recommendation_dag",
    "recommendation_pipeline", "recommendation_oracle",
    "recommendation_regret",
    "linear_regression_online", "recommendation_online",
    "DeviceLowering", "run_device_dag", "linreg_device_lowering",
    "linear_regression_device", "recommendation_device_lowering",
    "recommendation_device", "linear_regression_hetero",
    "recommendation_hetero", "hetero_affinity_dag",
    "linear_regression_migrated", "recommendation_migrated",
]


def cc_step_numpy(G: CSRMatrix, c: np.ndarray) -> np.ndarray:
    """Serial oracle for one propagation step (whole matrix)."""
    return G.row_max_gather(c)


def connected_components(
    G: CSRMatrix,
    config: SchedulerConfig,
    max_iter: int = 100,
) -> tuple[np.ndarray, int, list[PipelineResult]]:
    """Paper Listing 1 on DaphneSched. Returns (labels, iters, per-iter results)."""
    n = G.n_rows
    c = np.arange(1, n + 1, dtype=np.int64)
    row_nnz = G.row_nnz()

    def cost_of_range(start: int, size: int) -> float:
        return float(row_nnz[start : start + size].sum() + size)

    history: list[PipelineResult] = []
    vee = VEE(config)
    for it in range(1, max_iter + 1):
        c_cur = c  # bind for the closure

        def op(start, size, c_cur=c_cur):
            return G.row_max_gather(c_cur, start, start + size)

        res = vee.run(n, op, combine="concat", cost_of_range=cost_of_range)
        u = res.value
        history.append(res)
        diff = int((u != c).sum())
        c = u
        if diff == 0:
            return c, it, history
    return c, max_iter, history


def linear_regression(
    num_rows: int,
    num_cols: int,
    config: SchedulerConfig,
    lam: float = 0.001,
    seed: int = 1,
) -> tuple[np.ndarray, list[PipelineResult]]:
    """Paper Listing 2 on DaphneSched. Returns (beta, stage results)."""
    rng = np.random.default_rng(seed)
    XY = rng.uniform(0.0, 1.0, size=(num_rows, num_cols))
    X, y = XY[:, :-1], XY[:, -1:]

    # normalization / standardization (dense row-parallel)
    Xmean = X.mean(axis=0)
    Xstd = X.std(axis=0)
    Xstd[Xstd == 0] = 1.0

    vee = VEE(config)
    history: list[PipelineResult] = []

    # A = syrk(X1) = X1^T X1 and b = gemv(X1, y), partial-summed over row
    # blocks; X1 = [(X - mean)/std, 1]
    def partial_syrk_gemv(start: int, size: int):
        Xb = (X[start : start + size] - Xmean) / Xstd
        Xb = np.concatenate([Xb, np.ones((Xb.shape[0], 1))], axis=1)
        yb = y[start : start + size]
        return np.concatenate([Xb.T @ Xb, Xb.T @ yb], axis=1)

    res = vee.run(num_rows, partial_syrk_gemv, combine="sum")
    history.append(res)
    Ab = res.value
    A, b = Ab[:, :-1], Ab[:, -1:]
    A = A + np.eye(A.shape[0]) * lam
    beta = np.linalg.solve(A, b)
    return beta, history


def linear_regression_oracle(num_rows: int, num_cols: int, lam: float = 0.001, seed: int = 1):
    """Serial numpy oracle for correctness tests."""
    rng = np.random.default_rng(seed)
    XY = rng.uniform(0.0, 1.0, size=(num_rows, num_cols))
    X, y = XY[:, :-1], XY[:, -1:]
    Xm, Xs = X.mean(0), X.std(0)
    Xs[Xs == 0] = 1.0
    X1 = np.concatenate([(X - Xm) / Xs, np.ones((num_rows, 1))], axis=1)
    A = X1.T @ X1 + np.eye(num_cols) * lam
    b = X1.T @ y
    return np.linalg.solve(A, b)


# ---------------------------------------------------------------------------
# pipeline-DAG versions (core/dag.py): the paper's pipelines as stage graphs
# ---------------------------------------------------------------------------

def cc_iteration_dag(G: CSRMatrix, c_cur: np.ndarray) -> PipelineDAG:
    """One CC iteration as a two-stage DAG.

    ``propagate`` (sparse, skewed: per-row cost ~ nnz) produces the new
    labels; ``changed`` (dense, uniform) counts label flips. The edge is
    elementwise, so convergence checking streams over completed label
    chunks instead of waiting for the propagation barrier — the classic
    producer/consumer overlap the DAG runtime exists for.
    """
    n = G.n_rows
    row_nnz = G.row_nnz()

    def cost_of_range(start: int, size: int) -> float:
        return float(row_nnz[start:start + size].sum() + size)

    propagate = Stage(
        "propagate", n,
        lambda inputs, s, z: G.row_max_gather(c_cur, s, s + z),
        combine="concat", cost_of_range=cost_of_range)
    changed = Stage(
        "changed", n,
        lambda inputs, s, z: int((inputs["propagate"][s:s + z]
                                  != c_cur[s:s + z]).sum()),
        combine="sum", deps=(StageDep("propagate", DEP_ELEMENTWISE),))
    return PipelineDAG([propagate, changed])


def connected_components_dag(
    G: CSRMatrix,
    config: SchedulerConfig,
    per_stage: dict | None = None,
    max_iter: int = 100,
    tuner=None,
) -> tuple[np.ndarray, int, list[DagResult]]:
    """Paper Listing 1 through the pipeline-DAG runtime.

    ``per_stage`` maps stage name -> (technique, layout, victim) combo or
    SchedulerConfig; ``tuner`` (a core.DagTuner) overrides it per iteration
    and observes the iteration wall time (online per-stage selection).
    """
    n = G.n_rows
    c = np.arange(1, n + 1, dtype=np.int64)
    history: list[DagResult] = []
    for it in range(1, max_iter + 1):
        if tuner is not None:
            per_stage = tuner.suggest()
        dag = cc_iteration_dag(G, c)
        res = PipelineExecutor(dag, config).run(Submission(per_stage=per_stage))
        if tuner is not None:
            tuner.observe(res.wall_time_s)
        history.append(res)
        diff = int(res.values["changed"])
        c = res.values["propagate"]
        if diff == 0:
            return c, it, history
    return c, max_iter, history


def linreg_dag(
    num_rows: int,
    num_cols: int,
    lam: float = 0.001,
    seed: int = 1,
):
    """Paper Listing 2 as a composable DAG (no execution).

    Returns ``(dag, finalize)``: stage ``moments`` partial-sums column
    sums and squared sums (for mean/std standardization); ``syrk_gemv``
    depends on it in full and accumulates X1^T X1 and X1^T y over row
    blocks. ``finalize(values)`` performs the tiny host-side solve and
    returns beta. Used directly by linear_regression_dag and as a serving
    Job payload (core/server.py).
    """
    rng = np.random.default_rng(seed)
    XY = rng.uniform(0.0, 1.0, size=(num_rows, num_cols))
    X, y = XY[:, :-1], XY[:, -1:]

    def moments_op(inputs, s, z):
        Xb = X[s:s + z]
        return np.stack([Xb.sum(axis=0), (Xb ** 2).sum(axis=0)])

    def syrk_gemv_op(inputs, s, z):
        m = inputs["moments"]
        mean = m[0] / num_rows
        std = np.sqrt(np.maximum(m[1] / num_rows - mean ** 2, 0.0))
        std[std == 0] = 1.0
        Xb = (X[s:s + z] - mean) / std
        Xb = np.concatenate([Xb, np.ones((Xb.shape[0], 1))], axis=1)
        yb = y[s:s + z]
        return np.concatenate([Xb.T @ Xb, Xb.T @ yb], axis=1)

    dag = PipelineDAG([
        Stage("moments", num_rows, moments_op, combine="sum"),
        Stage("syrk_gemv", num_rows, syrk_gemv_op, combine="sum",
              deps=(StageDep("moments", DEP_FULL),)),
    ])

    def finalize(values: dict) -> np.ndarray:
        Ab = values["syrk_gemv"]
        A, b = Ab[:, :-1], Ab[:, -1:]
        A = A + np.eye(A.shape[0]) * lam
        return np.linalg.solve(A, b)

    return dag, finalize


def linear_regression_dag(
    num_rows: int,
    num_cols: int,
    config: SchedulerConfig,
    lam: float = 0.001,
    seed: int = 1,
    per_stage: dict | None = None,
) -> tuple[np.ndarray, DagResult]:
    """Paper Listing 2 as a DAG: moments -> standardized syrk/gemv -> solve.

    The DAG comes from ``linreg_dag``; the tiny solve happens on the host
    after the run. Returns (beta, DagResult).
    """
    dag, finalize = linreg_dag(num_rows, num_cols, lam=lam, seed=seed)
    res = PipelineExecutor(dag, config).run(Submission(per_stage=per_stage))
    return finalize(res.values), res


def _make_online(online, selector: str, seed: int):
    """Default OnlineScheduler for real-pool loops (SS excluded: chunk=1
    over thousands of rows swamps a thread pool with task dust)."""
    if online is not None:
        return online
    from ..core.online import OnlineScheduler, default_online_arms
    return OnlineScheduler(selector=selector,
                           arms=default_online_arms(include_ss=False),
                           seed=seed)


def linear_regression_online(
    num_rows: int,
    num_cols: int,
    config: SchedulerConfig,
    rounds: int = 3,
    online=None,
    selector: str = "ucb",
    lam: float = 0.001,
    seed: int = 1,
) -> tuple[np.ndarray, list[DagResult], object]:
    """Paper Listing 2 served repeatedly under the online feedback loop.

    Each round replays the linreg DAG on a real PipelineExecutor pool with
    the same core.online.OnlineScheduler: the per-stage bandits pick the
    round's configs, measured chunk times stream back, and stage
    remainders resize mid-run — the closed-loop counterpart of passing a
    ``select_offline_dag`` assignment in ``per_stage``. Returns
    (beta from the final round, per-round DagResults, the trained
    scheduler — reusable across calls to keep learning).
    """
    online = _make_online(online, selector, seed)
    dag, finalize = linreg_dag(num_rows, num_cols, lam=lam, seed=seed)
    history: list[DagResult] = []
    for _ in range(max(1, rounds)):
        res = PipelineExecutor(dag, config).run(Submission(online=online))
        history.append(res)
    return finalize(history[-1].values), history, online


def recommendation_online(
    n_users: int,
    n_items: int,
    config: SchedulerConfig,
    rounds: int = 3,
    online=None,
    selector: str = "ucb",
    density: float = 0.3,
    seed: int = 0,
) -> tuple[np.ndarray, list[DagResult], object]:
    """The recommendation DAG served repeatedly under the feedback loop.

    Same closed loop as ``linear_regression_online`` over the two-branch
    recommendation pipeline. Returns (final top items, per-round
    DagResults, the trained OnlineScheduler).
    """
    online = _make_online(online, selector, seed)
    dag = recommendation_dag(n_users, n_items, density=density, seed=seed)
    history: list[DagResult] = []
    for _ in range(max(1, rounds)):
        res = PipelineExecutor(dag, config).run(Submission(online=online))
        history.append(res)
    return history[-1].values["scores"], history, online


def recommendation_dag(
    n_users: int,
    n_items: int,
    density: float = 0.3,
    seed: int = 0,
) -> PipelineDAG:
    """The two-branch recommendation DAG (no execution).

    ``item_norms`` (reduction over the ratings matrix) and ``user_bias``
    (per-user mean) have no edge between them, so they overlap on a
    shared pool; ``scores`` consumes item_norms in full and user_bias
    elementwise and emits each user's top item.
    """
    R = _ratings(n_users, n_items, density, seed)

    item_norms = Stage(
        "item_norms", n_users,
        lambda inputs, s, z: (R[s:s + z] ** 2).sum(axis=0), combine="sum")
    user_bias = Stage(
        "user_bias", n_users,
        lambda inputs, s, z: R[s:s + z].mean(axis=1), combine="concat")

    def scores_op(inputs, s, z):
        norms = np.sqrt(inputs["item_norms"]) + 1e-9
        bias = inputs["user_bias"][s:s + z]
        return np.argmax(R[s:s + z] / norms - bias[:, None], axis=1)

    scores = Stage(
        "scores", n_users, scores_op, combine="concat",
        deps=(StageDep("item_norms", DEP_FULL),
              StageDep("user_bias", DEP_ELEMENTWISE)))
    return PipelineDAG([item_norms, user_bias, scores])


def recommendation_pipeline(
    n_users: int,
    n_items: int,
    config: SchedulerConfig,
    per_stage: dict | None = None,
    density: float = 0.3,
    seed: int = 0,
) -> tuple[np.ndarray, DagResult]:
    """Run the recommendation DAG on one PipelineExecutor pool.

    See ``recommendation_dag`` for the stage graph (the two independent
    branches overlap on the shared pool). Returns (top_items, result).
    """
    dag = recommendation_dag(n_users, n_items, density=density, seed=seed)
    res = PipelineExecutor(dag, config).run(Submission(per_stage=per_stage))
    return res.values["scores"], res


def _ratings(n_users: int, n_items: int, density: float, seed: int) -> np.ndarray:
    """The seeded float64 ratings matrix every recommendation path shares."""
    rng = np.random.default_rng(seed)
    R = rng.uniform(0.0, 1.0, size=(n_users, n_items))
    R *= rng.uniform(size=(n_users, n_items)) < density
    return R


def recommendation_oracle(n_users: int, n_items: int, density: float = 0.3,
                          seed: int = 0) -> np.ndarray:
    """Serial numpy oracle for recommendation_pipeline."""
    R = _ratings(n_users, n_items, density, seed)
    norms = np.sqrt((R ** 2).sum(axis=0)) + 1e-9
    bias = R.mean(axis=1)
    return np.argmax(R / norms - bias[:, None], axis=1)


def recommendation_regret(items, n_users: int, n_items: int,
                          density: float = 0.3, seed: int = 0) -> np.ndarray:
    """Per user, how far the float64 score of ``items`` falls below the best.

    Scores are ``R / item_norms - user_bias`` as in recommendation_oracle;
    a user's bias is common to all their items, so the regret is that of
    ``R / item_norms``. It is zero where ``items`` holds a best item, and
    small where a float32 path broke a near-tie the other way.
    """
    R = _ratings(n_users, n_items, density, seed)
    R /= np.sqrt((R ** 2).sum(axis=0)) + 1e-9
    items = np.asarray(items).reshape(-1)
    return R.max(axis=1) - R[np.arange(n_users), items]


# ---------------------------------------------------------------------------
# device lowerings (DESIGN.md §11): the same pipelines as one fused launch
# through build_dag_tables + the Pallas multi-stage walker
# ---------------------------------------------------------------------------

@dataclass
class DeviceLowering:
    """A pipeline lowered for the device-DAG path, host-checkable.

    ``dag`` is a host PipelineDAG in TILE units (one task row = one
    device row tile, so any host technique's chunks stay tile-aligned)
    whose ops run the SAME per-tile float32 jnp functions as the device
    ``stages`` (kernels/dag_walk.py WalkStage specs over ``operands`` /
    ``values``, in row space). Host concat values are
    ``(n_tiles, tile, ...)``; ``reshape(-1, ...)`` recovers row space.

    Host ops run those functions eagerly through XLA, the walker runs
    them inside one kernel (Mosaic on a TPU, interpreted elsewhere),
    so the two agree within float32 rounding, not bit for bit: tests
    hold both to the float64 numpy oracle within written tolerances.
    For sum stages the walker accumulates in flat ascending tile order
    (any technique, one shard); the host folds in the same order when run
    with ``technique="SS"`` (one-tile chunks) and ``n_workers=1``.
    ``finalize`` maps stage values to the pipeline's answer (e.g. the
    linreg solve).
    """

    dag: PipelineDAG
    stages: list
    operands: list
    values: dict
    tile: int
    finalize: object = None


def run_device_dag(
    lowering: DeviceLowering,
    stage_techniques: dict | str | None = None,
    n_shards: int = 1,
    n_workers: int | None = None,
    chunk_costs: dict | None = None,
    seed: int = 0,
    stagewise: bool = False,
):
    """Execute a DeviceLowering end-to-end on the device-DAG path.

    Freezes the tile-unit DAG with ``build_dag_tables_cached`` (per-stage
    techniques), scales the super-table slots to row space, then drains
    them with the fused multi-stage walker — or one launch per stage
    when ``stagewise=True`` (the pre-fusion baseline the
    ``device_dag_linreg`` bench row compares against). Returns
    ``(values, tables)``: stage outputs as numpy arrays (row space) and
    the DeviceDagTables (tile units) actually walked.

    Repeat jobs of the same shape (every member of a front-door batch
    signature, or a recurring single job) hit two caches: the host
    lowering memo keyed by ``dag_signature`` and the walker's
    device-resident table cache keyed by the same signature — the table
    transfer happens once, not once per job.
    """
    from ..core.device_schedule import build_dag_tables_cached, dag_signature
    from ..kernels.dag_walk import dag_walk_sharded, dag_walk_stagewise

    key = dag_signature(
        lowering.dag, 1, stage_techniques, n_shards=n_shards,
        n_workers=n_workers, chunk_costs=chunk_costs, seed=seed)
    ddt = build_dag_tables_cached(
        lowering.dag, 1, stage_techniques, n_shards=n_shards,
        n_workers=n_workers, chunk_costs=chunk_costs, seed=seed)
    rows = ddt.tables.copy()
    rows[:, :, 1:] *= lowering.tile  # tile units -> row space for the walker
    if stagewise:
        if n_shards != 1:
            raise ValueError("stagewise baseline runs single-shard")
        out = dag_walk_stagewise(lowering.stages, lowering.operands,
                                 lowering.values, rows[0], lowering.tile)
    else:
        out = dag_walk_sharded(lowering.stages, lowering.operands,
                               lowering.values, rows, lowering.tile,
                               table_key=("devdag", lowering.tile, key))
    return {k: np.asarray(v) for k, v in out.items()}, ddt


def merge_device_lowerings(lowerings: list[DeviceLowering]) -> DeviceLowering:
    """Coalesce same-tile DeviceLowerings into ONE super-table launch (§14).

    The front door's batching on the device path: member ``j``'s stages,
    operands, and values are renamed ``name#j`` (the §14 batch
    convention), bodies and host ops wrapped to see their original names,
    and the host DAGs merged with ``core.admission.merge_dags`` — so
    ``build_dag_tables`` freezes one super-table covering every member
    and ``dag_walk`` drains the whole batch in one fused launch. Members
    stay disjoint (each keeps its own operands and accumulators), so the
    merged run is bit-equal to running each lowering alone. ``finalize``
    returns the list of per-member finalize results;
    ``split_device_values`` recovers per-member stage values.
    """
    from ..core.admission import BATCH_SEP, merge_dags

    if not lowerings:
        raise ValueError("cannot merge an empty batch of lowerings")
    tiles = {low.tile for low in lowerings}
    if len(tiles) != 1:
        raise ValueError(f"cannot merge lowerings with mixed tiles {tiles}")

    def _wrap_body(body):
        def wrapped(ctx, ins, out):
            body(ctx, {k.rsplit(BATCH_SEP, 1)[0]: v for k, v in ins.items()},
                 out)
        return wrapped

    by_name, operands, values = {}, [], {}
    for j, low in enumerate(lowerings):
        for st in low.stages:
            renamed = dataclasses.replace(
                st, name=f"{st.name}{BATCH_SEP}{j}",
                body=_wrap_body(st.body),
                operands=tuple(f"{o}{BATCH_SEP}{j}" for o in st.operands),
                reads=tuple((f"{p}{BATCH_SEP}{j}", kind)
                            for p, kind in st.reads))
            by_name[renamed.name] = renamed
        for op in low.operands:
            operands.append(dataclasses.replace(
                op, name=f"{op.name}{BATCH_SEP}{j}"))
        for k, v in low.values.items():
            values[f"{k}{BATCH_SEP}{j}"] = v

    merged_dag = merge_dags([low.dag for low in lowerings])
    # build_dag_tables numbers stage ids by the merged DAG's topological
    # order (members interleave) — the walker's stage list must match it
    stages = [by_name[n] for n in merged_dag.stage_names]

    members = list(lowerings)

    def finalize(stage_values: dict) -> list:
        per_member = split_device_values(stage_values, len(members))
        return [low.finalize(vals) if low.finalize is not None else vals
                for low, vals in zip(members, per_member)]

    return DeviceLowering(merged_dag, stages, operands, values,
                          lowerings[0].tile, finalize)


def split_device_values(values: dict, n_members: int) -> list[dict]:
    """Split merged ``name#j`` stage values back into per-member dicts."""
    from ..core.admission import BATCH_SEP

    out: list[dict] = [{} for _ in range(n_members)]
    for name, v in values.items():
        base, _, idx = name.rpartition(BATCH_SEP)
        out[int(idx)][base] = v
    return out


def linreg_device_lowering(
    num_rows: int,
    num_cols: int,
    tile: int = 64,
    lam: float = 0.001,
    seed: int = 1,
) -> DeviceLowering:
    """Paper Listing 2 lowered for the fused device walker.

    The device operand is ``W = [X, 1, y]^T``, feature-major
    ``(num_cols + 1, num_rows)`` float32: rows run along the lanes, so a
    TPU holds it without padding 101 features out to 128 lanes and the
    walker reads it without a relayout copy. Two sum stages joined by a
    barrier edge: ``moments`` accumulates the sums and squared sums of
    each feature (``(num_cols + 1, 2)``); ``syrk_gemv`` standardizes the X
    features of each row tile against the FULL moments (read straight from
    the walker's accumulator ref mid-launch), which turns the tile into
    ``[X1, y]^T``, and accumulates the first d+1 rows of its Gram matrix,
    ``[X1^T X1 | X1^T y]``, as one float32 matrix product.
    Host ops and device bodies share the per-tile math.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.dag_walk import WalkOperand, WalkStage

    if num_rows % tile:
        raise ValueError(f"num_rows={num_rows} must be a multiple of tile={tile}")
    rng = np.random.default_rng(seed)
    XY = rng.uniform(0.0, 1.0, size=(num_rows, num_cols)).astype(np.float32)
    d = num_cols - 1
    n = num_rows
    units = n // tile
    W = np.empty((d + 2, n), np.float32)
    W[:d] = XY[:, :d].T
    W[d] = 1.0
    W[d + 1] = XY[:, d]
    del XY

    def _moments_tile(Wb):
        return Wb.sum(axis=1, keepdims=True), (Wb * Wb).sum(axis=1, keepdims=True)

    def _syrk_tile(Wb, M):
        mean = M[:, 0:1] / n
        std = jnp.sqrt(jnp.maximum(M[:, 1:2] / n - mean * mean, 0.0))
        is_x = jax.lax.broadcasted_iota(jnp.int32, mean.shape, 0) < d
        mean = jnp.where(is_x, mean, 0.0)
        std = jnp.where(is_x & (std != 0), std, 1.0)
        Z = (Wb - mean) / std
        gram = jax.lax.dot_general(  # Z Z^T on the matrix unit, full f32
            Z, Z, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return gram[:d + 1]

    def moments_op(inputs, s, z):
        acc = None
        for t in range(s, s + z):
            v = jnp.concatenate(
                _moments_tile(jnp.asarray(W[:, t * tile:(t + 1) * tile])), axis=1)
            acc = v if acc is None else acc + v
        return acc

    def syrk_op(inputs, s, z):
        M = jnp.asarray(inputs["moments"])
        acc = None
        for t in range(s, s + z):
            v = _syrk_tile(jnp.asarray(W[:, t * tile:(t + 1) * tile]), M)
            acc = v if acc is None else acc + v
        return acc

    dag = PipelineDAG([
        Stage("moments", units, moments_op, combine="sum"),
        Stage("syrk_gemv", units, syrk_op, combine="sum",
              deps=(StageDep("moments", DEP_FULL),)),
    ])

    def moments_body(ctx, ins, out):
        sums, squares = _moments_tile(ins["W"][...])
        out[:, 0:1] += sums
        out[:, 1:2] += squares

    def syrk_body(ctx, ins, out):
        out[...] += _syrk_tile(ins["W"][...], ins["moments"][...])

    stages = [
        WalkStage("moments", n, (d + 2, 2), jnp.float32, "sum", moments_body,
                  operands=("W",)),
        WalkStage("syrk_gemv", n, (d + 1, d + 2), jnp.float32, "sum",
                  syrk_body, operands=("W",),
                  reads=(("moments", "full"),)),
    ]
    operands = [WalkOperand("W", (d + 2, tile), ("zero", "row"))]
    values = {"W": jnp.asarray(W)}

    def finalize(stage_values: dict) -> np.ndarray:
        Ab = np.asarray(stage_values["syrk_gemv"])
        A, b = Ab[:, :-1], Ab[:, -1:]
        A = A + np.eye(A.shape[0], dtype=A.dtype) * lam
        return np.linalg.solve(A, b)

    return DeviceLowering(dag, stages, operands, values, tile, finalize)


def linear_regression_device(
    num_rows: int,
    num_cols: int,
    tile: int = 64,
    stage_techniques: dict | str | None = None,
    lam: float = 0.001,
    seed: int = 1,
    stagewise: bool = False,
):
    """Paper Listing 2 end-to-end on the device-DAG path.

    Returns (beta, stage values, DeviceDagTables). ``stagewise=True``
    runs the one-launch-per-stage baseline instead of the fused walker.
    """
    low = linreg_device_lowering(num_rows, num_cols, tile=tile, lam=lam,
                                 seed=seed)
    vals, ddt = run_device_dag(low, stage_techniques, stagewise=stagewise)
    return low.finalize(vals), vals, ddt


def recommendation_device_lowering(
    n_users: int,
    n_items: int,
    tile: int = 64,
    density: float = 0.3,
    seed: int = 0,
) -> DeviceLowering:
    """The two-branch recommendation DAG lowered for the fused walker.

    ``item_norms`` (sum, ``(1, n_items)``) and ``user_bias`` (concat,
    ``(n_users, 1)``) are independent; ``scores`` reads item_norms in full
    (sum accumulator ref) and user_bias elementwise (its own row tile of
    the concat buffer) — exercising every edge kind the walker supports in
    one super-table. Per-user outputs are ``(n_users, 1)`` columns, so
    row reductions stay on the axis they reduce to; ``scores`` picks the
    first item of maximal score, as ``np.argmax`` does.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.dag_walk import WalkOperand, WalkStage

    if n_users % tile:
        raise ValueError(f"n_users={n_users} must be a multiple of tile={tile}")
    R = _ratings(n_users, n_items, density, seed).astype(np.float32)
    units = n_users // tile

    def _norms_tile(Rb):
        return (Rb * Rb).sum(axis=0, keepdims=True)

    def _bias_tile(Rb):
        return Rb.mean(axis=1, keepdims=True)

    def _scores_tile(Rb, norms, bias):
        S = Rb / (jnp.sqrt(norms) + 1e-9) - bias
        item = jax.lax.broadcasted_iota(jnp.int32, S.shape, 1)
        best = jnp.where(S == S.max(axis=1, keepdims=True), item, S.shape[1])
        return best.min(axis=1, keepdims=True)

    def item_norms_op(inputs, s, z):
        acc = None
        for t in range(s, s + z):
            v = _norms_tile(jnp.asarray(R[t * tile:(t + 1) * tile]))
            acc = v if acc is None else acc + v
        return acc

    def user_bias_op(inputs, s, z):
        return jnp.stack([_bias_tile(jnp.asarray(R[t * tile:(t + 1) * tile]))
                          for t in range(s, s + z)])

    def scores_op(inputs, s, z):
        norms = jnp.asarray(inputs["item_norms"])
        return jnp.stack([
            _scores_tile(jnp.asarray(R[t * tile:(t + 1) * tile]), norms,
                         jnp.asarray(inputs["user_bias"][t]))
            for t in range(s, s + z)
        ])

    dag = PipelineDAG([
        Stage("item_norms", units, item_norms_op, combine="sum"),
        Stage("user_bias", units, user_bias_op, combine="concat"),
        Stage("scores", units, scores_op, combine="concat",
              deps=(StageDep("item_norms", DEP_FULL),
                    StageDep("user_bias", DEP_ELEMENTWISE))),
    ])

    def item_norms_body(ctx, ins, out):
        out[...] += _norms_tile(ins["R"][...])

    def user_bias_body(ctx, ins, out):
        out[...] = _bias_tile(ins["R"][...])

    def scores_body(ctx, ins, out):
        out[...] = _scores_tile(ins["R"][...], ins["item_norms"][...],
                                ins["user_bias"][...])

    stages = [
        WalkStage("item_norms", n_users, (1, n_items), jnp.float32, "sum",
                  item_norms_body, operands=("R",)),
        WalkStage("user_bias", n_users, (n_users, 1), jnp.float32, "concat",
                  user_bias_body, operands=("R",)),
        WalkStage("scores", n_users, (n_users, 1), jnp.int32, "concat",
                  scores_body, operands=("R",),
                  reads=(("item_norms", "full"), ("user_bias", "rows"))),
    ]
    operands = [WalkOperand("R", (tile, n_items), ("row", "zero"))]
    values = {"R": jnp.asarray(R)}
    return DeviceLowering(dag, stages, operands, values, tile)


def recommendation_device(
    n_users: int,
    n_items: int,
    tile: int = 64,
    stage_techniques: dict | str | None = None,
    density: float = 0.3,
    seed: int = 0,
    stagewise: bool = False,
):
    """The recommendation pipeline end-to-end on the device-DAG path.

    Returns (top_items, stage values, DeviceDagTables); top items are one
    per user, in row order.
    """
    low = recommendation_device_lowering(n_users, n_items, tile=tile,
                                         density=density, seed=seed)
    vals, ddt = run_device_dag(low, stage_techniques, stagewise=stagewise)
    return vals["scores"].reshape(-1), vals, ddt


# ---------------------------------------------------------------------------
# heterogeneous co-execution (DESIGN.md §13): the same pipelines split
# across the host pool and device walker lanes by a solved placement
# ---------------------------------------------------------------------------

def hetero_affinity_dag(n: int = 4096):
    """The §13 transfer-heavy demo workload: opposite branch affinities.

    ``ingest`` feeds two independent branches — ``featurize`` is
    host-friendly, ``embed`` wants the accelerator — and ``join``
    consumes both elementwise. The transfer term is priced so that
    ping-ponging rows across the boundary is expensive: the solver must
    keep each branch substrate-resident and overlap them to win. ONE
    definition serves the ``hetero_linreg_placement`` CI gate
    (``benchmarks/run.py``), ``examples/hetero_pipeline.py``, and
    ``tests/test_placement.py`` so they cannot drift apart. Returns
    ``(dag, HeteroCostModel)``; the ops are placeholders (virtual-time
    replays never execute stage bodies).
    """
    from ..core.placement import HeteroCostModel, TransferModel

    def _op(inputs, s, z):
        return np.zeros(z)

    dag = PipelineDAG([
        Stage("ingest", n, _op, combine="concat"),
        Stage("featurize", n, _op, combine="concat",
              deps=(StageDep("ingest", DEP_ELEMENTWISE),)),
        Stage("embed", n, _op, combine="concat",
              deps=(StageDep("ingest", DEP_ELEMENTWISE),)),
        Stage("join", n, _op, combine="concat",
              deps=(StageDep("featurize", DEP_ELEMENTWISE),
                    StageDep("embed", DEP_ELEMENTWISE))),
    ])
    costs = HeteroCostModel(
        host={"ingest": np.full(n, 1e-7), "featurize": np.full(n, 1e-7),
              "embed": np.full(n, 1e-5), "join": np.full(n, 1e-7)},
        device={"ingest": np.full(n, 2e-7), "featurize": np.full(n, 2e-6),
                "embed": np.full(n, 1e-8), "join": np.full(n, 2e-6)},
        transfer=TransferModel(latency_s=5e-5, bytes_per_row=64.0,
                               gb_per_s=4.0))
    return dag, costs

def _run_hetero(low: DeviceLowering, config, placement, costs,
                device_speedup, n_device: int):
    """Solve a placement for ``low.dag`` (if none given) and co-execute it.

    The executor runs at tile granularity (technique pinned to ``SS`` on
    the tile-unit DAG), so sum stages fold per-tile partials in ascending
    order and the values are bit-equal to the host-only
    ``PipelineExecutor(technique="SS", n_workers=1)`` run regardless of
    the placement (core/hetero.py). Returns (values, HeteroResult,
    Placement).
    """
    import dataclasses

    from ..core.hetero import HeteroExecutor
    from ..core.placement import calibrate_hetero_costs, select_placement

    if placement is None:
        cm = costs if costs is not None else calibrate_hetero_costs(
            low.dag, device_speedup=device_speedup)
        placement, _, _ = select_placement(
            low.dag, cm, n_workers=config.n_workers, passes=1)
    cfg = dataclasses.replace(config, technique="SS",
                              queue_layout="CENTRALIZED")
    res = HeteroExecutor(low.dag, cfg, placement, n_device=n_device).run()
    return res.values, res, placement


def _run_migrated(low: DeviceLowering, cut: int, direction: str) -> dict:
    """Run ``low`` with one mid-flight substrate migration at chunk ``cut``.

    ``host_to_device`` starts the tile-unit DAG on the host pool
    (technique pinned to SS / one worker — the bit-equality regime),
    preempts after ``cut`` chunks, and re-lowers the checkpointed
    remainder onto the device walker. ``device_to_host`` drains ``cut``
    super-table slots on the walker, freezes the rest, and finishes on
    the host pool. Either way the values match a never-preempted run
    (DESIGN.md §15; bit for bit where host ops and walker agree, see
    ``DeviceLowering``). Returns row-space values.
    """
    from ..core.preempt import (PreemptiveRunner, migrate_to_device,
                                resume_on_host, run_device_prefix)

    cfg = dataclasses.replace(SchedulerConfig(), technique="SS",
                              queue_layout="CENTRALIZED", n_workers=1)
    if direction == "host_to_device":
        res, ck = PreemptiveRunner(low.dag, cfg, preempt_after=cut).run()
        if ck is None:
            return {k: np.asarray(v) for k, v in res.values.items()}
        return migrate_to_device(ck, low)
    if direction == "device_to_host":
        ck, _ = run_device_prefix(low, cut)
        fin = resume_on_host(ck, low.dag, cfg)
        return {k: np.asarray(v) for k, v in fin.values.items()}
    raise ValueError(f"unknown migration direction {direction!r}; expected "
                     "'host_to_device' or 'device_to_host'")


def linear_regression_migrated(
    num_rows: int,
    num_cols: int,
    cut: int,
    direction: str = "host_to_device",
    tile: int = 64,
    lam: float = 0.001,
    seed: int = 1,
) -> np.ndarray:
    """Listing 2 with a mid-flight substrate migration; returns beta.

    Convenience wrapper over ``_run_migrated`` for the linreg lowering —
    the beta matches both ``linear_regression_device`` and the host-only
    executor, whichever substrate the job started on (as ``_run_migrated``
    says).
    """
    low = linreg_device_lowering(num_rows, num_cols, tile=tile, lam=lam,
                                 seed=seed)
    return low.finalize(_run_migrated(low, cut, direction))


def recommendation_migrated(
    n_users: int,
    n_items: int,
    cut: int,
    direction: str = "host_to_device",
    tile: int = 64,
    density: float = 0.3,
    seed: int = 0,
) -> np.ndarray:
    """The recommendation pipeline with one mid-flight migration.

    Returns the scores in row space, matching the unmigrated runs (as
    ``_run_migrated`` says).
    """
    low = recommendation_device_lowering(n_users, n_items, tile=tile,
                                         density=density, seed=seed)
    values = _run_migrated(low, cut, direction)
    return np.asarray(values["scores"]).reshape(-1)


def linear_regression_hetero(
    num_rows: int,
    num_cols: int,
    config: SchedulerConfig,
    placement=None,
    costs=None,
    device_speedup: float = 4.0,
    tile: int = 64,
    n_device: int = 1,
    lam: float = 0.001,
    seed: int = 1,
):
    """Paper Listing 2 split across the host pool and device walker lanes.

    Lowers linreg for the device path (``linreg_device_lowering``), solves
    a placement with ``select_placement`` over calibrated per-substrate
    costs (unless ``placement``/``costs`` are given), and co-executes it
    with a HeteroExecutor — host chunk workers and ``n_device`` walker
    lanes sharing the DAG, results bit-equal to the host-only path.
    Returns (beta, HeteroResult, Placement).
    """
    low = linreg_device_lowering(num_rows, num_cols, tile=tile, lam=lam,
                                 seed=seed)
    values, res, placement = _run_hetero(low, config, placement, costs,
                                         device_speedup, n_device)
    return low.finalize(values), res, placement


def recommendation_hetero(
    n_users: int,
    n_items: int,
    config: SchedulerConfig,
    placement=None,
    costs=None,
    device_speedup: float = 4.0,
    tile: int = 64,
    n_device: int = 1,
    density: float = 0.3,
    seed: int = 0,
):
    """The two-branch recommendation DAG split across both substrates.

    Same flow as ``linear_regression_hetero`` over the
    ``recommendation_device_lowering`` stage graph (independent branches
    can land on different substrates and overlap in real time). Returns
    (top_items, HeteroResult, Placement) — top items in row space.
    """
    low = recommendation_device_lowering(n_users, n_items, tile=tile,
                                         density=density, seed=seed)
    values, res, placement = _run_hetero(low, config, placement, costs,
                                         device_speedup, n_device)
    return np.asarray(values["scores"]).reshape(-1), res, placement
