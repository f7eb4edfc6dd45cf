"""Model-zoo lowering tests (DESIGN.md §17).

Covers the tentpole invariants:

  * a lowered transformer step is bit-equal to the direct (unscheduled)
    composition of the same per-row functions across partitioning
    techniques, layouts, and worker counts, and allclose to the real
    full-batch model forward;
  * lowered MoE expert dispatch is bit-equal to its direct oracle across
    techniques on the host AND on the device walker path (the
    ``_expert_tile`` fusion-stable math), and tracks the capacity
    semantics of ``models/moe.py``;
  * a skewed router triggers at least one ``rechunk_pending`` moldable
    resize in online mode, and the online loop never loses to the best
    static uniform partition there (deterministic virtual-time replay);
  * the §14 two-model serving pair reproduces both models' direct
    oracles bit-wise under solved §13 placements;
  * ``core.lower`` chain/fan-out builders behave (streaming edges,
    group-sized ``cost_of_range``, measured stage costs).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (OnlineScheduler, PipelineExecutor, select_offline_dag,
                        simulate_dag, tune_online_dag)
from repro.core.lower import (
    Lowered, chain_dag, costs_from_sizes, fanout_stage, measure_stage_costs,
    run_direct,
)
from repro.core.registry import make_config
from repro.vee.apps import run_device_dag
from repro.vee.ml_apps import (
    _dispatch_plan, moe_device_lowering, moe_dispatch_lowering, serving_pair,
    skewed_tokens, transformer_step_lowering,
)

COMBOS = ["gss", "fac2/percore", "tss/pergroup/rnd", "ss"]


@pytest.fixture(scope="module")
def tf_low():
    return transformer_step_lowering(batch=5, seq=8, seed=0)


@pytest.fixture(scope="module")
def moe_low():
    return moe_dispatch_lowering(n_tokens=48, skew=1.2, seed=0)


# ---------------------------------------------------------------------------
# transformer step chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", COMBOS)
def test_transformer_bitequal_across_techniques(tf_low, spec):
    direct = tf_low.run_direct()
    sched, res = tf_low.run(spec, n_workers=3)
    assert np.array_equal(direct, sched)
    assert set(res.values) == set(tf_low.dag.stage_names)


def test_transformer_bitequal_under_online_resizing(tf_low):
    direct = tf_low.run_direct()
    on = OnlineScheduler(seed=0, min_observe=2)
    sched, _ = tf_low.run("ss", n_workers=2, online=on)
    assert np.array_equal(direct, sched)


def test_transformer_matches_model_forward(tf_low):
    model, params = tf_low.meta["model"], tf_low.meta["params"]
    tokens, seq = tf_low.meta["tokens"], tf_low.meta["seq"]
    positions = jnp.arange(seq)
    x = model._embed_inputs(params, {"tokens": jnp.asarray(tokens)}, positions)
    x, _, _ = model._trunk(params, x, positions)
    ref = np.asarray(model._logits(params, x[:, -1:])[:, 0].astype(jnp.float32))
    np.testing.assert_allclose(tf_low.run_direct(), ref, rtol=3e-2, atol=3e-2)


def test_transformer_rejects_non_dense_arch():
    with pytest.raises(ValueError, match="dense"):
        transformer_step_lowering("qwen2-moe-a2.7b", batch=2, seq=4)


# ---------------------------------------------------------------------------
# MoE expert dispatch (host + device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", COMBOS)
def test_moe_bitequal_across_techniques(moe_low, spec):
    direct = moe_low.run_direct()
    sched, _ = moe_low.run(spec, n_workers=3)
    assert np.array_equal(direct, sched)


def _expert_slabs_f64(dlow):
    """Float64 numpy oracle of the gated expert FFN on every slab row."""
    x = np.asarray(dlow.values["xdisp"], np.float64)
    wi = np.asarray(dlow.values["wi"], np.float64)
    wo = np.asarray(dlow.values["wo"], np.float64)
    h = np.einsum("rd,rdf->rf", x, wi)
    g, u = np.split(h, 2, axis=-1)
    return np.einsum("rf,rfd->rd", g / (1.0 + np.exp(-g)) * u, wo)


# host ops run _expert_tile eagerly, the walker inside one kernel: they agree
# to float32 rounding over d_model-long sums, not bit for bit
MOE_ATOL = 1e-5


@pytest.mark.parametrize("tech", ["STATIC", "GSS", "TSS"])
def test_moe_host_vs_device_bitequal(moe_low, tech):
    dlow = moe_device_lowering(moe_low)
    e, cap, d = (moe_low.meta["n_experts"], moe_low.meta["capacity"],
                 moe_low.meta["d_model"])
    # host pool run of the tile-unit dag, any technique
    host = PipelineExecutor(dlow.dag, make_config(tech, n_workers=2)).run()
    host_flat = np.asarray(host.values["experts"]).reshape(e * cap, d)
    vals, _ = run_device_dag(dlow, tech)
    dev_flat = np.asarray(vals["experts"])
    want = _expert_slabs_f64(dlow)
    for got in (host_flat, dev_flat):
        np.testing.assert_allclose(got, want, rtol=0, atol=MOE_ATOL)
    np.testing.assert_allclose(dev_flat, host_flat, rtol=0, atol=MOE_ATOL)
    # token-side combine of device slabs ~ the host pipeline's answer
    np.testing.assert_allclose(dlow.finalize(vals), moe_low.run_direct(),
                               rtol=0, atol=MOE_ATOL)


def test_moe_capacity_semantics_match_reference(moe_low):
    """Honesty: the lowering tracks models/moe.py, not a private variant."""
    from repro.models.moe import _dispatch_compute_combine, _route

    meta = moe_low.meta
    x = jnp.asarray(meta["x_flat"])
    idx_ref, w_ref, _ = _route(meta["params"]["router"], x, meta["moe"])
    idx, w, pos, kept = _dispatch_plan(meta["route_build"],
                                       meta["n_experts"], meta["capacity"])
    # identical routing (mul-reduce vs dot logits may tie-break top-k
    # differently in principle; require near-total agreement and compare
    # those tokens)
    match = (np.asarray(idx_ref) == idx).all(axis=1)
    assert match.mean() > 0.9
    y_ref = np.asarray(_dispatch_compute_combine(
        meta["params"], x, idx_ref, w_ref, meta["capacity"], meta["moe"]))
    y = moe_low.run_direct()
    np.testing.assert_allclose(y[match], y_ref[match], rtol=2e-4, atol=2e-4)
    assert kept.sum() <= meta["x_flat"].shape[0] * meta["moe"].top_k


def test_moe_expert_costs_follow_router(moe_low):
    kept = moe_low.meta["expert_tokens"]
    stage = moe_low.dag.stages["experts"]
    e = moe_low.meta["n_experts"]
    assert stage.cost_of_range(0, e) == pytest.approx(float(kept.sum() + e))
    assert stage.cost_of_range(0, 1) == pytest.approx(float(kept[0] + 1))
    costs = moe_low.stage_costs["experts"]
    assert costs.shape == (e,)
    np.testing.assert_allclose(costs, costs_from_sizes(kept))


def test_skewed_router_triggers_rechunk_resize():
    low = moe_dispatch_lowering(n_tokens=384, skew=1.6, seed=0,
                                n_experts=32, capacity_factor=6.0)
    kept = low.meta["expert_tokens"]
    assert kept.max() >= 4 * max(1.0, kept.mean())  # the skew is real
    on = OnlineScheduler(seed=0)
    simulate_dag(low.dag, low.stage_costs, n_workers=4, online=on)
    assert on.resizes.get("experts", 0) >= 1
    # the expert fan-out's data-dependent chunk costs are what the bandits
    # and the resizer exploit: never worse than the best static partition
    # (unit-per-token costs scaled to microseconds, against the simulator's
    # per-chunk overheads)
    costs = {k: v * 1e-6 for k, v in low.stage_costs.items()}
    _, _, uniform = select_offline_dag(low.dag, costs, n_workers=4, passes=1)
    res = tune_online_dag(low.dag, costs, n_workers=4, rounds=40, seed=0)
    assert res.makespan <= min(uniform.values())


def test_skewed_tokens_prefer_low_experts():
    rng = np.random.default_rng(0)
    router = rng.standard_normal((32, 8)).astype(np.float32)
    x = skewed_tokens(router, 256, skew=1.6, seed=1)
    logits = x @ router
    hist = np.bincount(logits.argmax(axis=1), minlength=8)
    assert hist[0] == hist.max() and hist[0] > 256 // 8


# ---------------------------------------------------------------------------
# §14 serving pair
# ---------------------------------------------------------------------------

def test_serving_pair_bitequal_with_placement():
    archs = ("qwen2-0.5b", "granite-8b")
    results, subs, placements, lows = serving_pair(
        archs, batch=3, seq=6, n_workers=2)
    for arch, low in zip(archs, lows):
        assert np.array_equal(results[arch], low.run_direct())
    assert {s.name for s in subs} == set(archs)
    for arch in archs:
        assert set(placements[arch].stages) == set(lows[0].dag.stage_names)
    for sub in subs:
        assert sub.placement is not None and sub.stage_costs is not None


# ---------------------------------------------------------------------------
# core.lower builders
# ---------------------------------------------------------------------------

def test_chain_dag_streams_rows():
    dag = chain_dag(10, [("a", lambda _p, r: np.float64(r)),
                         ("b", lambda p, _r: p + 1.0),
                         ("c", lambda p, _r: p * 2.0)])
    vals = run_direct(dag)
    np.testing.assert_allclose(vals["c"], (np.arange(10) + 1.0) * 2.0)
    res = PipelineExecutor(dag, make_config("ss", n_workers=2)).run()
    np.testing.assert_array_equal(res.values["c"], vals["c"])
    assert dag.stages["b"].deps[0].kind == "elementwise"


def test_fanout_stage_cost_of_range():
    sizes = [5, 1, 9, 2]
    st = fanout_stage("f", lambda _i, g: np.zeros(3), sizes)
    assert st.cost_of_range(0, 4) == pytest.approx(17 + 4)
    assert st.cost_of_range(2, 1) == pytest.approx(10.0)
    assert st.n_rows == 4


def test_measure_stage_costs_shapes(moe_low):
    costs = measure_stage_costs(moe_low.dag, sample=2)
    for name in moe_low.dag.stage_names:
        vec = costs[name]
        assert vec.shape == (moe_low.dag.stages[name].n_rows,)
        assert (vec > 0).all()


def test_lowered_submission_carries_costs(moe_low):
    sub = moe_low.submission(name="moe", tenant="t0", weight=2.0)
    assert sub.dag is moe_low.dag
    assert sub.stage_costs is not None and "experts" in sub.stage_costs
    assert sub.tenant == "t0" and sub.weight == 2.0


def test_lowered_without_finalize_returns_values():
    dag = chain_dag(4, [("a", lambda _p, r: np.float64(r))])
    low = Lowered(dag)
    out = low.run_direct()
    assert set(out) == {"a"}
