"""Sharded lowering on a small host-device mesh (subprocess: 8 devices).

Proves the sharding policy + vocab-parallel + MoE shard_map lower and
compile on a real multi-device mesh inside the test suite (the 256/512-
device production meshes are exercised by launch/dryrun.py)."""

import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import Model
from repro.optim import AdamWConfig
from repro.runtime import axis_rules, build_train_step, make_policy, param_pspec_tree
from repro.runtime.steps import TrainState
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(2, 4)
for arch in ("qwen2-moe-a2.7b", "granite-8b"):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, n_heads=4, n_kv_heads=4, d_ff=128,
                              vocab_pad_multiple=64)
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_routed=8, n_routed_padded=8))
    model = Model(cfg)
    policy = make_policy(cfg, mesh)
    with axis_rules(mesh, policy.rules()):
        shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
        pspecs = param_pspec_tree(shapes, policy)
        sds = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=NamedSharding(mesh, sp)),
            shapes, pspecs, is_leaf=lambda x: hasattr(x, "shape"))
        opt_cfg = AdamWConfig()
        state = TrainState(params=sds,
                           opt={"mu": sds, "nu": sds,
                                "step": jax.ShapeDtypeStruct((), jnp.int32)},
                           step=jax.ShapeDtypeStruct((), jnp.int32))
        batch = {"tokens": jax.ShapeDtypeStruct(
            (4, 33), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))}
        step = build_train_step(model, opt_cfg)
        compiled = jax.jit(step).lower(state, batch).compile()
        ca = compiled.cost_analysis()
        assert ca["flops"] > 0
        print(f"OK {arch}")
'''


def test_lowering_on_8_device_mesh():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(SRC)],
        capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK qwen2-moe-a2.7b" in res.stdout
    assert "OK granite-8b" in res.stdout
