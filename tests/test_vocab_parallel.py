"""Numerical equivalence of the vocab-parallel shard_map paths on a REAL
multi-device mesh (8 host devices, subprocess): vp_embed == take,
vp_cross_entropy == dense CE, and gradients match."""

import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.vocab_parallel import vp_cross_entropy, vp_embed
from repro.models.model import cross_entropy
from repro.launch.mesh import make_host_mesh
from repro.runtime.pspec import axis_rules

mesh = make_host_mesh(2, 4)
rules = {"batch": ("data",), "embed": None, "ffn": "model", "vocab": "model",
         "experts": "model", "heads": None, "kv_heads": None, "seq": None,
         "kv_seq": None, "fsdp": "data"}

rng = np.random.default_rng(0)
B, S, V, D = 4, 16, 64, 8
vocab_size = 57  # < V: padding rows must be masked
table = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)
tokens = jnp.asarray(rng.integers(0, vocab_size, (B, S)), jnp.int32)
logits = jnp.asarray(rng.normal(size=(B, S, V)), jnp.float32)
labels = jnp.asarray(rng.integers(0, vocab_size, (B, S)), jnp.int32)
labels = labels.at[0, :3].set(-1)  # masked positions

table_s = jax.device_put(table, NamedSharding(mesh, P("model", None)))
tokens_s = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
logits_s = jax.device_put(logits, NamedSharding(mesh, P("data", None, "model")))
labels_s = jax.device_put(labels, NamedSharding(mesh, P("data", None)))

with axis_rules(mesh, rules):
    emb = jax.jit(lambda t, tok: vp_embed(t, tok, ("data",)))(table_s, tokens_s)
    np.testing.assert_allclose(np.asarray(emb), np.asarray(table)[np.asarray(tokens)],
                               rtol=1e-6)
    ce_vp = jax.jit(lambda l, y: vp_cross_entropy(l, y, vocab_size, ("data",)))(
        logits_s, labels_s)
    ce_dense = cross_entropy(logits, labels, vocab_size)
    np.testing.assert_allclose(float(ce_vp), float(ce_dense), rtol=1e-5)

    # gradients through the shard_map path match the dense path
    g_vp = jax.jit(jax.grad(lambda l: vp_cross_entropy(l, labels_s, vocab_size,
                                                       ("data",))))(logits_s)
    g_dn = jax.grad(lambda l: cross_entropy(l, labels, vocab_size))(logits)
    np.testing.assert_allclose(np.asarray(g_vp), np.asarray(g_dn), atol=1e-6)

    # embedding gradient: scatter back to the right rows
    def loss_vp(t):
        return vp_embed(t, tokens_s, ("data",)).sum()
    def loss_dn(t):
        return jnp.take(t, tokens, axis=0).sum()
    gt_vp = jax.jit(jax.grad(loss_vp))(table_s)
    gt_dn = jax.grad(loss_dn)(table)
    np.testing.assert_allclose(np.asarray(gt_vp), np.asarray(gt_dn), atol=1e-6)
print("OK")
'''


def test_vocab_parallel_numerics_8dev():
    res = subprocess.run([sys.executable, "-c", SCRIPT, str(SRC)],
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout
