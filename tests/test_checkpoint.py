"""Checkpoint: atomic roundtrip, crash-safety, async, GC, elastic restore."""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (gc_keep_last, latest_step, restore, save,
                              save_async, wait_for_pending)


def _tree():
    return {
        "params": {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((4,))},
        "opt": {"mu": {"w": jnp.zeros((3, 4))}, "step": jnp.int32(7)},
    }


def test_roundtrip(tmp_path):
    save(tmp_path, 5, _tree(), extra={"loss": 1.25})
    tree, extra, step = restore(tmp_path)
    assert step == 5
    assert extra["loss"] == 1.25
    np.testing.assert_array_equal(tree["params"]["w"], np.arange(12.0).reshape(3, 4))
    assert int(tree["opt"]["step"]) == 7


def test_uncommitted_checkpoint_ignored(tmp_path):
    save(tmp_path, 1, _tree())
    save(tmp_path, 2, _tree())
    # simulate crash: step 2's COMMITTED marker lost
    (tmp_path / "step_00000002.COMMITTED").unlink()
    assert latest_step(tmp_path) == 1
    _, _, step = restore(tmp_path)
    assert step == 1


def test_async_save(tmp_path):
    t = save_async(tmp_path, 3, _tree())
    wait_for_pending()
    assert latest_step(tmp_path) == 3


def test_gc_keep_last(tmp_path):
    for s in range(6):
        save(tmp_path, s, _tree())
    removed = gc_keep_last(tmp_path, keep=2)
    assert removed == [0, 1, 2, 3]
    assert latest_step(tmp_path) == 5
    restore(tmp_path, 4)  # second-newest still restorable


def test_elastic_restore_other_mesh(tmp_path):
    """Restore with shardings targeting a different (1x1) mesh layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    save(tmp_path, 9, _tree())
    mesh = make_host_mesh()
    shardings = {
        "params": {"w": NamedSharding(mesh, P("data", "model")),
                   "b": NamedSharding(mesh, P())},
        "opt": {"mu": {"w": NamedSharding(mesh, P(None, "model"))}, "step": None},
    }
    tree, _, _ = restore(tmp_path, shardings=shardings)
    assert tree["params"]["w"].sharding.spec == P("data", "model")
    np.testing.assert_array_equal(np.asarray(tree["params"]["w"]),
                                  np.arange(12.0).reshape(3, 4))
