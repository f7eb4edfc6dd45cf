"""Chip compiles of the main-path kernels at deployment sizes, without a chip.

Each test lowers a kernel from shapes for one chip of a described TPU v5e
(2x2) topology and compiles it with the TPU compiler installed beside JAX.
It checks that the Mosaic kernel is in the program (``tpu_custom_call``)
and that the program fits one chip's 16 GiB. The sizes are those of
``chip_smoke.py``: the paper's linear regression at 2,097,152 x 101, the
recommendation pipeline at 65,536 users x 4,096 items, the §14 batch of
three 262,144-row regressions, and CC propagation on a 16,384-node dense
adjacency, alone and as one round of the CC walker (its inner axis over
16 column tiles).

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
tests run under several workers that all import this file. The fixture
also makes ``kernels/mode.py`` build Mosaic kernels, as it does on a TPU,
although the process's backend is the CPU. A compile that passes is not a
chip run.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

HBM_BYTES = 16 * 2**30
# a relayout copy of an operand (a layout the kernel does not read) shows
# up as temp memory of the operand's size; the walker needs none
TEMP_LIMIT = 64 * 2**20

LINREG_ROWS, LINREG_COLS, LINREG_TILE = 2_097_152, 101, 2048
BATCH_ROWS, BATCH_MEMBERS = 262_144, 3
REC_USERS, REC_ITEMS, REC_TILE = 65_536, 4_096, 128
CC_N, CC_TILE, CC_TILE_C = 16_384, 256, 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import mode

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mode, "_on_tpu", lambda: True)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    # the Mosaic kernel, under the stable name a profile shows it by
    assert f"%{kernel}" in text and 'custom_call_target="tpu_custom_call"' in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    assert mem.temp_size_in_bytes < TEMP_LIMIT, mem.temp_size_in_bytes
    return compiled


def _walker(one_chip, low, shapes, n_rows, stages=None):
    """Compile the fused walker of ``low`` for a super-table that covers
    ``n_rows`` rows of every stage, one tile per slot."""
    from repro.kernels.dag_walk import KERNEL_NAME, walk_call

    stages = stages or low.stages
    n_slots = len(stages) * n_rows // low.tile
    call = walk_call(stages, low.operands, shapes, n_slots, low.tile)
    table = _sds(one_chip, (n_slots * (3 + len(stages)),), jnp.int32)
    operands = [_sds(one_chip, shapes[op.name], jnp.float32)
                for op in low.operands]
    return _compile(call, table, *operands, kernel=KERNEL_NAME)


def _linreg(tile=LINREG_TILE, seed=1):
    from repro.vee.apps import linreg_device_lowering

    # the stages and operand specs only: data of one tile, shapes full size
    return linreg_device_lowering(tile, LINREG_COLS, tile=tile, seed=seed)


def test_walker_linreg_compiles(one_chip):
    low = _linreg()
    _walker(one_chip, low, {"W": (LINREG_COLS + 1, LINREG_ROWS)}, LINREG_ROWS)


def test_walker_recommendation_compiles(one_chip):
    from repro.vee.apps import recommendation_device_lowering

    low = recommendation_device_lowering(REC_TILE, REC_ITEMS, tile=REC_TILE)
    stages = [dataclasses.replace(s, n_rows=REC_USERS, out_shape=(REC_USERS, 1))
              if s.combine == "concat" else s for s in low.stages]
    _walker(one_chip, low, {"R": (REC_USERS, REC_ITEMS)}, REC_USERS,
            stages=stages)


def test_walker_batched_linreg_compiles(one_chip):
    from repro.core.admission import BATCH_SEP
    from repro.vee.apps import merge_device_lowerings

    low = merge_device_lowerings([_linreg(seed=s)
                                  for s in range(1, BATCH_MEMBERS + 1)])
    shapes = {f"W{BATCH_SEP}{j}": (LINREG_COLS + 1, BATCH_ROWS)
              for j in range(BATCH_MEMBERS)}
    _walker(one_chip, low, shapes, BATCH_ROWS)


def test_cc_propagate_compiles(one_chip):
    from repro.kernels.cc_propagate import DEFAULT_TILE_R, cc_propagate

    _compile(cc_propagate,
             _sds(one_chip, (CC_N, CC_N), jnp.float32),
             _sds(one_chip, (CC_N,), jnp.float32),
             _sds(one_chip, (CC_N // DEFAULT_TILE_R,), jnp.int32),
             kernel="cc_propagate")


def test_walker_cc_round_compiles(one_chip):
    from repro.vee.apps import cc_device_lowering

    # the stages and operand specs of a small graph, resized to full size
    low = cc_device_lowering(10, 16, tile=CC_TILE, tile_c=CC_TILE_C)
    stages = [dataclasses.replace(s, n_rows=CC_N, out_shape=(CC_N, 1),
                                  inner=CC_N // CC_TILE_C)
              if s.name == "propagate" else dataclasses.replace(s, n_rows=CC_N)
              for s in low.stages]
    _walker(one_chip, low, {"G": (CC_N, CC_N), "c_col": (1, CC_N),
                            "c_row": (CC_N, 1)}, CC_N, stages=stages)
