"""Pallas TPU kernels: Mosaic-compiled on a TPU, interpreted elsewhere.

cc_propagate — DLS-task-table-scheduled CC propagation (the paper's VEE
hot spot); dag_walk — the multi-stage walker draining a whole
pipeline-DAG super-table in one launch (DESIGN.md §11); flash_attention —
tiled online-softmax attention; ssm_scan — Mamba2 chunked SSD;
rwkv6_scan — RWKV6 chunked WKV. mode.py picks Mosaic or the TPU
interpret mode from the backend; ops.py holds the public wrappers, ref.py
the pure-jnp oracles.
"""

from . import dag_walk, ops, ref

__all__ = ["dag_walk", "ops", "ref"]
