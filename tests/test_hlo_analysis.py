"""Roofline HLO analyzer regression tests.

The analyzer is the §Roofline foundation; these tests pin its behaviour on
controlled modules: (a) XLA's cost_analysis counts scan bodies once — the
analyzer must scale by trip count; (b) collective bytes are found; (c) the
slice-traffic model doesn't count full stacked operands.
"""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

from hlo_analysis import analyze_module, parse_hlo  # noqa: E402


@pytest.fixture(scope="module")
def scan_hlo():
    """Compile a scan of 8 matmuls on 4 host devices; return (hlo, xla_flops).

    The artifact is generated in-fixture (no dry-run run needed).
    """
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((4,), ("x",), axis_types=(AxisType.Auto,))
w = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                         sharding=NamedSharding(mesh, P()))
x = jax.ShapeDtypeStruct((8, 64), jnp.float32,
                         sharding=NamedSharding(mesh, P()))
def f(x, w):
    def body(c, _):
        return c @ w, ()
    y, _ = jax.lax.scan(body, x, None, length=8)
    return y.sum()
c = jax.jit(f).lower(x, w).compile()
ca = c.cost_analysis()
import sys
print("XLA_FLOPS", ca["flops"])
sys.stdout.write(c.as_text())
'''
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300)
    if res.returncode != 0:
        pytest.skip("could not compile the scan module on this jax/XLA: "
                    + res.stderr[-500:])
    first, _, hlo = res.stdout.partition("\n")
    return hlo, float(first.split()[1])


def test_trip_count_scaling(scan_hlo):
    hlo, xla_flops = scan_hlo
    costs = analyze_module(hlo)
    per_iter = 2 * 8 * 64 * 64  # one (8,64)@(64,64) matmul
    # XLA counts the body once...
    assert xla_flops < 2 * per_iter + 1000
    # ...the analyzer must count all 8 trips
    assert costs.dot_flops == pytest.approx(8 * per_iter, rel=0.01)


def test_parse_computations(scan_hlo):
    hlo, _ = scan_hlo
    comps = parse_hlo(hlo)
    assert any(i.opcode == "while" for c in comps.values() for i in c.instrs)
    assert any(i.opcode == "dot" for c in comps.values() for i in c.instrs)


def test_collectives_counted():
    hlo = """
HloModule test

ENTRY %main (p: f32[16,8]) -> f32[16,8] {
  %p = f32[16,8]{1,0} parameter(0)
  %ar = f32[16,8]{1,0} all-reduce(%p), replica_groups={}, to_apply=%add
  ROOT %out = f32[16,8]{1,0} add(%ar, %p)
}
"""
    costs = analyze_module(hlo)
    assert costs.coll_bytes["all-reduce"] == 16 * 8 * 4


def test_slice_of_stacked_param_not_overcounted():
    """A fusion whose parameter is only sliced contributes slice-output
    bytes, not the full stacked operand."""
    hlo = """
HloModule test

%fused_slice (param_0.1: f32[32,64,64], param_1.1: s32[]) -> f32[1,64,64] {
  %param_0.1 = f32[32,64,64]{2,1,0} parameter(0)
  %param_1.1 = s32[] parameter(1)
  %c0 = s32[] constant(0)
  ROOT %ds = f32[1,64,64]{2,1,0} dynamic-slice(%param_0.1, %param_1.1, %c0, %c0), dynamic_slice_sizes={1,64,64}
}

ENTRY %main (stack: f32[32,64,64], i: s32[]) -> f32[1,64,64] {
  %stack = f32[32,64,64]{2,1,0} parameter(0)
  %i = s32[] parameter(1)
  ROOT %fusion = f32[1,64,64]{2,1,0} fusion(%stack, %i), kind=kLoop, calls=%fused_slice
}
"""
    costs = analyze_module(hlo)
    slice_bytes = 1 * 64 * 64 * 4
    stack_bytes = 32 * 64 * 64 * 4
    # out + sliced input, NOT the whole stack
    assert costs.hbm_bytes < stack_bytes
    assert costs.hbm_bytes >= 2 * slice_bytes
