"""Kernel mode from the backend the process runs on.

Every Pallas kernel of this package is built through :func:`pallas_call`,
so no caller takes or threads an ``interpret`` flag. On a TPU the kernel is
compiled by Mosaic. On any other backend it runs in Pallas's TPU interpret
mode, which models the chip's block pipeline: an output block is never read
back from HBM, it is written back when its block index moves on, a block
once left may not be visited again, and fresh buffers hold NaN. Tests on
the CPU therefore fail where the chip would compute garbage.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_call"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` compiled by Mosaic on a TPU, interpreted elsewhere.

    Takes the keyword arguments of ``pl.pallas_call`` except ``interpret``;
    ``compiler_params`` only reach Mosaic.
    """
    if _on_tpu():
        return pl.pallas_call(kernel, **kwargs)
    kwargs.pop("compiler_params", None)
    return pl.pallas_call(kernel, interpret=pltpu.InterpretParams(), **kwargs)
