"""Pallas TPU kernel: masked global min (the engine's minD / threshold).

The sequential algorithms read these off heap roots; the PRAM version
(SP4 Step 1) uses a doubly-logarithmic reduction tree.  On TPU the VPU
gives us a lane-parallel min: the vector is laid out as ``(rows, 128)``,
each grid step folds its ``(block_rows, 128)`` block into a resident
``(8, 128)`` running-min tile with elementwise mins only (grid steps
are ordered on TPU), and the wrapper reduces that one tile to the
scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 512


def _masked_min_kernel(x_ref, m_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, jnp.inf, jnp.float32)

    blk = jnp.where(m_ref[...] != 0, x_ref[...], jnp.inf)
    out_ref[...] = jnp.minimum(out_ref[...],
                               jnp.min(blk.reshape(-1, 8, 128), axis=0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def masked_min(x: jax.Array, mask: jax.Array, *,
               block_rows: int = DEFAULT_BLOCK_ROWS,
               interpret: bool = False):
    """min over x[mask] -> float32 scalar (+inf when mask empty).

    x, mask are 1-D; the wrapper lays them out as ``(rows, 128)`` and
    pads to a block multiple with +inf/False.
    """
    n = x.shape[0]
    rows = -(-n // 128)
    block_rows = min(block_rows, -(-rows // 32) * 32)
    rows_pad = -(-rows // block_rows) * block_rows
    pad = rows_pad * 128 - n
    x = jnp.pad(x.astype(jnp.float32), (0, pad), constant_values=jnp.inf)
    mask = jnp.pad(mask.astype(jnp.int32), (0, pad))
    out = pl.pallas_call(
        _masked_min_kernel,
        grid=(rows_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 128), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=interpret,
    )(x.reshape(rows_pad, 128), mask.reshape(rows_pad, 128))
    return jnp.min(out)
