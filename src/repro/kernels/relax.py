"""Pallas TPU kernel: fused edge-relaxation row-min over the ELL layout.

The SSSP engine's hot op (and the GNN substrate's aggregation) is
    out[i] = min_j  mask[i,j] ? d_src[i,j] + w[i,j] : +inf
over the padded in-neighbour (ELL) matrix.  XLA would materialize the
masked sum in HBM between the elementwise ops and the reduction; the
kernel fuses gather-adjacent arithmetic + mask + row-reduction in VMEM.

TPU adaptation (DESIGN.md §2): the reduction axis (in-degree) sits in
lanes (multiple of 128), vertices in sublanes (multiple of 8).  The grid
walks (row-block i, col-block j); TPU grids execute sequentially, so the
same output row-block accumulates its running min across the j steps —
no atomics needed (the CRCW concurrent-min of the PRAM becomes a
sequential in-VMEM min).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 512


def _relax_kernel(d_src_ref, w_ref, mask_ref, out_ref):
    j = pl.program_id(1)
    cand = jnp.where(mask_ref[...], d_src_ref[...] + w_ref[...], jnp.inf)
    blk_min = jnp.min(cand, axis=-1, keepdims=True)  # [block_rows, 1]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = blk_min

    @pl.when(j > 0)
    def _acc():
        out_ref[...] = jnp.minimum(out_ref[...], blk_min)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "interpret"))
def relax_ell(d_src: jax.Array, w: jax.Array, mask: jax.Array,
              *, block_rows: int = DEFAULT_BLOCK_ROWS,
              block_cols: int = DEFAULT_BLOCK_COLS,
              interpret: bool = False) -> jax.Array:
    """float32[n_pad, deg_pad] x3 -> float32[n_pad] row-min.

    Requires n_pad % block_rows == 0 and deg_pad % block_cols == 0 (the
    ops.py wrapper pads).  VMEM per step: 3 * block_rows * block_cols * 4B
    (+ the output column) — defaults use 1.5 MiB, well inside VMEM.
    """
    n, deg = d_src.shape
    block_rows = min(block_rows, max(8, n))
    block_cols = min(block_cols, max(128, deg))
    n_pad = (n + block_rows - 1) // block_rows * block_rows
    deg_pad = (deg + block_cols - 1) // block_cols * block_cols
    if (n_pad, deg_pad) != (n, deg):
        d_src = jnp.pad(d_src, ((0, n_pad - n), (0, deg_pad - deg)),
                        constant_values=jnp.inf)
        w = jnp.pad(w, ((0, n_pad - n), (0, deg_pad - deg)),
                    constant_values=jnp.inf)
        mask = jnp.pad(mask, ((0, n_pad - n), (0, deg_pad - deg)),
                       constant_values=False)
    grid = (n_pad // block_rows, deg_pad // block_cols)
    out = pl.pallas_call(
        _relax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(d_src, w, mask)
    return out[:n, 0]
