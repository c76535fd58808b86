"""Pallas TPU kernel: compacted-frontier relax scatter-min.

The sparse-frontier round (core/sssp/frontier backend) gathers the
out-edges of the few vertices in the compacted frontier buffer and
scatter-MINs their relax candidates into the distance vector — per-round
work proportional to the wavefront, not the graph.  The XLA wrapper
(kernels/ops.frontier_relax_b) does the CSR gather (cand = x[u] + w and
the destination ids, both ``[cap, max_out_deg]``); this kernel owns the
scatter reduction:

    out[b, v] = min over cells k with tgt[k] == v of cand[b, k]

TPU adaptation: the grid is ``(B, cell_blocks)`` with the cell axis
innermost and executed in order, so lane b's output stays resident in
VMEM while every cell block is folded into it — the PRAM's CRCW
concurrent-min write becomes an ordered in-VMEM min, no atomics.  The
targets and candidates are read one at a time as scalars, so their
blocks live in SMEM.  The output is kept as a dense ``(n//128 + 1, 128)``
tile (row ``t // 128``, lane ``t % 128``); each live cell loads its
row, mins one lane under an iota mask and stores the row back — a
dynamic *sublane* offset, which the TPU addresses freely, where a
dynamic lane offset would have to be a multiple of 128.  Padding cells
carry ``cand = +inf`` and are skipped, so the wrapper may clamp
sentinel targets instead of branching.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_CELLS = 1024
_VMEM_DEFAULT = 16 << 20      # v5e scoped-VMEM default


def _scatter_min_kernel(tgt_ref, cand_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, jnp.inf, jnp.float32)

    last = out_ref.shape[0] * 128 - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)

    def cell(k, carry):
        v = cand_ref[k]

        @pl.when(v < jnp.inf)
        def _fold():
            t = jnp.minimum(tgt_ref[k], last)
            row = pl.ds(t // 128, 1)
            cur = out_ref[row, :]
            out_ref[row, :] = jnp.where(lane == t % 128,
                                        jnp.minimum(cur, v), cur)
        return carry

    jax.lax.fori_loop(0, tgt_ref.shape[0], cell, 0)


@functools.partial(jax.jit,
                   static_argnames=("n", "block_cells", "interpret"))
def frontier_scatter_min_batch(tgt: jax.Array, cand: jax.Array, n: int,
                               *, block_cells: int = DEFAULT_BLOCK_CELLS,
                               interpret: bool = False) -> jax.Array:
    """Shared-table batched scatter-min -> float32[B, n].

    ``tgt`` int32[cap, deg] is ONE union-frontier target table shared by
    every lane; ``cand`` float32[B, cap, deg] carries per-lane
    candidates (+inf on padding and lane-masked cells).  Cells ``>= n``
    are padding (their ``cand`` must be +inf).  One target table serves
    all lanes (the shared-batch-frontier contract).
    """
    B = cand.shape[0]
    cells = tgt.size
    block = min(block_cells, -(-cells // 128) * 128)
    cells_pad = -(-cells // block) * block
    tgt = jnp.pad(tgt.reshape(-1).astype(jnp.int32), (0, cells_pad - cells),
                  constant_values=n)
    cand = jnp.pad(cand.reshape(B, -1).astype(jnp.float32),
                   ((0, 0), (0, cells_pad - cells)),
                   constant_values=jnp.inf).reshape(-1)
    nblk = cells_pad // block
    rows = n // 128 + 1          # rows * 128 >= n + 1: sentinels stay out
    # the resident output is double-buffered across lanes
    out_bytes = 2 * (-(-rows // 8) * 8) * 128 * 4
    params = None
    if out_bytes + (1 << 20) > _VMEM_DEFAULT:
        params = pltpu.CompilerParams(vmem_limit_bytes=out_bytes + (4 << 20))
    out = pl.pallas_call(
        _scatter_min_kernel,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((block,), lambda b, i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), lambda b, i: (b * nblk + i,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, rows, 128), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, rows, 128), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(tgt, cand)
    return out.reshape(B, rows * 128)[:, :n]


def frontier_scatter_min(tgt: jax.Array, cand: jax.Array, n: int,
                         *, interpret: bool = False) -> jax.Array:
    """int32/float32[cap, deg] scatter-min -> float32[n]: the batched
    kernel at B = 1."""
    return frontier_scatter_min_batch(tgt, cand[None], n,
                                      interpret=interpret)[0]
