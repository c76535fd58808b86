"""jit'd dispatch wrappers: Pallas kernel vs pure-jnp reference.

The jnp reference is the default path everywhere; the Pallas path is
selected explicitly (``use_pallas=True``, the ``pallas`` backend, or
``REPRO_USE_PALLAS=1``).  Every kernel call below goes through
:func:`_interpret`: on a TPU the kernels compile with Mosaic, and on the
CPU backend (tests, CI) they run in Pallas interpret mode, where the
kernel body executes op by op — correct but slow.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.graph import CsrGraph, EllGraph
from repro.kernels import ref
from repro.kernels.frontier_relax import (
    frontier_scatter_min as _frontier_scatter_pallas,
    frontier_scatter_min_batch as _frontier_scatter_batch_pallas)
from repro.kernels.relax import relax_ell as _relax_pallas
from repro.kernels.segment_min import masked_min as _masked_min_pallas
from repro.kernels.cin import cin_layer as _cin_pallas
from repro.kernels.flash_attn import flash_attention as _flash_pallas


def _interpret() -> bool:
    """Interpret mode only on the CPU backend; compiled kernels elsewhere."""
    return jax.default_backend() == "cpu"


def _use_pallas(flag: bool | None) -> bool:
    if flag is not None:
        return flag
    return os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def relax_ell(D: jax.Array, ell: EllGraph, src_mask: jax.Array,
              *, use_pallas: bool | None = None) -> jax.Array:
    """Candidate D' per vertex: min over in-edges of D[src]+w (masked).

    D: float32[n]; src_mask: bool[n] (which sources may relax).
    Returns float32[n] (ELL padding rows dropped).

    ELL padding cells carry ``in_src == n`` (one past the vertex range)
    and ``in_w == +inf``.  Instead of concatenating a sentinel row onto
    ``D``/``src_mask`` on every call — twice per round inside the hot
    ``while_loop`` — the gather index is clamped and padding cells are
    masked out: the padding contribution is +inf either way (masked
    ``where``, and ``in_w`` is +inf there regardless), so results are
    bitwise identical to the sentinel-row formulation.
    """
    idx = jnp.minimum(ell.in_src, ell.n - 1)   # clamp: pure gathers below
    in_range = ell.in_src < ell.n
    # [n_pad, deg_pad] XLA gather; the source mask rides on the values
    # (a masked source offers +inf) — no bool gather, see
    # backends.segment_prims
    d_src = jnp.where(src_mask, D, jnp.inf)[idx]
    if _use_pallas(use_pallas):
        out = _relax_pallas(d_src, ell.in_w, in_range,
                            interpret=_interpret())
    else:
        out = ref.relax_ell_ref(d_src, ell.in_w, in_range)
    return out[: ell.n]


def frontier_relax(x: jax.Array, csr: CsrGraph, f_idx: jax.Array,
                   src_mask: jax.Array,
                   *, use_pallas: bool | None = None) -> jax.Array:
    """Sparse-frontier relax: min over out-edges of the buffered vertices.

    x: float32[n] vertex values; f_idx: int32[cap] compacted frontier
    buffer (padding slots carry ``n``); src_mask: bool[n] (which sources
    may relax this round — label-setting masks non-fixed ones out).
    Returns float32[n]: per-vertex min of ``x[u] + w`` over CSR
    out-edges (u, v, w) with u buffered and masked, +inf elsewhere —
    the same candidate multiset the dense relax reduces for those
    sources, hence bitwise-identical where it matters (min-folding).

    The gather is bounded by ``cap * csr.max_out_deg`` edge slots —
    wavefront-proportional; the graph's ``e_pad`` never appears.  The
    scatter-min runs through the Pallas kernel (kernels/frontier_relax)
    when selected, the jnp ``.at[].min`` oracle otherwise.
    """
    n = csr.n
    u = jnp.minimum(f_idx, n - 1)              # clamp: pure gathers below
    xu = jnp.where(src_mask, x, jnp.inf)[u]    # masked sources offer +inf
    base = csr.indptr[u]                       # int32[cap]
    deg = csr.indptr[u + 1] - base
    j = jnp.arange(csr.max_out_deg, dtype=jnp.int32)[None, :]
    cell_ok = (f_idx < n)[:, None] & (j < deg[:, None])
    epos = jnp.minimum(base[:, None] + j, csr.e_pad - 1)
    tgt = jnp.where(cell_ok, csr.dst[epos], n)      # n = dropped
    cand = jnp.where(cell_ok, xu[:, None] + csr.w[epos], jnp.inf)
    if _use_pallas(use_pallas):
        return _frontier_scatter_pallas(tgt, cand, n,
                                        interpret=_interpret())
    return ref.frontier_scatter_min_ref(tgt, cand, n)


def frontier_relax_b(x: jax.Array, csr: CsrGraph, f_idx: jax.Array,
                     src_mask: jax.Array,
                     *, use_pallas: bool | None = None) -> jax.Array:
    """Batched shared-buffer relax: one union gather, B scatter-mins.

    x: float32[B, n] per-lane vertex values; f_idx: int32[cap] compacted
    UNION frontier (shared across lanes, padding ``n``); src_mask:
    bool[B, n] per-lane relax-source mask.  The CSR walk (offsets,
    destinations, weights) happens ONCE for the whole batch — lanes only
    differ in the gathered ``x`` values and the mask — and the per-lane
    candidates reduce through the batched scatter-min kernel (or the
    jnp oracle).  Returns float32[B, n], +inf where no live offer.
    """
    n = csr.n
    u = jnp.minimum(f_idx, n - 1)              # clamp: pure gathers below
    base = csr.indptr[u]
    deg = csr.indptr[u + 1] - base
    j = jnp.arange(csr.max_out_deg, dtype=jnp.int32)[None, :]
    cell_ok = (f_idx < n)[:, None] & (j < deg[:, None])
    epos = jnp.minimum(base[:, None] + j, csr.e_pad - 1)
    tgt = jnp.where(cell_ok, csr.dst[epos], n)      # SHARED [cap, max_out]
    w = csr.w[epos]
    xu = jnp.where(src_mask, x, jnp.inf)[:, u]    # masked sources: +inf
    cand = jnp.where(cell_ok[None], xu[:, :, None] + w[None], jnp.inf)
    if _use_pallas(use_pallas):
        return _frontier_scatter_batch_pallas(tgt, cand, n,
                                              interpret=_interpret())
    return ref.frontier_scatter_min_batch_ref(tgt, cand, n)


def masked_min(x: jax.Array, mask: jax.Array,
               *, use_pallas: bool | None = None) -> jax.Array:
    if _use_pallas(use_pallas):
        return _masked_min_pallas(x, mask, interpret=_interpret())
    return ref.masked_min_ref(x, mask)


def cin_layer(x_k: jax.Array, x_0: jax.Array, w: jax.Array,
              *, use_pallas: bool | None = None) -> jax.Array:
    if _use_pallas(use_pallas):
        B = x_k.shape[0]
        bb = 32
        pad = (-B) % bb
        if pad:
            x_k = jnp.concatenate(
                [x_k, jnp.zeros((pad,) + x_k.shape[1:], x_k.dtype)])
            x_0 = jnp.concatenate(
                [x_0, jnp.zeros((pad,) + x_0.shape[1:], x_0.dtype)])
        out = _cin_pallas(x_k, x_0, w, block_b=bb,
                          interpret=_interpret())
        return out[:B]
    return ref.cin_layer_ref(x_k, x_0, w)


def flash_attention(q, k, v, *, causal: bool = True,
                    use_pallas: bool | None = None):
    if _use_pallas(use_pallas):
        return _flash_pallas(q, k, v, causal=causal,
                             interpret=_interpret())
    return ref.flash_attention_ref(q, k, v, causal=causal)
