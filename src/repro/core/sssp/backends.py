"""Backend primitives: the protocol that makes every execution path one
program.

The engine's round body (engine._round) is written once against four
vertex-level primitives; a *backend* is nothing but a concrete choice of
these four.  This is the engine's own SP1–SP4-as-configurations
philosophy applied to execution substrates: segment ops over the
dst-sorted edge list, the dense ELL layout (jnp oracle or Pallas
kernels), and the edge-sharded ``shard_map`` mesh are *instances* of the
same round, not copies of it.

    relax(x, src_mask)      -> float32[n]
        min over in-edges (u, v, w) with src_mask[u] of x[u] + w,
        reduced at v (INF where no participating in-edge).  This is the
        paper's concurrent-min relaxation and also computes inWeight_nf
        (x = 0) and the Eqn-(1) C-propagation (x = C, mask = all).
    in_weight_nf(nf_mask)   -> float32[n]
        min in-edge weight over edges whose source is in nf_mask —
        semantically relax(zeros, nf_mask); backends may specialize.
    relax2(x, src_mask, nf_mask) -> (relax(x, src_mask),
                                     in_weight_nf(nf_mask))
        optional fusion hook: both reductions depend only on round-start
        state, so a backend may fuse them (the distributed backend stacks
        them into ONE pmin all-reduce, halving per-round collective
        launches).  ``None`` means "run them separately".
    masked_min(x, mask)     -> float32 scalar
        global min over masked vertices (the heap minimum of SP1–SP3).
    relax_frontier(x, f_idx, src_mask) -> float32[n]
        optional sparse hook (the frontier backend): the same reduction
        as ``relax``, but only over out-edges of the vertices in the
        compacted frontier buffer ``f_idx`` (int32[frontier_cap],
        padding slots = n).  Setting it switches the engine's step-1
        D-relaxation to wavefront-proportional rounds; ``frontier_cap``
        must then be > 0 (the buffer's static size; the engine falls
        back to dense ``relax`` for any round whose true frontier
        outgrew it).

All primitives take and return *vertex* arrays; edge-layout details
(gathers, segment ids, ELL padding, CSR offsets, shard partitions) live
entirely behind this line.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.analysis.contracts import contract
from repro.core.graph import CsrGraph, EllGraph, Graph, INF


@dataclasses.dataclass(frozen=True)
class Primitives:
    """The four ops one SSSP round needs (see module docstring)."""

    relax: Callable[[jax.Array, jax.Array], jax.Array]
    in_weight_nf: Callable[[jax.Array], jax.Array]
    masked_min: Callable[[jax.Array, jax.Array], jax.Array]
    relax2: Callable | None = None  # optional fused (relax, in_weight_nf)
    relax_frontier: Callable | None = None  # optional sparse step-1 relax
    frontier_cap: int = 0           # static frontier-buffer size (0 = dense)
    # --- shared-batch-frontier hooks (engine._round_shared; setting
    # relax_frontier_b routes every Solver/Dynamic/Fleet solve — single
    # or batched — through the batch-aware sparse round body) ---
    relax_frontier_b: Callable | None = None  # (x[B,n], f_idx[cap],
    #   src_mask[B,n]) -> [B,n]: ONE shared gather of the union
    #   frontier's out-edges, per-lane scatter-min.
    out_nbrs: Callable | None = None  # (idx[cap]) -> int32[cap, max_out]
    #   shared target table of one inWeight_nf refresh chunk (padding n).
    in_min_at: Callable | None = None  # (x[B,n]|None, tgt, mask[B,n]|None)
    #   -> [B, *tgt.shape]: full in-neighbourhood masked min per target
    #   over the CSC view — the incremental inWeight_nf recompute
    #   primitive.


def _masked_min_local(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.min(jnp.where(mask, x, INF))


@contract(
    "backend.segment",
    routes=("segment.*",),
    require=("scatter-min",),
    dense_budget={"segment.warm": 8, "segment.*": 6},
    notes="The default backend relaxes via jax.ops.segment_min over "
          "the dst-sorted edge list — the compiled program must "
          "contain the scatter-min lowering in the hot region, and a "
          "round costs at most the declared number of full-e_pad "
          "sweeps (warm carries the 2-lane taint/reseed overhead).")
def segment_prims(g: Graph) -> Primitives:
    """Segment reductions over the dst-sorted edge list (the default)."""

    def relax(x, src_mask):
        # The mask rides on the value gather: a masked source offers
        # +inf, and +inf + w is +inf.  One f32 gather per edge, no bool
        # gather: on TPU v5e a vmapped bool gather of this shape
        # miscompiled inside the round loop (batched solves stopped
        # after two rounds with wrong distances).
        xm = jnp.where(src_mask, x, INF)
        return g.seg_min_at_dst(g.gather_src(xm) + g.w)

    def in_weight_nf(nf_mask):
        return relax(jnp.zeros(nf_mask.shape, jnp.float32), nf_mask)

    return Primitives(relax=relax, in_weight_nf=in_weight_nf,
                      masked_min=_masked_min_local)


@contract(
    "backend.ell",
    routes=("ell.*",),
    require=("gather", "reduce_min"),
    dense_budget={"ell.warm": 4, "ell.*": 3},
    notes="The ELL backend is row-form: relax is a masked row-min over "
          "the padded in-neighbourhood (gather + reduce_min; no "
          "scatter at all), which is why its dense budget is the "
          "lowest of the edge-list backends.")
@contract(
    "backend.pallas",
    routes=("pallas.*",),
    require=("pallas_call",),
    dense_budget=8,
    notes="use_pallas=True must actually route through the Pallas "
          "kernels: the hot region must contain pallas_call eqns "
          "(interpret mode on CPU CI still lowers to pallas_call).")
def ell_prims(g: Graph, ell: EllGraph, use_pallas: bool) -> Primitives:
    """Dense padded in-neighbour (ELL) layout.

    Every reduction is one call of the fused relax kernel (row-min over
    the in-neighbourhood of x[src]+w, masked); ``use_pallas=True`` routes
    through the Pallas TPU kernels (kernels/relax.py, segment_min.py),
    otherwise the jnp oracle — same protocol either way.
    """
    from repro.kernels import ops

    zeros = jnp.zeros((g.n,), jnp.float32)

    def relax(x, src_mask):
        return ops.relax_ell(x, ell, src_mask, use_pallas=use_pallas)

    def in_weight_nf(nf_mask):
        return ops.relax_ell(zeros, ell, nf_mask, use_pallas=use_pallas)

    def masked_min(x, mask):
        return ops.masked_min(x, mask, use_pallas=use_pallas)

    return Primitives(relax=relax, in_weight_nf=in_weight_nf,
                      masked_min=masked_min)


@contract(
    "backend.frontier",
    routes=("frontier.*",),
    require=("cumsum", "scatter-min"),
    dense_budget={"frontier.cold": 4, "frontier.targeted": 4,
                  "frontier.batched": 4, "frontier.warm": 6},
    notes="The whole point of this backend is the compacted sparse "
          "relax: the program must contain the cumsum frontier "
          "compaction AND the scatter-min relax — on EVERY route, "
          "batched and warm included (the shared batch frontier of "
          "engine._round_shared; the old dense-under-vmap waiver is "
          "retired).  The budgets count the step-1 dense-relax "
          "fallback branch (2), the Eqn-(1) C-propagation sweep (2: "
          "after the Lemma-7 lift nearly every unfixed vertex's C can "
          "move, so a wavefront-bounded walk covers more slots than "
          "one dense sweep) and the warm taint sweep (2).  inWeight_nf "
          "stays an incremental chunked update with no dense rebuild "
          "(docs/round-anatomy.md).")
def frontier_prims(g: Graph, csr: CsrGraph, cap: int,
                   use_pallas: bool = False) -> Primitives:
    """Sparse-frontier backend: compacted-buffer relax over the CSR view.

    Step-1 D-relaxation gathers only the out-edges of the (at most
    ``cap``) buffered vertices — ``cap * csr.max_out_deg`` edge slots
    instead of ``e_pad`` — through the Pallas scatter-min kernel
    (kernels/frontier_relax) when ``use_pallas``, the jnp oracle
    otherwise.  The batched hooks (``relax_frontier_b`` / ``out_nbrs``
    / ``in_min_at``) switch the engine to ``_round_shared``: one UNION
    frontier per batch and incremental inWeight_nf over the CSC run
    table.  The dense segment primitives remain as the step-1 overflow
    fallback, the Eqn-(1) C-propagation sweep and the init-region
    seeds, which keeps every round bitwise-identical to the segment
    backend.
    """
    from repro.kernels import ops

    base = segment_prims(g)

    def relax_frontier(x, f_idx, src_mask):
        return ops.frontier_relax(x, csr, f_idx, src_mask,
                                  use_pallas=use_pallas)

    def relax_frontier_b(x, f_idx, src_mask):
        return ops.frontier_relax_b(x, csr, f_idx, src_mask,
                                    use_pallas=use_pallas)

    def out_nbrs(idx):
        return ops.out_nbrs(csr, idx)

    def in_min_at(x, tgt, src_mask):
        return ops.in_min_at(g, csr, x, tgt, src_mask)

    return Primitives(relax=base.relax, in_weight_nf=base.in_weight_nf,
                      masked_min=_masked_min_local,
                      relax_frontier=relax_frontier,
                      frontier_cap=int(cap),
                      relax_frontier_b=relax_frontier_b,
                      out_nbrs=out_nbrs, in_min_at=in_min_at)


@contract(
    "backend.distributed",
    routes=("distributed.*",),
    require=("scatter-min", "pmin"),
    dense_budget={"distributed.warm": 8, "distributed.*": 6},
    notes="Shard-local segment relax + cross-shard pmin combine: both "
          "must survive compilation (a missing pmin means the combine "
          "was constant-folded away and shards silently diverge).")
def distributed_prims(lg: Graph, axes: tuple[str, ...]) -> Primitives:
    """Edge-sharded segment reductions inside a ``shard_map`` body.

    ``lg`` is the device-local Graph view (same static metadata, local
    edge block); vertex vectors are replicated, so each device reduces
    its local edges and the mesh combines with `lax.pmin` — the TPU
    analogue of the PRAM's concurrent-min memory.  ``relax2`` stacks the
    two independent reductions into a single pmin all-reduce (§Perf 3.1).
    """
    local = segment_prims(lg)

    def relax(x, src_mask):
        return jax.lax.pmin(local.relax(x, src_mask), axes)

    def in_weight_nf(nf_mask):
        return jax.lax.pmin(local.in_weight_nf(nf_mask), axes)

    def relax2(x, src_mask, nf_mask):
        both = jax.lax.pmin(
            jnp.stack([local.relax(x, src_mask),
                       local.in_weight_nf(nf_mask)]), axes)
        return both[0], both[1]

    # vertex arrays are replicated: the global masked min needs no
    # collective of its own.
    return Primitives(relax=relax, in_weight_nf=in_weight_nf,
                      masked_min=_masked_min_local, relax2=relax2)
