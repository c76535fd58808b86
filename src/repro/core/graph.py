"""Device-ready graph container for the SSSP engine and GNN substrate.

Design (see DESIGN.md §2):
  * The paper (Garg 2018) assumes access to *incoming* edges (its assumption
    #2).  We therefore store the edge list sorted by **destination** (CSC
    order) as the primary form: every per-round operation of the SSSP engine
    ("for each edge, combine a value at src, min/sum-reduce at dst") is a
    segment reduction over `dst`.
  * Arrays are padded to a fixed size so shapes are static under jit.
    Padding edges use ``src = dst = n`` and ``w = +inf``; vertex-segment
    reductions use ``num_segments = n + 1`` and slice off the sentinel row.
  * An optional dense ELL ("padded in-neighbour") form `in_src/in_w` of shape
    ``[n_pad, deg_pad]`` feeds the Pallas relax kernel (row-min over the
    in-neighbourhood is a dense, VPU-aligned reduction).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.float32(jnp.inf)


def _pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full((size,) + x.shape[1:], fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """Static, padded, dst-sorted edge-list graph.

    Fields with leading dim ``e_pad`` are edge arrays (dst-sorted); fields
    with leading dim ``n`` are vertex arrays.  ``n``/``e`` are static python
    ints (pytree aux data) so they can drive shapes under jit.
    """

    # --- static metadata ---
    n: int = dataclasses.field(metadata=dict(static=True))
    e: int = dataclasses.field(metadata=dict(static=True))
    e_pad: int = dataclasses.field(metadata=dict(static=True))

    # --- edge arrays, sorted by dst; padding: src=dst=n, w=inf ---
    src: jax.Array  # int32[e_pad]
    dst: jax.Array  # int32[e_pad]
    w: jax.Array    # float32[e_pad]

    # --- static per-vertex derived arrays ---
    in_deg: jax.Array      # int32[n]  number of incoming edges
    out_deg: jax.Array     # int32[n]
    in_weight: jax.Array   # float32[n]  min incoming edge weight (inf if none)
    out_weight: jax.Array  # float32[n]  min outgoing edge weight (inf if none)

    @property
    def num_segments(self) -> int:
        return self.n + 1  # one sentinel row for padding edges

    # --- the three segment primitives every engine round uses ---
    def seg_min_at_dst(self, edge_vals: jax.Array) -> jax.Array:
        """min-reduce edge values at their destination vertex -> float32[n]."""
        out = jax.ops.segment_min(
            edge_vals, self.dst, num_segments=self.num_segments,
            indices_are_sorted=True)
        return out[: self.n]

    def seg_max_at_dst(self, edge_vals: jax.Array) -> jax.Array:
        out = jax.ops.segment_max(
            edge_vals, self.dst, num_segments=self.num_segments,
            indices_are_sorted=True)
        return out[: self.n]

    def seg_sum_at_dst(self, edge_vals: jax.Array) -> jax.Array:
        out = jax.ops.segment_sum(
            edge_vals, self.dst, num_segments=self.num_segments,
            indices_are_sorted=True)
        return out[: self.n]

    def gather_src(self, vertex_vals: jax.Array, fill=INF) -> jax.Array:
        """Gather a vertex array at edge sources; padding edges get `fill`."""
        ext = jnp.concatenate(
            [vertex_vals, jnp.full((1,), fill, vertex_vals.dtype)])
        return ext[self.src]

    def gather_dst(self, vertex_vals: jax.Array, fill=INF) -> jax.Array:
        ext = jnp.concatenate(
            [vertex_vals, jnp.full((1,), fill, vertex_vals.dtype)])
        return ext[self.dst]

    def apply_delta(self, delta) -> "Graph":
        """New Graph with a batch of edge-weight updates applied.

        ``delta`` is a :class:`repro.core.sssp.dynamic.GraphDelta`
        (duck-typed): ``edge_idx`` int32[k_pad] indexes THIS graph's
        dst-sorted edge arrays (padding rows use ``edge_idx >= e_pad``
        and are scatter-dropped), ``new_w`` float32[k_pad] the new
        weights.  Topology (src/dst/degrees) is unchanged; the derived
        ``in_weight``/``out_weight`` minima are recomputed so every
        engine rule keeps seeing coherent per-vertex bounds.  jit-safe:
        static shapes, no retrace when only the delta values change.

        Weights must stay strictly positive (the builder's invariant);
        concrete (non-traced) deltas are validated loudly here, traced
        ones must be validated at construction (``make_delta`` does).
        """
        _validate_delta_weights(delta)
        w = self.w.at[delta.edge_idx].set(delta.new_w, mode="drop")
        in_weight = jax.ops.segment_min(
            w, self.dst, num_segments=self.num_segments,
            indices_are_sorted=True)[: self.n]
        out_weight = jax.ops.segment_min(
            w, self.src, num_segments=self.num_segments)[: self.n]
        return dataclasses.replace(
            self, w=w, in_weight=in_weight, out_weight=out_weight)

    def to_host(self) -> "HostGraph":
        """Host adjacency view of the REAL (non-padding) edges — the
        inverse of ``HostGraph.to_device()``; reference algorithms check
        mutated graphs through this."""
        e = self.e
        return HostGraph(self.n, np.asarray(self.src[:e]),
                         np.asarray(self.dst[:e]), np.asarray(self.w[:e]))

    def reverse(self, **kw) -> "Graph":
        """The transpose graph: every edge (u, v, w) becomes (v, u, w).

        Distances from L on ``reverse()`` are distances TO L on the
        original — the d(·, L) half of the landmark (ALT) tables.  The
        edge list is re-sorted by the new destinations, so forward edge
        ``i`` lands at position ``argsort(src, stable)⁻¹[i]`` of the
        reverse list (sssp/landmarks.py precomputes that permutation to
        remap :class:`GraphDelta` batches).  Preprocessing-time only —
        builds host-side.
        """
        e = self.e
        return build_graph(self.n, np.asarray(self.dst[:e]),
                           np.asarray(self.src[:e]),
                           np.asarray(self.w[:e]), **kw)

    def csr(self) -> "CsrGraph":
        """Src-sorted (CSR) out-edge view for the frontier backend.

        The primary layout is dst-sorted (CSC) because every dense round
        reduces *at destinations*; the sparse-frontier round instead
        walks the *out*-edges of a handful of vertices, which needs
        contiguous per-source runs.  Preprocessing-time only — builds
        host-side; weight updates ride :meth:`CsrGraph.apply_delta`
        through the same :class:`~repro.core.sssp.dynamic.GraphDelta`
        (``csr_pos`` is the dst-sorted→src-sorted edge permutation,
        precomputed by ``make_delta``).
        """
        return build_csr(self)


def _validate_delta_weights(delta) -> None:
    """Loudly reject non-positive/NaN update weights (post-construction
    mutation must keep the builder's ``w > 0`` invariant).  ALL rows are
    checked, padding included — ``make_delta`` pads with 1.0, and
    requiring positive fill keeps the Graph and EllGraph layouts'
    validity judgments identical for any duck-typed delta.  Skipped for
    traced values — the compiled dynamic-update path validates at
    ``GraphDelta`` construction instead."""
    if isinstance(delta.new_w, jax.core.Tracer):
        return
    new_w = np.asarray(delta.new_w)
    if new_w.size and not (np.isfinite(new_w).all() and (new_w > 0).all()):
        raise ValueError(
            "apply_delta: update weights must be strictly positive and "
            f"finite (got min={new_w.min()!r}, padding rows included); "
            "the engine's fixing rules assume w > 0")


def build_graph(n: int, src, dst, w, *, edge_pad_multiple: int = 128) -> Graph:
    """Build a device-ready Graph from numpy COO arrays (host-side)."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    w = np.asarray(w, np.float32)
    e = int(src.shape[0])
    if e:
        assert src.min() >= 0 and src.max() < n, "src out of range"
        assert dst.min() >= 0 and dst.max() < n, "dst out of range"
        assert (w > 0).all(), "paper assumes strictly positive weights"
        assert (src != dst).all(), "paper assumes loop-free graphs"
    # dst-sorted (CSC order); stable so parallel edges keep input order.
    order = np.argsort(dst, kind="stable")
    src, dst, w = src[order], dst[order], w[order]

    e_pad = max(edge_pad_multiple, round_up(max(e, 1), edge_pad_multiple))
    src_p = _pad_to(src, e_pad, n)
    dst_p = _pad_to(dst, e_pad, n)
    w_p = _pad_to(w, e_pad, np.inf)

    in_deg = np.bincount(dst, minlength=n).astype(np.int32)
    out_deg = np.bincount(src, minlength=n).astype(np.int32)
    in_weight = np.full(n, np.inf, np.float32)
    np.minimum.at(in_weight, dst, w)
    out_weight = np.full(n, np.inf, np.float32)
    np.minimum.at(out_weight, src, w)

    return Graph(
        n=n, e=e, e_pad=e_pad,
        src=jnp.asarray(src_p), dst=jnp.asarray(dst_p), w=jnp.asarray(w_p),
        in_deg=jnp.asarray(in_deg), out_deg=jnp.asarray(out_deg),
        in_weight=jnp.asarray(in_weight), out_weight=jnp.asarray(out_weight),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CsrGraph:
    """Src-sorted out-edge (CSR) view for the sparse-frontier backend.

    ``indptr[u] : indptr[u+1]`` is vertex u's contiguous run of
    out-edges in the src-sorted ``dst``/``w`` arrays (real edges only —
    offsets live in ``[0, e]``; the tail up to ``e_pad`` is padding with
    ``dst = n``, ``w = +inf``).  ``max_out_deg`` bounds the per-vertex
    gather width, so a compacted frontier of ``cap`` vertices touches at
    most ``cap * max_out_deg`` edge slots per round — wavefront-
    proportional, never graph-proportional.

    ``in_indptr`` is the symmetric CSC run table: the primary ``Graph``
    edge arrays are dst-sorted already, so vertex v's in-edges are the
    contiguous run ``g.src/g.w[in_indptr[v] : in_indptr[v+1]]``.  No
    second copy of the weights is needed — the CSC gathers read the
    primary arrays, which ``Graph.apply_delta`` keeps current, so the
    view is GraphDelta-coherent for free.  ``max_in_deg`` is the
    longest such run.

    Registered as a pytree (sizes static) so it rides through jit /
    ``lax.while_loop`` as a traced operand like ``Graph``/``EllGraph``.
    """

    n: int = dataclasses.field(metadata=dict(static=True))
    e: int = dataclasses.field(metadata=dict(static=True))
    e_pad: int = dataclasses.field(metadata=dict(static=True))
    max_out_deg: int = dataclasses.field(metadata=dict(static=True))
    max_in_deg: int = dataclasses.field(metadata=dict(static=True))
    indptr: jax.Array    # int32[n + 1] out-edge run offsets (CSR)
    dst: jax.Array       # int32[e_pad] src-sorted edge heads (padding: n)
    w: jax.Array         # float32[e_pad] src-sorted weights (padding: inf)
    in_indptr: jax.Array  # int32[n + 1] in-edge run offsets into g.src/g.w

    def apply_delta(self, delta) -> "CsrGraph":
        """The same weight updates ``Graph.apply_delta`` applies, landed
        at the src-sorted positions (``delta.csr_pos``, precomputed by
        ``make_delta``; padding rows are out-of-bounds and scatter-
        dropped).  Keeping the CSR view coherent with the CSC list is
        what lets the frontier backend re-solve incrementally."""
        _validate_delta_weights(delta)
        if getattr(delta, "csr_pos", None) is None:
            raise ValueError(
                "delta carries no csr_pos permutation; build it via "
                "make_delta/make_delta_from_endpoints against the "
                "current graph to update a CsrGraph")
        w = self.w.at[delta.csr_pos].set(delta.new_w, mode="drop")
        return dataclasses.replace(self, w=w)


def build_csr(g: Graph) -> CsrGraph:
    """Host-side CSR (out-edge) view of a device Graph."""
    e = g.e
    src = np.asarray(g.src[:e])
    dst = np.asarray(g.dst[:e])
    w = np.asarray(g.w[:e])
    order = np.argsort(src, kind="stable")  # csr_perm: dst-sorted -> CSR
    out_deg = np.bincount(src, minlength=g.n).astype(np.int64)
    indptr = np.zeros(g.n + 1, np.int32)
    np.cumsum(out_deg, out=indptr[1:])
    in_deg = np.bincount(dst, minlength=g.n).astype(np.int64)
    in_indptr = np.zeros(g.n + 1, np.int32)
    np.cumsum(in_deg, out=in_indptr[1:])
    return CsrGraph(
        n=g.n, e=e, e_pad=g.e_pad,
        max_out_deg=max(int(out_deg.max()) if e else 0, 1),
        max_in_deg=max(int(in_deg.max()) if e else 0, 1),
        indptr=jnp.asarray(indptr),
        dst=jnp.asarray(_pad_to(dst[order].astype(np.int32), g.e_pad, g.n)),
        w=jnp.asarray(_pad_to(w[order].astype(np.float32), g.e_pad,
                              np.inf)),
        in_indptr=jnp.asarray(in_indptr))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EllGraph:
    """Dense padded in-neighbour (ELL) form for the Pallas relax kernel.

    ``in_src[i, j]`` is the j-th in-neighbour of vertex i (or ``n`` padding),
    ``in_w[i, j]`` the corresponding weight (or +inf).  Rows are padded to
    ``deg_pad`` (multiple of 128 lanes) and vertices to ``n_pad`` (multiple
    of 8 sublanes) so blocks tile the TPU VPU exactly.

    Registered as a pytree (sizes static) so the ELL engine backend runs
    inside ``jit``/``lax.while_loop`` like every other backend.
    """

    n: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    deg_pad: int = dataclasses.field(metadata=dict(static=True))
    in_src: jax.Array  # int32[n_pad, deg_pad]
    in_w: jax.Array    # float32[n_pad, deg_pad]

    def apply_delta(self, delta) -> "EllGraph":
        """New EllGraph with the same weight updates ``Graph.apply_delta``
        applies — the dense layout's cell for edge i is ``(dst[i], rank
        of i within its dst segment)``, precomputed by ``make_delta`` as
        ``ell_row``/``ell_col`` (padding rows are out-of-bounds and
        scatter-dropped).  Keeping both layouts updated by ONE delta is
        what lets the ell/pallas backends re-solve incrementally without
        a host-side rebuild."""
        _validate_delta_weights(delta)
        in_w = self.in_w.at[delta.ell_row, delta.ell_col].set(
            delta.new_w, mode="drop")
        return dataclasses.replace(self, in_w=in_w)


def build_ell(n: int, src, dst, w, *, lane: int = 128, sublane: int = 8,
              max_deg_cap: int | None = None) -> EllGraph:
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float32)
    in_deg = np.bincount(dst, minlength=n)
    max_deg = int(in_deg.max()) if len(dst) else 0
    if max_deg_cap is not None and max_deg > max_deg_cap:
        raise ValueError(
            f"max in-degree {max_deg} exceeds ELL cap {max_deg_cap}; "
            "use the edge-list (segment-op) path for power-law graphs")
    deg_pad = max(lane, round_up(max(max_deg, 1), lane))
    n_pad = max(sublane, round_up(n, sublane))
    in_src = np.full((n_pad, deg_pad), n, np.int32)
    in_w = np.full((n_pad, deg_pad), np.inf, np.float32)
    # stable sort by destination, then each edge's slot is its rank
    # within its destination's run (input order among parallel edges)
    order = np.argsort(dst, kind="stable")
    d = dst[order]
    run_start = np.cumsum(in_deg) - in_deg
    slot = np.arange(len(d)) - run_start[d]
    in_src[d, slot] = src[order]
    in_w[d, slot] = w[order]
    return EllGraph(n=n, n_pad=n_pad, deg_pad=deg_pad,
                    in_src=jnp.asarray(in_src), in_w=jnp.asarray(in_w))


# ---------------------------------------------------------------------------
# Host-side adjacency view for the sequential reference algorithms.
# ---------------------------------------------------------------------------

class HostGraph:
    """Host COO edge arrays, with plain-python adjacency views (out- and
    in-lists) for the reference algorithms.

    The lists are built on first use: a graph that only travels to the
    device (``to_device``/``to_ell``) never pays the per-edge Python loop.
    """

    def __init__(self, n: int, src, dst, w):
        self.n = int(n)
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.w = np.asarray(w, np.float64)
        self.e = len(self.src)
        assert (self.w > 0).all(), "strictly positive weights required"

    @functools.cached_property
    def out(self) -> list[list[tuple[int, float]]]:
        return self._adjacency(self.src, self.dst)

    @functools.cached_property
    def inn(self) -> list[list[tuple[int, float]]]:
        return self._adjacency(self.dst, self.src)

    def _adjacency(self, key, other) -> list[list[tuple[int, float]]]:
        lists: list[list[tuple[int, float]]] = [[] for _ in range(self.n)]
        for k, o, ww in zip(key.tolist(), other.tolist(), self.w.tolist()):
            lists[k].append((o, ww))
        return lists

    def to_device(self, **kw) -> Graph:
        return build_graph(self.n, self.src, self.dst, self.w, **kw)

    def to_ell(self, **kw) -> EllGraph:
        return build_ell(self.n, self.src, self.dst, self.w, **kw)

    def reverse(self) -> "HostGraph":
        """The transpose graph (edges flipped, weights kept)."""
        return HostGraph(self.n, self.dst, self.src, self.w)
