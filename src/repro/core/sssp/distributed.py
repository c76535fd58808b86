"""Multi-device / multi-pod SSSP: edge-sharded shard_map engine.

Mapping of the paper's PRAM model onto the TPU mesh (DESIGN.md §2/§5):

  * Edge arrays (src, dst, w) are sharded over the mesh's data axes —
    each device owns a contiguous block of the dst-sorted edge list.
  * Vertex vectors (D, C, fixed) are replicated; each round every device
    computes its local segment reductions and the mesh combines them with
    `lax.pmin` / `pmax` (an all-reduce with MIN — the concurrent-min
    memory of the CRCW PRAM, in ICI collectives).
  * The whole while_loop runs inside one shard_map call, so rounds need
    no host round-trips and XLA can schedule the pmin of round r against
    the gathers of round r (compute/comm overlap).

The round body itself is ``engine._round`` — this module only supplies
the edge-sharded backend primitives (backends.distributed_prims) and the
shard_map plumbing.  Batched multi-source solves put the `jax.vmap` over
sources *inside* the shard_map body: vertex state is replicated, so the
per-round pmin simply reduces [B, n] blocks instead of [n].

For graphs whose vertex vectors outgrow a chip (≥1e8 vertices) the
vertex axis would additionally be sharded over `model`; that variant is
exercised by the dry-run configs in configs/sssp_*.py.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import Graph, INF, round_up
from repro.core.sssp.backends import distributed_prims
from repro.core.sssp.engine import SSSPConfig, SP4_CONFIG, _solve


def shard_graph_edges(g: Graph, n_shards: int) -> Graph:
    """Re-pad edge arrays so e_pad divides evenly across shards."""
    e_pad = round_up(g.e_pad, n_shards * 128)
    if e_pad == g.e_pad:
        return g
    pad = e_pad - g.e_pad
    return dataclasses.replace(
        g, e_pad=e_pad,
        src=jnp.concatenate([g.src, jnp.full((pad,), g.n, g.src.dtype)]),
        dst=jnp.concatenate([g.dst, jnp.full((pad,), g.n, g.dst.dtype)]),
        w=jnp.concatenate([g.w, jnp.full((pad,), INF, g.w.dtype)]),
    )


def default_mesh() -> tuple[Mesh, tuple[str, ...]]:
    return Mesh(np.asarray(jax.devices()).reshape(-1), ("data",)), ("data",)


def make_sharded_solver(g: Graph, cfg: SSSPConfig = SP4_CONFIG,
                        mesh: Mesh | None = None,
                        axes: tuple[str, ...] = ("data",),
                        on_trace=None):
    """Build (sharded_graph, jitted batched solve) for the Solver facade.

    The returned callable maps ``sources: int32[B] -> SSSPState`` with
    batched (leading-B) state arrays; sources are replicated over the
    mesh and vmapped inside the shard_map body.  ``on_trace`` (if given)
    is called once per XLA trace — the Solver's retrace counter.
    """
    if mesh is None:
        mesh, axes = default_mesh()
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    g = shard_graph_edges(g, n_shards)
    edge_spec = P(axes)          # shard edge arrays along the flat data axes
    vert_spec = P()              # vertex arrays (and sources) replicated

    def body(src, dst, w, out_weight, sources, targets, C0):
        if on_trace is not None:
            on_trace()
        # a device-local Graph view: same static metadata, local edge
        # block.  out_weight is an OPERAND (not the closed-over g's):
        # the dynamic subsystem re-solves on mutated weights, and a
        # stale out_weight would let the R_out rule fix too early.
        lg = dataclasses.replace(
            g, e_pad=g.e_pad // n_shards, src=src, dst=dst, w=w,
            out_weight=out_weight)
        prims = distributed_prims(lg, axes)
        return jax.vmap(
            lambda s, t, c: _solve(lg, cfg, s, prims=prims, C0=c, target=t)
        )(sources, targets, C0)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec) + (vert_spec,) * 4,
        out_specs=vert_spec, check_vma=False)
    jitted = jax.jit(fn)

    def solve_batch(sources: jax.Array, graph: Graph | None = None,
                    targets=None, C0=None):
        # ``graph`` lets callers solve on a NEWER version of the same
        # shape (the dynamic subsystem mutates weights between solves);
        # default is the build-time graph.  ``targets``/``C0`` are the
        # goal-directed operands (replicated, like the vertex state):
        # -1 sentinel = untargeted lane, zeros = trivial lower bounds.
        gg = g if graph is None else graph
        sources = jnp.asarray(sources, jnp.int32)
        b = sources.shape[0]
        if targets is None:
            targets = jnp.full((b,), -1, jnp.int32)
        if C0 is None:
            C0 = jnp.zeros((b, g.n), jnp.float32)
        return jitted(gg.src, gg.dst, gg.w, gg.out_weight, sources,
                      jnp.asarray(targets, jnp.int32),
                      jnp.asarray(C0, jnp.float32))

    return g, solve_batch


def make_sharded_warm(g: Graph, cfg: SSSPConfig = SP4_CONFIG,
                      mesh: Mesh | None = None,
                      axes: tuple[str, ...] = ("data",), on_trace=None):
    """Edge-sharded warm update+re-solve program (sssp/dynamic.py).

    Returns a callable ``(g_old, ell_unused, csr_unused, delta,
    prev_D[B, n], prev_fixed[B, n]) -> (g_new, None, None, states,
    sweeps, tainted)`` matching ``DynamicSolver._warm_program``.  The delta application and
    the per-source taint *seeds* (which need global-index gathers into
    the old edge arrays) run at the jit level outside ``shard_map``;
    taint *propagation* and the warm rounds run inside it, against the
    same ``distributed_prims`` the cold path uses — the warm while_loop
    is the cold while_loop with a different entry state.

    ``g_old`` must be the shard-padded graph ``make_sharded_solver``
    returned (same static shape as ``g``).
    """
    from repro.core.sssp.engine import _solve_warm, delta_taint_seeds

    if mesh is None:
        mesh, axes = default_mesh()
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    assert g.e_pad % n_shards == 0, "graph must be shard-padded"
    edge_spec, vert_spec = P(axes), P()

    def body(src, dst, w, out_weight, seeds, pure_inc, prev_D, prev_F):
        if on_trace is not None:
            on_trace()
        lg = dataclasses.replace(
            g, e_pad=g.e_pad // n_shards, src=src, dst=dst, w=w,
            out_weight=out_weight)
        prims = distributed_prims(lg, axes)
        return jax.vmap(
            lambda D0, f0, s, p: _solve_warm(lg, cfg, D0, f0, s, p,
                                             prims=prims)
        )(prev_D, prev_F, seeds, pure_inc)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(edge_spec, edge_spec, edge_spec) + (vert_spec,) * 5,
        out_specs=vert_spec, check_vma=False)

    @jax.jit
    def warm(g_old: Graph, _ell, _csr, delta, prev_D, prev_F):
        g_new = g_old.apply_delta(delta)
        seeds, pure = jax.vmap(
            lambda D0: delta_taint_seeds(g_old, delta, D0))(prev_D)
        states, sweeps, taint = sharded(
            g_new.src, g_new.dst, g_new.w, g_new.out_weight,
            seeds, pure, prev_D, prev_F)
        return g_new, None, None, states, sweeps, jnp.sum(taint, axis=1)

    return warm


def run_sssp_distributed(g: Graph, source: int = 0,
                         cfg: SSSPConfig = SP4_CONFIG,
                         mesh: Mesh | None = None,
                         axes: tuple[str, ...] = ("data",)):
    """Run the engine with edges sharded over `axes` of `mesh`.

    Compatibility shim (prefer ``repro.sssp.Solver(backend="distributed")``).
    Returns (D, C, fixed, rounds) — bitwise identical to the single-device
    engine (min is associative and the edge partition is disjoint).
    """
    _, solve_batch = make_sharded_solver(g, cfg, mesh, axes)
    state = solve_batch(jnp.asarray([source], jnp.int32))
    return state.D[0], state.C[0], state.fixed[0], state.round[0]


def lower_distributed(g: Graph, mesh: Mesh, source: int = 0,
                      cfg: SSSPConfig = SP4_CONFIG,
                      axes: tuple[str, ...] = ("data",)):
    """Lower (no execute) for the dry-run: returns jax.stages.Lowered."""
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    g = shard_graph_edges(g, n_shards)
    edge_spec, vert_spec = P(axes), P()

    def body(src, dst, w):
        lg = dataclasses.replace(
            g, e_pad=g.e_pad // n_shards, src=src, dst=dst, w=w)
        state = _solve(lg, cfg, source, prims=distributed_prims(lg, axes))
        return state.D, state.C, state.fixed, state.round

    fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(edge_spec, edge_spec, edge_spec),
                   out_specs=(vert_spec,) * 4, check_vma=False)
    shapes = (jax.ShapeDtypeStruct((g.e_pad,), jnp.int32),
              jax.ShapeDtypeStruct((g.e_pad,), jnp.int32),
              jax.ShapeDtypeStruct((g.e_pad,), jnp.float32))
    in_shardings = tuple(NamedSharding(mesh, edge_spec) for _ in range(3))
    return jax.jit(fn, in_shardings=in_shardings).lower(*shapes)
