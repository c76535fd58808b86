"""The paper's own architecture: the distributed SSSP engine.

Two dry-run cells beyond the assigned 40 prove the paper's technique
itself shards to the production mesh:

  sssp_web_64m  — n=4M vertices, e=64M edges (web-graph scale):
                  edges sharded over DATA axes, vertex vectors
                  replicated, pmin all-reduces per round.
  sssp_road_16m — n=16M vertices, e=48M edges (road-network: high
                  diameter, many rounds — the worst case for
                  bulk-synchronous SSSP).

Lowering is fully abstract: the edge arrays and the outWeight vertex
vector are jit ARGUMENTS (ShapeDtypeStructs), so no 64M-edge graph is
materialized on this host.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ArchSpec, register
from repro.configs.cells import Cell
from repro.core.graph import Graph
from repro.core.sssp.backends import distributed_prims
from repro.core.sssp.engine import SP4_CONFIG, SSSPConfig, _solve
from repro.distributed.mesh import data_axes

SHAPES = {
    "sssp_web_64m": dict(n=4_000_000, e=64_000_000, max_rounds=512),
    "sssp_road_16m": dict(n=16_000_000, e=48_000_000, max_rounds=4096),
}

FULL = SP4_CONFIG
SMOKE = SSSPConfig(max_rounds=64)


def build_cell(cfg: SSSPConfig, shape: str) -> Cell:
    info = SHAPES[shape]
    n, e = info["n"], info["e"]

    def lower(mesh: Mesh):
        axes = data_axes(mesh)
        import numpy as np
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
        e_pad = -(-e // (n_shards * 128)) * (n_shards * 128)
        e_loc = e_pad // n_shards
        max_rounds = info["max_rounds"]

        def body(src, dst, w, out_weight):
            zeros = jnp.zeros((n,), jnp.float32)
            lg = Graph(n=n, e=e, e_pad=e_loc, src=src, dst=dst, w=w,
                       in_deg=zeros, out_deg=zeros, in_weight=zeros,
                       out_weight=out_weight)
            run_cfg = dataclasses.replace(cfg, max_rounds=max_rounds)
            state = _solve(lg, run_cfg, 0,
                           prims=distributed_prims(lg, axes))
            return state.D, state.C, state.round

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axes), P(axes), P(axes), P()),
            out_specs=(P(), P(), P()), check_vma=False)
        shapes = (jax.ShapeDtypeStruct((e_pad,), jnp.int32),
                  jax.ShapeDtypeStruct((e_pad,), jnp.int32),
                  jax.ShapeDtypeStruct((e_pad,), jnp.float32),
                  jax.ShapeDtypeStruct((n,), jnp.float32))
        in_sh = (NamedSharding(mesh, P(axes)),) * 3 + (
            NamedSharding(mesh, P()),)
        return jax.jit(fn, in_shardings=in_sh).lower(*shapes)

    # per round: ~4 segment ops over e edges (~6 flops each) x est rounds
    return Cell(arch="sssp", shape=shape, kind="sssp", lower=lower,
                model_flops=6.0 * e * 4, tokens=n,
                notes="paper-core distributed cell")


ARCH = register(ArchSpec(
    name="sssp", kind="sssp", full=FULL, smoke=SMOKE,
    shapes=tuple(SHAPES), build_cell=build_cell,
    notes="the paper's engine on the production mesh",
))
