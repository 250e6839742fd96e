"""PageRank: iterative shuffle over ICI.

The reference's second headline benchmark is GraphX PageRank-19GB, 2.01×
faster over 100GbE RoCE (README.md:25-31; BASELINE.md config #3). GraphX
shuffles edge contributions to vertex owners every iteration — the workload
that stresses *repeated* exchange with stable routing.

TPU-native design: vertices are range-sharded over the mesh; edges live on
their source vertex's device. One iteration is one jitted SPMD step:

1. contribution per local edge = rank[src] / out_degree[src] (local gather
   — src is local by construction);
2. ragged exchange of ``(dst, contribution)`` rows to dst's owner device
   (the GraphX shuffle);
3. segment-sum received contributions into local ranks (one scatter-add),
   then ``rank = (1 - d)/V + d * sums``.

Ranks never leave their shard; only contributions move — the same traffic
shape GraphX produces, minus the host.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.parallel.exchange import resolve_impl, shuffle_shard


@dataclass(frozen=True)
class PageRankConfig:
    num_vertices: int          # global, multiple of mesh size
    edges_per_device: int      # local edge capacity (padded)
    damping: float = 0.85
    out_factor: int = 2


def make_pagerank_step(mesh: Mesh, axis_name: str, cfg: PageRankConfig,
                       impl: str = "auto"):
    """One jitted PageRank iteration.

    Per-device inputs (leading axis sharded over ``axis_name``):
      ``edges: i32[D*E, 2]`` — (src, dst) global vertex ids; padding rows
        have src = -1;
      ``ranks: f32[V]`` — vertex ranks, range-sharded (device d owns
        ``[d*V/D, (d+1)*V/D)``);
      ``out_deg: f32[V]`` — out-degrees, sharded identically.

    Returns ``(ranks, overflowed[D])``; ``overflowed[d]`` flags a receive
    buffer too small for the contribution fan-in (results invalid — raise
    ``out_factor``), mirroring the TeraSort/join steps.
    """
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    v_local = cfg.num_vertices // n
    spec = P(axis_name)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=(spec, spec))
    def step(edges, ranks, out_deg):
        me = jax.lax.axis_index(axis_name)
        src, dst = edges[:, 0], edges[:, 1]
        valid = src >= 0
        # local rank lookup: src ids are local to this shard
        src_local = jnp.where(valid, src - me * v_local, 0)
        contrib = jnp.where(valid,
                            ranks[src_local] / jnp.maximum(out_deg[src_local], 1.0),
                            0.0)
        # rows: (dst, contribution bits) — one u32 matrix for the exchange
        rows = jnp.stack([dst.astype(jnp.uint32),
                          jax.lax.bitcast_convert_type(
                              contrib.astype(jnp.float32), jnp.uint32)], axis=1)
        dest_dev = jnp.where(valid, dst // v_local, -1)
        output = jnp.zeros((rows.shape[0] * cfg.out_factor, 2), jnp.uint32)
        received, recv_counts, _, overflowed = shuffle_shard(
            rows, dest_dev, axis_name, n, output=output, impl=impl)
        total = recv_counts.sum()
        rvalid = jnp.arange(received.shape[0], dtype=jnp.int32) < total
        rdst = jnp.where(rvalid,
                         received[:, 0].astype(jnp.int32) - me * v_local, 0)
        rcontrib = jnp.where(
            rvalid,
            jax.lax.bitcast_convert_type(received[:, 1], jnp.float32), 0.0)
        sums = jnp.zeros(v_local, jnp.float32).at[rdst].add(rcontrib)
        new_ranks = (1.0 - cfg.damping) / cfg.num_vertices + cfg.damping * sums
        return new_ranks, overflowed[None]

    return step


def random_graph(cfg: PageRankConfig, num_devices: int, seed: int = 0,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Random directed graph, edges placed on their src's device.
    Returns (edges[D*E, 2], ranks[V], out_deg[V])."""
    rng = np.random.default_rng(seed)
    v_local = cfg.num_vertices // num_devices
    edges = np.full((num_devices * cfg.edges_per_device, 2), -1, dtype=np.int32)
    out_deg = np.zeros(cfg.num_vertices, dtype=np.float32)
    for d in range(num_devices):
        e = rng.integers(0, v_local, size=(cfg.edges_per_device, 2))
        e[:, 0] += d * v_local                          # src local to d
        e[:, 1] = rng.integers(0, cfg.num_vertices,     # dst anywhere
                               size=cfg.edges_per_device)
        lo = d * cfg.edges_per_device
        edges[lo:lo + cfg.edges_per_device] = e
        np.add.at(out_deg, e[:, 0], 1.0)
    ranks = np.full(cfg.num_vertices, 1.0 / cfg.num_vertices, dtype=np.float32)
    return edges, ranks, out_deg


def run_pagerank(mesh: Mesh, cfg: PageRankConfig, iterations: int,
                 axis_name: str = "shuffle", seed: int = 0,
                 impl: str = "auto") -> np.ndarray:
    """Host loop: `iterations` jitted shuffle rounds; returns final ranks."""
    n = mesh.shape[axis_name]
    edges, ranks, out_deg = random_graph(cfg, n, seed)
    step = make_pagerank_step(mesh, axis_name, cfg, impl)
    shard = NamedSharding(mesh, P(axis_name))
    edges_d = jax.device_put(edges, shard)
    ranks_d = jax.device_put(ranks, shard)
    deg_d = jax.device_put(out_deg, shard)
    overflowed = None
    for _ in range(iterations):
        ranks_d, overflowed = step(edges_d, ranks_d, deg_d)
    ranks_h = np.asarray(jax.block_until_ready(ranks_d))
    if overflowed is not None and np.asarray(overflowed).any():
        raise OverflowError(
            "pagerank receive buffer overflow: contribution fan-in exceeds "
            "out_factor headroom; raise PageRankConfig.out_factor")
    return ranks_h


def numpy_pagerank(edges: np.ndarray, num_vertices: int, damping: float,
                   iterations: int) -> np.ndarray:
    """Dense host oracle for correctness checks."""
    valid = edges[:, 0] >= 0
    src, dst = edges[valid, 0], edges[valid, 1]
    out_deg = np.zeros(num_vertices, dtype=np.float64)
    np.add.at(out_deg, src, 1.0)
    ranks = np.full(num_vertices, 1.0 / num_vertices, dtype=np.float64)
    for _ in range(iterations):
        contrib = ranks[src] / np.maximum(out_deg[src], 1.0)
        sums = np.zeros(num_vertices, dtype=np.float64)
        np.add.at(sums, dst, contrib)
        ranks = (1.0 - damping) / num_vertices + damping * sums
    return ranks.astype(np.float32)
