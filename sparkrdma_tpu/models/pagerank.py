"""PageRank: iterative shuffle over ICI.

The reference's second headline benchmark is GraphX PageRank-19GB, 2.01×
faster over 100GbE RoCE (README.md:25-31; BASELINE.md config #3). GraphX
shuffles edge contributions to vertex owners every iteration — the workload
that stresses *repeated* exchange with stable routing.

TPU-native design: vertices are range-sharded over the mesh; edges live on
their source vertex's device. One iteration is one jitted SPMD step:

1. contribution per local edge: the per-vertex divide ``rank /
   out_degree`` makes one table, and one per-edge gather reads it at
   ``src`` (local by construction): one random read an edge;
2. ragged exchange of ``(dst, contribution)`` rows to dst's owner device
   (the GraphX shuffle);
3. segment-sum received contributions into local ranks (one scatter-add
   in a loop over the receive buffer's chunks that hold them), then
   ``rank = (1 - d)/V + d * sums``.

Ranks never leave their shard; only contributions move — the same traffic
shape GraphX produces, minus the host.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.row_permute import forms_label
from sparkrdma_tpu.parallel.exchange import (
    record_capacity,
    record_exchange,
    resolve_impl,
    row_mover,
    shuffle_records_shard,
)
from sparkrdma_tpu.utils import trace


@dataclass(frozen=True)
class PageRankConfig:
    num_vertices: int          # global, multiple of mesh size
    edges_per_device: int      # local edge capacity (padded)
    damping: float = 0.85
    out_factor: int = 2


_ACCUMULATE_CHUNKS = 32   # chunks a receive buffer is cut into
_ACCUMULATE_TILE = 1024   # a chunk is whole tiles of this many slots


def accumulate_chunk(capacity: int) -> int:
    """Receive slots one trip of ``pagerank.accumulate``'s loop reads: a
    32nd of ``capacity`` rounded up to whole 1,024-slot tiles, and never
    past ``capacity`` (1,049,600 of 33,554,560 at the cells' size)."""
    per = -(-capacity // _ACCUMULATE_CHUNKS)
    return min(capacity, -(-per // _ACCUMULATE_TILE) * _ACCUMULATE_TILE)


def make_pagerank_step(mesh: Mesh, axis_name: str, cfg: PageRankConfig,
                       impl: str = "auto"):
    """One jitted PageRank iteration (a *superstep*).

    Per-device inputs (leading axis sharded over ``axis_name``):
      ``edges: i32[D*E, 2]`` — (src, dst) global vertex ids; padding rows
        have src = -1;
      ``ranks: f32[V]`` — vertex ranks, range-sharded (device d owns
        ``[d*V/D, (d+1)*V/D)``);
      ``out_deg: f32[V]`` — out-degrees, sharded identically.

    Returns ``(ranks, received[D, 2], overflowed[D])``: ``received[d]``
    is the number of contributions device d was sent and the number of
    records they came as (the exchange's fill included);
    ``overflowed[d]`` flags a receive buffer too
    small for that fan-in (results invalid — raise ``out_factor``),
    mirroring the TeraSort/join steps.

    The shuffle's record is 8 bytes, ``(u32 dst, f32 contribution)``,
    rows ``u32[E, 2]`` through ``exchange.shuffle_records_shard``: narrow
    rows, so 64 records of one destination travel as one
    wire row of 128 lanes (``exchange.pack_exchange_shard``). The fill
    record the exchange asks for is ``(the destination's first vertex,
    0.0)``, which adds nothing where it lands, so the receiver needs no
    count of them.

    A device profile names the step's three phases by scope:
    ``pagerank.contrib`` (one per-edge gather of ``rank / out_degree``
    and the per-vertex divide that makes that table),
    ``pagerank.exchange`` (grouping, with its ``row_sort``, and the
    transport) and ``pagerank.accumulate`` (a loop over the chunks of
    ``accumulate_chunk`` slots that hold the ``total`` records received,
    masking and scatter-adding one a trip; then the damping).
    ``step.row_moves`` lists the form the grouping's row move
    took (``ops.row_permute``), once the step has been traced.
    """
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    v_local = cfg.num_vertices // n
    spec = P(axis_name)
    # the form the grouping's row move took, filled while the step is
    # traced (ops.row_permute): step.row_moves
    row_moves: list = []
    move = row_mover(mesh, row_moves)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=(spec, spec, spec))
    def step(edges, ranks, out_deg):
        me = jax.lax.axis_index(axis_name)
        with jax.named_scope("pagerank.contrib"):
            src, dst = edges[:, 0], edges[:, 1]
            valid = src >= 0
            # local rank lookup: src ids are local to this shard
            src_local = jnp.where(valid, src - me * v_local, 0)
            # the quotient once a vertex (the float32 division an edge
            # would make), then ONE random read an edge
            share = ranks / jnp.maximum(out_deg, 1.0)
            contrib = jnp.where(valid, share[src_local], 0.0)
            dest_dev = jnp.where(valid, dst // v_local, -1)
        with jax.named_scope("pagerank.exchange"):
            # rows: (dst, contribution bits) — one u32 matrix to group
            rows = jnp.stack([
                dst.astype(jnp.uint32),
                jax.lax.bitcast_convert_type(contrib.astype(jnp.float32),
                                             jnp.uint32)], axis=1)
            devices = jnp.arange(n, dtype=jnp.uint32)
            fill = jnp.stack([devices * v_local, jnp.zeros_like(devices)],
                             axis=1)
            received, recv_counts, contributions, overflowed = \
                shuffle_records_shard(rows, dest_dev, fill, axis_name, n,
                                      cfg.out_factor, impl, move)
        with jax.named_scope("pagerank.accumulate"):
            total = recv_counts.sum()
            cap = received.shape[0]
            chunk = accumulate_chunk(cap)
            iota = jnp.arange(chunk, dtype=jnp.int32)

            def add_chunk(c, sums):
                # the last chunk's start is clamped to cap - chunk: mask by
                # the slot it read, so the overlap is not counted twice
                first = c * chunk
                start = jnp.minimum(first, cap - chunk)
                slot = start + iota
                rvalid = (slot >= first) & (slot < total)
                part = jax.lax.dynamic_slice_in_dim(received, start, chunk)
                rdst = jnp.where(
                    rvalid, part[:, 0].astype(jnp.int32) - me * v_local, 0)
                rcontrib = jnp.where(
                    rvalid,
                    jax.lax.bitcast_convert_type(part[:, 1], jnp.float32),
                    0.0)
                return sums.at[rdst].add(rcontrib)

            # the records arrived as the prefix [0, total): one scatter-add
            # a chunk, over the chunks that hold them and no further
            sums = jax.lax.fori_loop(0, (total + chunk - 1) // chunk,
                                     add_chunk, jnp.zeros_like(ranks))
            new_ranks = ((1.0 - cfg.damping) / cfg.num_vertices
                         + cfg.damping * sums)
        return (new_ranks,
                jnp.stack([contributions, total]).astype(jnp.int32)[None],
                overflowed[None])

    step.row_moves = row_moves
    return step


def _initial_ranks(num_vertices: int) -> np.ndarray:
    return np.full(num_vertices, 1.0 / num_vertices, dtype=np.float32)


def random_graph(cfg: PageRankConfig, num_devices: int, seed: int = 0,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Random directed graph with *uniform* endpoints (no real graph has
    them: the tests' small fixture; ``powerlaw_graph`` is the web's
    shape), edges placed on their src's device.
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
    return edges, _initial_ranks(cfg.num_vertices), out_deg


_GRAPH_CHUNK = 1 << 20   # edges a generator task draws; part of the seeding


def powerlaw_graph(cfg: PageRankConfig, num_devices: int, seed: int = 0,
                   zipf_s: float = 0.9,
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Seeded directed graph whose in-links follow a power law, edges
    placed on their src's device. Returns what ``random_graph`` returns.

    Sources are uniform over the device's own vertices. Targets are drawn
    from a Zipf over all ``V`` vertices *bounded to* ``V``: the inverse
    CDF of the cumulative weights ``k^-zipf_s``, k = 1..V
    (``np.random.zipf`` is unbounded and needs s > 1). A seeded
    permutation of the vertex ids then spreads the hubs over the id range,
    and so over the devices. Vectorised, in chunks of ``_GRAPH_CHUNK``
    edges on a few threads (numpy's generators and ``searchsorted``
    release the interpreter lock): each chunk has a generator of its own,
    seeded by ``(seed, device, chunk)``, so the graph does not depend on
    the number of threads."""
    num_v, per_dev = cfg.num_vertices, cfg.edges_per_device
    v_local = num_v // num_devices
    cdf = np.cumsum(np.arange(1, num_v + 1, dtype=np.float64) ** -zipf_s)
    cdf /= cdf[-1]
    perm = np.random.default_rng([seed, num_v]).permutation(
        num_v).astype(np.int32)
    edges = np.empty((num_devices * per_dev, 2), dtype=np.int32)

    def draw(task) -> None:
        d, lo = task
        hi = min(lo + _GRAPH_CHUNK, per_dev)
        rng = np.random.default_rng([seed, d, lo // _GRAPH_CHUNK])
        out = edges[d * per_dev + lo:d * per_dev + hi]
        out[:, 0] = rng.integers(d * v_local, (d + 1) * v_local,
                                 size=hi - lo, dtype=np.int32)
        # rank k-1 of the Zipf; a draw of exactly 1.0 cannot happen, the
        # clip guards the last cumulative weight's rounding
        zipf_rank = np.searchsorted(cdf, rng.random(hi - lo), side="right")
        out[:, 1] = perm[np.minimum(zipf_rank, num_v - 1)]

    tasks = [(d, lo) for d in range(num_devices)
             for lo in range(0, per_dev, _GRAPH_CHUNK)]
    with ThreadPoolExecutor(max_workers=min(8, len(tasks))) as pool:
        list(pool.map(draw, tasks))
    out_deg = np.bincount(edges[:, 0], minlength=num_v).astype(np.float32)
    return edges, _initial_ranks(num_v), out_deg


class ResidentGraph(NamedTuple):
    """A graph on the devices, as a job takes it."""
    edges: jax.Array      # i32[D*E, 2], sharded over the shuffle axis
    out_deg: jax.Array    # f32[V], range-sharded
    num_edges: int        # valid (non-padding) edges
    max_in_degree: int    # the largest hub's fan-in


def place_graph(mesh: Mesh, axis_name: str, edges: np.ndarray,
                out_deg: np.ndarray) -> ResidentGraph:
    """Put a host graph on the mesh, once, for any number of jobs."""
    shard = NamedSharding(mesh, P(axis_name))
    valid = edges[:, 0] >= 0
    num_edges = int(valid.sum())
    dst = edges[:, 1] if num_edges == len(edges) else edges[valid, 1]
    return ResidentGraph(jax.device_put(edges, shard),
                         jax.device_put(out_deg, shard), num_edges,
                         int(np.bincount(dst).max()) if num_edges else 0)


class PageRankJob:
    """``job(graph) -> ranks``: one PageRank job over a resident graph.

    A job resets the ranks to ``1/V`` on the devices, dispatches
    ``iterations`` supersteps back to back (the ranks never leave HBM
    and the host does not wait between them), blocks once, and only then
    reads every superstep's ``overflowed`` flag: any one set raises
    ``OverflowError``. Returns the ranks as a sharded ``jax.Array``.
    The programs are built here, once, for any number of jobs.

    Spans, on ``self.tracer`` (a caller may set one per job, as with the
    engine's): ``pagerank.job`` (``iterations``, ``edges``, ``vertices``;
    at its end ``received``, the contributions delivered in each
    superstep, and ``row_move``, the form the rows followed their order
    in, and ``accumulate_fill``, the most slots any device's accumulate
    loop read, whole chunks up to the capacity, over that capacity) around
    ``pagerank.dispatch`` and ``pagerank.wait``.
    Counters, per job: ``pagerank.recv_fill`` (most records any device
    received, the exchange's fill among them, over its receive capacity)
    and ``pagerank.max_in_degree``.
    """

    def __init__(self, mesh: Mesh, axis_name: str, cfg: PageRankConfig,
                 iterations: int, impl: str = "auto", tracer=trace.NULL):
        self.cfg = cfg
        self.iterations = iterations
        self.tracer = tracer
        self._step = make_pagerank_step(mesh, axis_name, cfg, impl)
        # the receive buffer, in records (the exchange's ``output``)
        self._capacity = record_capacity(
            cfg.edges_per_device, 2, mesh.shape[axis_name], cfg.out_factor)
        self._reset = jax.jit(
            lambda: jnp.full(cfg.num_vertices, 1.0 / cfg.num_vertices,
                             jnp.float32),
            out_shardings=NamedSharding(mesh, P(axis_name)))

    def __call__(self, graph: ResidentGraph) -> jax.Array:
        tracer = self.tracer
        with tracer.span("pagerank.job", "pagerank",
                         iterations=self.iterations, edges=graph.num_edges,
                         vertices=self.cfg.num_vertices) as args:
            with tracer.span("pagerank.dispatch", "pagerank"):
                ranks = self._reset()
                facts = []
                for _ in range(self.iterations):
                    ranks, received, overflowed = self._step(
                        graph.edges, ranks, graph.out_deg)
                    facts.append((received, overflowed))
                    record_exchange(graph.num_edges)
            with tracer.span("pagerank.wait", "pagerank"):
                jax.block_until_ready(ranks)
            received = np.array([np.asarray(r) for r, _ in facts])
            args["received"] = received[:, :, 0].sum(axis=1).tolist()
            args["row_move"] = forms_label(self._step.row_moves)
            most = int(received[:, :, 1].max())
            chunk = accumulate_chunk(self._capacity)
            args["accumulate_fill"] = min(
                -(-most // chunk) * chunk, self._capacity) / self._capacity
            tracer.counter("pagerank.recv_fill", most / self._capacity,
                           "pagerank")
            tracer.counter("pagerank.max_in_degree", graph.max_in_degree,
                           "pagerank")
            late = [i for i, (_, o) in enumerate(facts)
                    if np.asarray(o).any()]
            if late:
                raise OverflowError(
                    f"pagerank receive buffer overflow in supersteps "
                    f"{late}: contribution fan-in exceeds out_factor "
                    "headroom; raise PageRankConfig.out_factor")
        return ranks


def run_pagerank(mesh: Mesh, cfg: PageRankConfig, iterations: int,
                 axis_name: str = "shuffle", seed: int = 0,
                 impl: str = "auto") -> np.ndarray:
    """``iterations`` supersteps over ``random_graph(cfg, seed)``; returns
    the final ranks on the host. The small-graph convenience over
    ``place_graph`` + ``PageRankJob``."""
    edges, _, out_deg = random_graph(cfg, mesh.shape[axis_name], seed)
    job = PageRankJob(mesh, axis_name, cfg, iterations, impl)
    return np.asarray(job(place_graph(mesh, axis_name, edges, out_deg)))


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
