"""TPC-DS-shaped multi-join: the q64/q95-class shuffle-heavy SQL workload.

BASELINE.md config #4's missing half: where ``models/join.py`` is one
equi-join, real TPC-DS plans chain shuffles — q64/q95 join a skewed fact
table against several dimension tables and aggregate (the reference's
published workloads are shuffle-bound Spark jobs of exactly this class,
/root/reference/README.md:7-31). This model runs the canonical star shape

    fact  ⋈(key1) dim1  ⋈(key2) dim2  -> GROUP BY g -> (count, sum)

as FOUR chained ragged exchanges inside ONE jitted SPMD step (fact and
dim1 by hash(key1); the join-1 survivors and dim2 by hash(key2); the
joined rows by group owner), stressing multiple concurrent shuffles per
job the way a TPC-DS stage graph does. Fact keys are Zipf-skewed
(realistic key popularity); dimension keys are unique with partial
coverage, so both joins are selective inner joins implemented as static-
shape sorted lookups (no data-dependent output sizes — validity masks
carry selectivity).

The same logical plan is also expressed as a DAG-engine job
(``build_tpcds_job``) driving the drop-in SPI — source stages for the
three tables, two join MapStages, one aggregating ResultStage — so the
workload exercises both the on-mesh collective path and the host/DCN
engine path against one oracle (``numpy_tpcds``).
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

from sparkrdma_tpu.ops.partition import hash_partition
from sparkrdma_tpu.parallel.exchange import resolve_impl, shuffle_shard

PAD = np.uint32(0xFFFFFFFF)


@dataclass(frozen=True)
class TpcdsConfig:
    fact_rows_per_device: int
    dim1_size: int              # global; keys in [0, dim1_size)
    dim2_size: int
    num_groups: int = 256
    zipf_a: float = 1.2         # fact key1 skew exponent
    out_factor: int = 3         # receive headroom for the skewed exchange
    dim_coverage_mod: int = 10  # dim keeps keys with k % mod != 0 (90%)


def _mix_group(key1, key2, num_groups):
    """Group key from both join keys (u32 wrap, same in numpy and jnp)."""
    return (key1 * 31 + key2) % num_groups


def generate_star(cfg: TpcdsConfig, num_devices: int, seed: int = 0,
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(fact u32[D*F, 3], dim1 u32[M1', 2], dim2 u32[M2', 2]).

    fact columns: (key1 zipf-skewed, key2 uniform, measure). Dim tables
    have unique keys with ``(mod-1)/mod`` coverage; attrs are small so
    i32 per-group partial sums cannot wrap at bench sizes.
    """
    rng = np.random.default_rng(seed)
    n = num_devices * cfg.fact_rows_per_device
    key1 = (rng.zipf(cfg.zipf_a, size=n) - 1) % cfg.dim1_size
    key2 = rng.integers(0, cfg.dim2_size, size=n)
    measure = rng.integers(0, 97, size=n)
    fact = np.stack([key1, key2, measure], axis=1).astype(np.uint32)

    def dim(size, attr_mod, salt):
        keys = np.arange(size, dtype=np.uint32)
        keys = keys[keys % cfg.dim_coverage_mod != 0]
        attr = ((keys * 2654435761 + salt) % attr_mod).astype(np.uint32)
        return np.stack([keys, attr], axis=1)

    return fact, dim(cfg.dim1_size, 89, 7), dim(cfg.dim2_size, 83, 13)


def pad_to_devices(rows: np.ndarray, num_devices: int) -> np.ndarray:
    """Pad (with PAD-key rows) so the leading axis splits evenly; at least
    one row per device so an empty table still exchanges/probes cleanly
    (static shapes: a zero-capacity buffer can't be gathered from)."""
    per = max(1, -(-len(rows) // num_devices))
    out = np.full((per * num_devices, rows.shape[1]), PAD, rows.dtype)
    out[:len(rows)] = rows
    return out


def make_tpcds_step(mesh: Mesh, axis_name: str, cfg: TpcdsConfig,
                    impl: str = "auto"):
    """Jitted star-join + aggregate over ``mesh``.

    Inputs sharded on the leading axis: ``fact u32[D*F, 3]``,
    ``dim1 u32[D*M1, 2]``, ``dim2 u32[D*M2, 2]`` (PAD-key rows ignored).
    Returns ``(counts i32[D, G], sums i32[D, G], overflowed bool[D])`` —
    device d's rows hold exact totals for the groups it owns
    (``g % D == d``) and zeros elsewhere, so a plain host sum over
    devices is the full GROUP BY result.
    """
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    spec = P(axis_name)
    G = cfg.num_groups
    pad = jnp.uint32(PAD)

    def exchange(rows, dest, capacity):
        output = jnp.zeros((capacity, rows.shape[1]), rows.dtype)
        received, recv_counts, _, overflowed = shuffle_shard(
            rows, dest, axis_name, n, output=output, impl=impl)
        total = recv_counts.sum()
        valid = jnp.arange(capacity, dtype=jnp.int32) < total
        return received, valid, overflowed

    def dim_lookup(dim_rows, dim_valid, query_keys):
        """Unique-key join: sorted dim + one searchsorted per probe."""
        dkeys = jnp.where(dim_valid, dim_rows[:, 0], pad)
        order = jnp.argsort(dkeys, stable=True)
        dkeys_s = jnp.take(dkeys, order)
        dattr_s = jnp.take(dim_rows[:, 1], order)
        idx = jnp.clip(jnp.searchsorted(dkeys_s, query_keys),
                       0, dkeys_s.shape[0] - 1)
        found = (jnp.take(dkeys_s, idx) == query_keys) & (query_keys != pad)
        return jnp.take(dattr_s, idx), found

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=(spec, spec, spec))
    def step(fact, dim1, dim2):
        F = fact.shape[0]

        def route(rows, key_col):
            keys = rows[:, key_col]
            return jnp.where(keys != pad,
                             hash_partition(keys, n), -1)

        # shuffles 1+2: fact and dim1 to hash(key1) owners
        d1, d1_valid, of1 = exchange(dim1, route(dim1, 0),
                                     dim1.shape[0] * cfg.out_factor)
        f1, f1_valid, of2 = exchange(fact, route(fact, 0),
                                     F * cfg.out_factor)
        attr1, found1 = dim_lookup(d1, d1_valid, f1[:, 0])
        live1 = f1_valid & found1
        value1 = (f1[:, 2] * attr1) % jnp.uint32(10007)
        # join-1 survivors: (key2, key1, value1), PAD-keyed when dead
        mid = jnp.stack([jnp.where(live1, f1[:, 1], pad),
                         f1[:, 0], value1], axis=1)

        # shuffles 3+4: survivors and dim2 to hash(key2) owners
        d2, d2_valid, of3 = exchange(dim2, route(dim2, 0),
                                     dim2.shape[0] * cfg.out_factor)
        m2, m2_valid, of4 = exchange(mid, route(mid, 0),
                                     F * cfg.out_factor)
        attr2, found2 = dim_lookup(d2, d2_valid, m2[:, 0])
        live2 = m2_valid & found2
        value = (m2[:, 2] + attr2) % jnp.uint32(10007)
        group = _mix_group(m2[:, 1], m2[:, 0], jnp.uint32(G))

        # shuffle 5: joined rows to their group's owner (g % D)
        rows3 = jnp.stack([jnp.where(live2, group, pad), value], axis=1)
        dest3 = jnp.where(live2, (group % n).astype(jnp.int32), -1)
        agg_cap = F * cfg.out_factor
        out3 = jnp.zeros((agg_cap, 2), rows3.dtype)
        recv3, rc3, _, of5 = shuffle_shard(rows3, dest3, axis_name, n,
                                           output=out3, impl=impl)
        total3 = rc3.sum()
        v3 = jnp.arange(agg_cap, dtype=jnp.int32) < total3
        g3 = jnp.where(v3 & (recv3[:, 0] != pad), recv3[:, 0], jnp.uint32(G))
        counts = jnp.bincount(g3, length=G + 1)[:G].astype(jnp.int32)
        sums = jnp.bincount(
            g3, weights=jnp.where(g3 < G, recv3[:, 1], 0).astype(jnp.int32),
            length=G + 1)[:G].astype(jnp.int32)
        overflowed = of1 | of2 | of3 | of4 | of5
        return counts[None], sums[None], overflowed[None]

    return step


def run_tpcds(mesh: Mesh, cfg: TpcdsConfig, axis_name: str = "shuffle",
              seed: int = 0, impl: str = "auto",
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Host driver: returns exact global (counts[G], sums[G])."""
    n = mesh.shape[axis_name]
    fact, dim1, dim2 = generate_star(cfg, n, seed)
    step = make_tpcds_step(mesh, axis_name, cfg, impl)
    shard = NamedSharding(mesh, P(axis_name))
    counts, sums, overflowed = jax.block_until_ready(step(
        jax.device_put(fact, shard),
        jax.device_put(pad_to_devices(dim1, n), shard),
        jax.device_put(pad_to_devices(dim2, n), shard)))
    if np.asarray(overflowed).any():
        raise OverflowError("tpcds shuffle overflowed receive headroom; "
                            "raise TpcdsConfig.out_factor")
    return (np.asarray(counts).sum(axis=0).astype(np.int64),
            np.asarray(sums).sum(axis=0).astype(np.int64))


def numpy_tpcds(fact: np.ndarray, dim1: np.ndarray, dim2: np.ndarray,
                num_groups: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host oracle: exact star-join + GROUP BY with the same arithmetic."""
    a1 = dict(zip(dim1[:, 0].tolist(), dim1[:, 1].tolist()))
    a2 = dict(zip(dim2[:, 0].tolist(), dim2[:, 1].tolist()))
    counts = np.zeros(num_groups, np.int64)
    sums = np.zeros(num_groups, np.int64)
    for k1, k2, m in fact.tolist():
        v1 = a1.get(k1)
        v2 = a2.get(k2)
        if v1 is None or v2 is None:
            continue
        value = (np.uint32(m) * np.uint32(v1) % np.uint32(10007)
                 + np.uint32(v2)) % np.uint32(10007)
        g = int(_mix_group(np.uint32(k1), np.uint32(k2),
                           np.uint32(num_groups)))
        counts[g] += 1
        sums[g] += int(value)
    return counts, sums


# -- the same plan through the DAG engine (drop-in SPI path) --------------

def build_tpcds_job(cfg: TpcdsConfig, num_maps: int, num_partitions: int,
                    seed: int = 0):
    """The star query as a stage DAG for ``engine.DAGEngine.run``.

    Returns ``(result_stage, finish)`` where ``finish(results)`` folds the
    per-partition dicts into global ``(counts[G], sums[G])``. Stage graph:
    three sources (fact/dim1/dim2, modulo-partitioned on their join key),
    join-1 (reads fact+dim1, writes by key2), join-2 (reads join-1+dim2,
    writes by group), aggregate ResultStage — five shuffles, the SPI
    sequence a TPC-DS stage graph drives through Spark.
    """
    from sparkrdma_tpu.engine import MapStage, ResultStage
    from sparkrdma_tpu.shuffle.manager import PartitionerSpec
    from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency

    G = cfg.num_groups
    fact_all, dim1_all, dim2_all = generate_star(cfg, 1, seed)

    def dep(payload_bytes):
        return ShuffleDependency(num_partitions, PartitionerSpec("modulo"),
                                 row_payload_bytes=payload_bytes)

    def rows_of(table, task):  # deterministic striping across map tasks
        return table[task::num_maps]

    def src(table, key_col, payload_cols):
        width = 4 * len(payload_cols)

        def fn(ctx, writer, task):
            rows = rows_of(table, task)
            payload = np.ascontiguousarray(
                rows[:, payload_cols], dtype="<u4").view(np.uint8)
            writer.write((rows[:, key_col].astype(np.uint64),
                          payload.reshape(len(rows), width)))
        return fn

    fact_st = MapStage(num_maps, dep(8), src(fact_all, 0, [1, 2]))
    dim1_st = MapStage(num_maps, dep(4), src(dim1_all, 0, [1]))
    dim2_st = MapStage(num_maps, dep(4), src(dim2_all, 0, [1]))

    def read_u32(ctx, parent):  # -> (keys u64[N], cols u32[N, W])
        ks, vs = [], []
        for keys, payload in ctx.read(parent).readBatches():
            ks.append(keys)
            vs.append(np.ascontiguousarray(payload).view("<u4")
                      .reshape(len(keys), -1))
        if not ks:
            return np.zeros(0, np.uint64), np.zeros((0, 1), np.uint32)
        return np.concatenate(ks), np.concatenate(vs)

    def np_lookup(dkeys, dattr, probes):
        """Vectorized unique-key join: (attr[N] u32, found[N] bool)."""
        if len(dkeys) == 0:
            return (np.zeros(len(probes), np.uint32),
                    np.zeros(len(probes), bool))
        order = np.argsort(dkeys)
        ks, at = dkeys[order], dattr[order]
        idx = np.clip(np.searchsorted(ks, probes), 0, len(ks) - 1)
        return at[idx].astype(np.uint32), ks[idx] == probes

    def join1_fn(ctx, writer, task):
        fkeys, fcols = read_u32(ctx, 0)   # key1 -> (key2, measure)
        dkeys, dcols = read_u32(ctx, 1)   # key1 -> (attr1,)
        attr, found = np_lookup(dkeys, dcols[:, 0], fkeys)
        v1 = (fcols[:, 1].astype(np.uint32) * attr) % np.uint32(10007)
        keep = found
        payload = np.stack([fkeys.astype(np.uint32)[keep], v1[keep]],
                           axis=1)  # (key1, value1)
        writer.write((fcols[:, 0][keep].astype(np.uint64),
                      np.ascontiguousarray(payload, "<u4").view(np.uint8)
                      .reshape(int(keep.sum()), 8)))
        del task

    join1_st = MapStage(num_partitions, dep(8), join1_fn,
                        parents=[fact_st, dim1_st])

    def join2_fn(ctx, writer, task):
        mkeys, mcols = read_u32(ctx, 0)   # key2 -> (key1, value1)
        dkeys, dcols = read_u32(ctx, 1)   # key2 -> (attr2,)
        attr, found = np_lookup(dkeys, dcols[:, 0], mkeys)
        value = (mcols[:, 1].astype(np.uint32) + attr) % np.uint32(10007)
        group = _mix_group(mcols[:, 0].astype(np.uint32),
                           mkeys.astype(np.uint32), np.uint32(G))
        keep = found
        writer.write((group[keep].astype(np.uint64),
                      np.ascontiguousarray(value[keep], "<u4")
                      .view(np.uint8).reshape(int(keep.sum()), 4)))
        del task

    join2_st = MapStage(num_partitions, dep(4), join2_fn,
                        parents=[join1_st, dim2_st])

    def agg_fn(ctx, task):
        counts = np.zeros(G, np.int64)
        sums = np.zeros(G, np.int64)
        for keys, payload in ctx.read(0).readBatches():
            vals = np.ascontiguousarray(payload).view("<u4").ravel()
            np.add.at(counts, keys.astype(np.int64), 1)
            np.add.at(sums, keys.astype(np.int64), vals.astype(np.int64))
        del task
        return counts, sums

    result = ResultStage(num_partitions, agg_fn, parents=[join2_st])

    def finish(results):
        counts = sum(c for c, _ in results)
        sums = sum(s for _, s in results)
        return counts, sums

    return result, finish
