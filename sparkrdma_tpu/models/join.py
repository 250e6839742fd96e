"""Shuffle join: the TPC-DS q64/q95-style workload.

BASELINE.md config #4: shuffle-heavy SQL joins. A distributed equi-join is
two shuffles (both sides hash-partitioned on the join key to the same
devices) followed by a local join per partition — exactly the traffic the
reference accelerates for Spark SQL.

TPU-native design, one jitted SPMD step:

1. both row sets are hash-partitioned on key and ragged-exchanged to the
   key's owner device (two collectives, same routing);
2. the local join is sort-merge: co-sort both sides by key, then for every
   left row count/sum its key's matches on the right via two
   ``searchsorted`` boundaries — static shapes, no data-dependent output
   (the step returns per-device aggregates: match count + sum of joined
   measures, the q95-style reduction).
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


@dataclass(frozen=True)
class JoinConfig:
    rows_per_device_left: int
    rows_per_device_right: int
    key_space: int
    out_factor: int = 2


def make_join_step(mesh: Mesh, axis_name: str, cfg: JoinConfig,
                   impl: str = "auto"):
    """Jitted hash-shuffle join.

    Inputs (leading axis sharded): ``left: u32[D*L, 2]`` (key, measure),
    ``right: u32[D*R, 2]`` (key, measure). Padding rows use key
    0xFFFFFFFF. Returns per-device ``(match_count: i32[D, 1],
    measure_sum: i32[D, 1])`` where measure_sum adds left.measure *
    right_match_count + right measures of matches — a fixed-shape
    aggregate standing in for the materialized join. Per-device partial
    sums are i32 (x64 is off under jit); callers needing >2^31 totals
    aggregate the per-device partials host-side.
    """
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    spec = P(axis_name)
    PAD = jnp.uint32(0xFFFFFFFF)

    def exchange_side(rows, capacity_factor):
        keys = rows[:, 0]
        valid = keys != PAD
        dest = jnp.where(valid, hash_partition(keys, n), -1)
        output = jnp.zeros((rows.shape[0] * capacity_factor, rows.shape[1]),
                           rows.dtype)
        received, recv_counts, _, overflowed = shuffle_shard(
            rows, dest, axis_name, n, output=output, impl=impl)
        total = recv_counts.sum()
        rvalid = jnp.arange(received.shape[0], dtype=jnp.int32) < total
        rkeys = jnp.where(rvalid, received[:, 0], PAD)
        order = jnp.argsort(rkeys, stable=True)
        return (jnp.sort(rkeys), jnp.take(received[:, 1], order),
                total, overflowed)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec),
                       out_specs=(spec, spec, spec))
    def step(left, right):
        lk, lv, ln_, lof = exchange_side(left, cfg.out_factor)
        rk, rv, rn_, rof = exchange_side(right, cfg.out_factor)
        # right-side prefix sums of measures for O(1) range sums
        rv32 = rv.astype(jnp.int32)
        rpref = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(rv32)])
        lo = jnp.searchsorted(rk, lk, side="left")
        hi = jnp.searchsorted(rk, lk, side="right")
        lvalid = lk != PAD
        matches = jnp.where(lvalid, (hi - lo).astype(jnp.int32), 0)
        # sum over matched pairs of (left.measure + right.measure)
        pair_sum = jnp.where(
            lvalid,
            matches * lv.astype(jnp.int32) + (rpref[hi] - rpref[lo]),
            0)
        overflowed = lof | rof
        return (matches.sum()[None, None], pair_sum.sum()[None, None],
                overflowed[None])

    return step


def generate_tables(cfg: JoinConfig, num_devices: int, seed: int = 0,
                    ) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    left = rng.integers(0, cfg.key_space,
                        size=(num_devices * cfg.rows_per_device_left, 2),
                        dtype=np.uint32)
    right = rng.integers(0, cfg.key_space,
                         size=(num_devices * cfg.rows_per_device_right, 2),
                         dtype=np.uint32)
    left[:, 1] %= 1000
    right[:, 1] %= 1000
    return left, right


def run_join(mesh: Mesh, cfg: JoinConfig, axis_name: str = "shuffle",
             seed: int = 0, impl: str = "auto") -> Tuple[int, int]:
    """Returns (total_matches, total_pair_measure_sum)."""
    n = mesh.shape[axis_name]
    left, right = generate_tables(cfg, n, seed)
    step = make_join_step(mesh, axis_name, cfg, impl)
    shard = NamedSharding(mesh, P(axis_name))
    counts, sums, overflowed = jax.block_until_ready(
        step(jax.device_put(left, shard), jax.device_put(right, shard)))
    if np.asarray(overflowed).any():
        raise OverflowError("join shuffle overflowed receive headroom; "
                            "raise JoinConfig.out_factor")
    return int(np.asarray(counts).sum()), int(np.asarray(sums).sum())


def numpy_join(left: np.ndarray, right: np.ndarray) -> Tuple[int, int]:
    """Host oracle: exact inner-join aggregates."""
    matches = 0
    pair_sum = 0
    right_by_key: dict = {}
    for k, v in right.tolist():
        right_by_key.setdefault(k, []).append(v)
    for k, v in left.tolist():
        rs = right_by_key.get(k)
        if rs:
            matches += len(rs)
            pair_sum += len(rs) * v + sum(rs)
    return matches, pair_sum
