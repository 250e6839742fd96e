"""Pallas ring all-to-all: hand-scheduled ICI transport.

This is the framework's closest structural analogue of the reference's
one-sided verbs engine (java/RdmaChannel.java): where the reference posts
RDMA work requests NIC-to-NIC with explicit completion semaphores, this
kernel posts **async remote DMAs chip-to-chip over ICI** with explicit
send/recv semaphores — one-sided writes into a neighbor's VMEM, no host in
the loop, double-buffered so step ``s``'s transfer overlaps step ``s-1``'s
absorption.

Algorithm (shift-register ring, D-1 steps):

* ``T[k]`` holds the block whose destination is ``k`` hops to my right;
  initially ``T[k] = my block for device (me + k) % D``.
* each step remote-writes ``T[1:]`` into the right neighbour's next-slot
  ``T'[:-1]`` (everyone sends right / receives left with the same SPMD
  semaphores), then absorbs ``T'[0]`` — the block that just completed its
  journey — into the output row of its originator.

Ring traffic is O(D/2) blocks per link versus the switch-routed
``ragged_all_to_all`` — this kernel is not the default transport; it exists
for topologies/slices where neighbor-only traffic wins (1D ICI rings) and
as the from-scratch demonstration that the exchange needs nothing from XLA
but raw inter-chip DMA. Used in production paths via
``parallel.exchange.make_chunked_exchange(impl="ring")`` whose fixed
per-pair quota gives the static block shape the kernel needs.

Validated in Pallas interpret mode on the multi-device CPU mesh (remote
DMA emulation) against the collective-based exchange oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P


def _ring_kernel(axis_name: str, num_devices: int, use_barrier: bool,
                 blocks_ref, out_ref, transit, send_sem, recv_sem, bar_dir):
    """blocks_ref/out_ref: [D, C, W] u32. transit: [2, D, C, W] scratch."""
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, num_devices)
    left = jax.lax.rem(my - 1 + num_devices, num_devices)

    if use_barrier:
        # Entry rendezvous on the system barrier semaphore: scratch VMEM
        # addresses are only valid once every participant has entered the
        # kernel; each device signals each neighbor exactly once, so the
        # wait(2) cannot be satisfied by one fast neighbor double-signaling.
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, inc=1, device_id=left)
        pltpu.semaphore_signal(bar, inc=1, device_id=right)
        pltpu.semaphore_wait(bar, 2)

    # T[k] = my block destined k hops to the right = blocks[(my + k) % D].
    def init_body(k, _):
        src = jax.lax.rem(my + k, num_devices)
        transit[0, k] = blocks_ref[src]
        return 0
    jax.lax.fori_loop(0, num_devices, init_body, 0)

    # my own block never travels
    out_ref[my] = transit[0, 0]

    def step_body(s, _):
        cur = jax.lax.rem(s - 1, 2)
        nxt = jax.lax.rem(s, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=transit.at[cur, pl.ds(1, num_devices - 1)],
            dst_ref=transit.at[nxt, pl.ds(0, num_devices - 1)],
            send_sem=send_sem.at[cur],
            recv_sem=recv_sem.at[nxt],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()  # SPMD: waits my send AND my receive from the left
        # Neighbor barrier before the next step: my step s+1 remote-writes
        # the right neighbor's slot (s+1)%2 — the SAME slot parity its own
        # step-s send reads from. Without the barrier a fast device could
        # overwrite a slow neighbor's in-flight send buffer (WAR race).
        # The two directions use SEPARATE counting semaphores (bar_dir[0]:
        # left neighbor arrived, bar_dir[1]: right arrived): a single
        # semaphore with wait(2) could be satisfied by a fast left
        # neighbor's step-s AND step-s+1 signals with the right neighbor
        # still mid-DMA — exactly the WAR race the barrier must prevent.
        # Counting absorbs one-step run-ahead per direction. (The
        # interpreter's emulation is lock-step and lacks remote semaphore
        # signaling, so the barrier is compiled-mode only.)
        if use_barrier:
            pltpu.semaphore_signal(bar_dir.at[1], inc=1, device_id=left)
            pltpu.semaphore_signal(bar_dir.at[0], inc=1, device_id=right)
            pltpu.semaphore_wait(bar_dir.at[0], 1)
            pltpu.semaphore_wait(bar_dir.at[1], 1)
        # the block in slot 0 just completed its journey: it originated
        # s hops to my left
        origin = jax.lax.rem(my - s + num_devices, num_devices)
        out_ref[origin] = transit[nxt, 0]
        return 0

    jax.lax.fori_loop(1, num_devices, step_body, 0)


def ring_all_to_all_shard(blocks: jnp.ndarray, axis_name: str,
                          num_devices: int, interpret: bool = False,
                          ) -> jnp.ndarray:
    """Per-shard dense all-to-all. Call inside ``shard_map``.

    ``blocks: [D, C, W]`` — row j is this device's payload for device j.
    Returns ``[D, C, W]`` — row j is the payload received from device j.
    """
    if num_devices == 1:
        return blocks
    kernel = functools.partial(_ring_kernel, axis_name, num_devices,
                               not interpret)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(blocks.shape, blocks.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2,) + tuple(blocks.shape), blocks.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),  # per-direction step barrier
        ],
        # collective_id names the system barrier semaphore used by the
        # entry rendezvous; interpret mode has no barrier (and Mosaic
        # rejects the id when no barrier semaphore is referenced)
        compiler_params=(None if interpret
                         else pltpu.CompilerParams(collective_id=7)),
        interpret=interpret,
    )(blocks)


def make_ring_all_to_all(mesh: Mesh, axis_name: str,
                         interpret: bool = False):
    """Jitted all-device wrapper: ``x[D, D, C, W]`` sharded on axis 0
    (device i's row i = its D outgoing blocks) -> same shape, received."""
    n = mesh.shape[axis_name]

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=P(axis_name), out_specs=P(axis_name),
                       check_vma=False)
    def a2a(x):
        return ring_all_to_all_shard(x[0], axis_name, n, interpret)[None]

    return a2a
