"""Multi-host integration: a real 2-process jax.distributed cluster (4 CPU
devices each) runs the TeraSort exchange over the 8-device GLOBAL mesh —
the process-boundary behaviors (global array assembly, cross-process
collectives over the Gloo/DCN path) that single-process tests can't reach.
"""

import os
import socket
import subprocess
import sys

_WORKER = r'''
import sys, numpy as np
pid, port = int(sys.argv[1]), sys.argv[2]
from sparkrdma_tpu.parallel.multihost import (
    init_multihost, global_mesh, run_multihost_terasort)
init_multihost(f"127.0.0.1:{port}", num_processes=2, process_id=pid,
               local_device_count=4, platform="cpu")
import jax
assert len(jax.devices()) == 8, jax.devices()
assert len(jax.local_devices()) == 4
mesh = global_mesh("shuffle")
rows_per_device = 64
local_out, local_counts = run_multihost_terasort(
    mesh, "shuffle", rows_per_device, payload_words=2, seed=5)
# each local device shard must be internally sorted with the right count
per_dev = local_out.reshape(4, -1, 3)
cnts = local_counts.reshape(4, -1)
for d in range(4):
    total = int(cnts[d].sum())
    keys = per_dev[d][:total, 0].astype(np.int64)
    assert (np.diff(keys) >= 0).all(), f"proc {pid} dev {d} unsorted"
# global row conservation across both processes
total_here = int(cnts.sum())
print(f"MULTIHOST_OK {pid} rows={total_here}", flush=True)
'''


_REDUCE_WORKER = r'''
import pathlib, sys, tempfile, time
import numpy as np

pid, port = int(sys.argv[1]), sys.argv[2]
from sparkrdma_tpu.parallel.multihost import (
    global_mesh, init_multihost, run_multihost_mesh_reduce)
init_multihost(f"127.0.0.1:{port}", num_processes=2, process_id=pid,
               local_device_count=4, platform="cpu")
import jax
from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.shuffle.manager import (
    PartitionerSpec, ShuffleHandle, TpuShuffleManager)

conf = TpuShuffleConf(connect_timeout_ms=5000)
PARTS, MAPS, ROWS, W = 16, 4, 2000, 8
addr_file = pathlib.Path("driver_addr.txt")
driver = None
if pid == 0:
    driver = TpuShuffleManager(conf, is_driver=True)
    handle = driver.register_shuffle(7, MAPS, PARTS,
                                     PartitionerSpec("modulo"),
                                     row_payload_bytes=W)
    # atomic publish: write-then-rename so the poller never reads a
    # half-written address
    tmp = addr_file.with_suffix(".tmp")
    tmp.write_text("%s:%d" % driver.driver_addr)
    tmp.replace(addr_file)
    driver_addr = driver.driver_addr
else:
    # the handle is a value object; both processes construct it identically
    handle = ShuffleHandle(7, MAPS, PARTS, W, PartitionerSpec("modulo"))
    deadline = time.monotonic() + 30
    while not addr_file.exists():
        assert time.monotonic() < deadline, "driver address never appeared"
        time.sleep(0.05)
    h, p = addr_file.read_text().split(":")
    driver_addr = (h, int(p))

mgr = TpuShuffleManager(conf, driver_addr=driver_addr,
                        executor_id=f"h{pid}",
                        spill_dir=tempfile.mkdtemp())
mgr.executor.wait_for_members(2)

def table(m):
    rng = np.random.default_rng(1000 + m)
    return (rng.integers(0, 100000, ROWS).astype(np.uint64),
            rng.integers(0, 255, (ROWS, W)).astype(np.uint8))

# SPI writes: maps 0,1 on host 0; maps 2,3 on host 1
for m in ((0, 1) if pid == 0 else (2, 3)):
    w = mgr.get_writer(handle, m)
    w.write_batch(*table(m))
    w.close()

mesh = global_mesh("shuffle")
results = run_multihost_mesh_reduce([mgr], handle, mesh)

# the 2-process cluster IS a 2-slice topology (per-host seams): the
# reduce must have tallied its cross-host bytes on the DCN metric
from sparkrdma_tpu.parallel import topology as topo_mod
assert not topo_mod.detect_topology(mesh).is_flat, "seams undetected"
assert topo_mod.CROSS_SLICE["bytes"] > 0, "per-host seam traffic untallied"

# verify OUR devices against the deterministic global truth
tk = np.concatenate([table(m)[0] for m in range(MAPS)])
tp = np.concatenate([table(m)[1] for m in range(MAPS)])
owner_dev = (tk % PARTS % 8).astype(np.int64)

def canon(k, p):
    rows = np.concatenate(
        [np.ascontiguousarray(k)[:, None].view(np.uint8).reshape(len(k), 8),
         p], axis=1)
    return rows[np.lexsort(rows.T[::-1])]

local_devs = [i for i, d in enumerate(mesh.devices.flat)
              if d.process_index == jax.process_index()]
got_rows = 0
for (k, p, parts), dev in zip(results, local_devs):
    assert (parts % 8 == dev).all()
    assert (np.diff(k.astype(np.int64)) >= 0).all(), "not key-sorted"
    mask = owner_dev == dev
    assert np.array_equal(canon(k, p), canon(tk[mask], tp[mask])), \
        f"device {dev} mismatch"
    got_rows += len(k)

# streamed rounds (rows_per_round bounds device memory; cap=1000 here, so
# 64/round = 16 collective rounds) must produce identical results
streamed = run_multihost_mesh_reduce([mgr], handle, mesh, rows_per_round=64)
for (k1, p1, pa1), (k2, p2, pa2) in zip(results, streamed):
    assert np.array_equal(canon(k1, p1), canon(k2, p2)), "streamed mismatch"
    assert np.array_equal(np.sort(pa1), np.sort(pa2))

from jax.experimental import multihost_utils
multihost_utils.sync_global_devices("done")  # driver outlives readers
print(f"MESHREDUCE_OK {pid} rows={got_rows}", flush=True)
mgr.stop()
if driver is not None:
    driver.stop()
'''


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_two_process(worker: str, tmp_path, ok_marker: str):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen([sys.executable, "-c", worker, str(i), str(port)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         env=env, cwd=str(tmp_path))
        for i in range(2)
    ]
    outputs = []
    for p in procs:
        out, _ = p.communicate(timeout=150)
        outputs.append(out.decode())
    for i, out in enumerate(outputs):
        assert f"{ok_marker} {i}" in out, f"proc {i} failed:\n{out[-2000:]}"
    return outputs


def test_two_process_spi_mesh_reduce(tmp_path):
    """The reference's multi-node pipeline end-to-end (README.md:11-31):
    spills committed through the SPI on TWO processes feed ONE global-mesh
    exchange; every device's reduce output is exact vs. the global truth."""
    outputs = _run_two_process(_REDUCE_WORKER, tmp_path, "MESHREDUCE_OK")
    rows = sum(int(out.split("rows=")[1].split()[0]) for out in outputs)
    assert rows == 4 * 2000  # global conservation: every written row landed


def test_two_process_global_mesh_terasort(tmp_path):
    outputs = _run_two_process(_WORKER, tmp_path, "MULTIHOST_OK")
    # global conservation: the two processes' rows sum to the full dataset
    rows = sum(int(out.split("rows=")[1].split()[0]) for out in outputs)
    assert rows == 8 * 64
