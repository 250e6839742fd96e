"""BASELINE config #2 dress rehearsal at environment scale.

The reference's headline run is TeraSort-320GB across 7 workers
(reference README.md:11-17). This environment has one host and a virtual
8-device CPU mesh, so the rehearsal scales the *shape* of that run, not
its size: a dataset many times one round's device capacity, streamed
through R >= 32 bounded rounds, with the host's address space capped so
any per-round memory leak (e.g. the out_factor-sized round buffers
surviving past their round) aborts the run instead of silently paging.

Runs in a subprocess: RLIMIT_AS must not poison the shared test process,
and jax must initialize fresh under the cap-free generation phase.
Size is env-tunable (REHEARSAL_MB, default 512 — "GB-class" for a CPU
mesh; real hardware rehearsals raise it).
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json, os, resource, sys, time
sys.path.insert(0, {repo!r})
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from jax.sharding import Mesh
from sparkrdma_tpu.models.terasort import (
    TeraSortConfig, run_terasort_streamed)

D = 8
size_mb = {size_mb}
row_words = 25  # 100-byte classic TeraSort rows
rows_total = (size_mb << 20) // (4 * row_words)
# >= 32 rounds: per-round capacity is ceil(total / 32) rows over D devices
rows_per_device = -(-rows_total // (32 * D))
cfg = TeraSortConfig(rows_per_device=rows_per_device, payload_words=24,
                     out_factor=2)
rows = np.random.default_rng(7).integers(
    0, 2**32, size=(rows_total, row_words), dtype=np.uint32)
data_bytes = rows.nbytes

# Warm/compile the step BEFORE the cap: XLA compilation transiently maps
# large address ranges that have nothing to do with the streaming path
# under test.
mesh = Mesh(np.array(jax.devices()[:D]), ("shuffle",))
warm = {{}}
run_terasort_streamed(mesh, cfg, rows[: D * cfg.rows_per_device],
                      phase_times=warm)

# Cap the address space: current usage + the streaming path's legitimate
# needs (per-device runs ~= dataset, merged output ~= dataset, two
# pipelined rounds of out_factor-sized buffers) + slack. A leak that
# retains per-round buffers across rounds costs ~2x dataset extra and
# blows the cap.
with open("/proc/self/status") as f:
    vm_kb = next(int(l.split()[1]) for l in f if l.startswith("VmSize"))
headroom = int(2.4 * data_bytes) + (512 << 20)
cap = (vm_kb << 10) + headroom
resource.setrlimit(resource.RLIMIT_AS, (cap, cap))
try:
    np.zeros(headroom + (64 << 20), np.uint8)
    print("CAP-NOT-EFFECTIVE")
except MemoryError:
    pass

phases = {{}}
t0 = time.perf_counter()
merged, rounds = run_terasort_streamed(mesh, cfg, rows, phase_times=phases)
wall = time.perf_counter() - t0
assert rounds >= 32, rounds

# exact global sort: per-device sorted, ranges non-overlapping in device
# order, multiset of keys preserved
prev_max = -1
got = []
for d, out in enumerate(merged):
    keys = out[:, 0].astype(np.int64)
    if len(keys):
        assert (np.diff(keys) >= 0).all(), f"device {{d}} unsorted"
        assert keys[0] >= prev_max, f"device {{d}} overlaps previous"
        prev_max = int(keys[-1])
    got.append(keys)
got = np.concatenate(got)
assert len(got) == rows_total, (len(got), rows_total)
np.testing.assert_array_equal(np.sort(got),
                              np.sort(rows[:, 0].astype(np.int64)))

print("PHASES=" + json.dumps({{
    "data_mb": size_mb, "rounds": rounds, "wall_s": round(wall, 2),
    "stage_s": round(phases["stage_s"], 2),
    "collect_s": round(phases["collect_s"], 2),
    "merge_s": round(phases["merge_s"], 2),
    "throughput_mb_s": round(size_mb / wall, 1)}}))
print("REHEARSAL-OK")
"""


def test_streamed_terasort_gb_class_rehearsal():
    size_mb = int(os.environ.get("REHEARSAL_MB", "512"))
    script = _SCRIPT.format(repo=_REPO, size_mb=size_mb)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # script pins cpu itself
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=880,
                          env=env)
    assert proc.returncode == 0, (proc.stdout[-1000:], proc.stderr[-3000:])
    if "CAP-NOT-EFFECTIVE" in proc.stdout:
        pytest.skip("RLIMIT_AS not enforceable on this platform")
    assert "REHEARSAL-OK" in proc.stdout
    phases = json.loads(next(
        ln for ln in proc.stdout.splitlines()
        if ln.startswith("PHASES=")).split("=", 1)[1])
    # the per-phase log IS the rehearsal evidence — surface it in the
    # test report even on success
    print("\nrehearsal phases:", json.dumps(phases))
    assert phases["rounds"] >= 32
