"""Run the Pallas ring all-to-all COMPILED on this host's chips, once.

    python scripts/ring_chip_check.py            # on a multi-chip TPU host

The ring (``ops/ring_exchange.py``) is off the default path and has only
ever run interpreted; this checks that ``make_ring_all_to_all(mesh,
"shuffle")`` compiles under Mosaic and moves the right bytes, against
``lax.all_to_all`` and the numpy block transpose. Each block shape runs in
a process of its own under a timeout, one after another (a semaphore bug
hangs rather than fails, and a chip belongs to one process at a time);
this parent never imports jax. Shapes: the AOT-tested ``[D, 8, 128]``
block, and chunked-round blocks of 25-word rows at quota 1024 and 8192
(``_ring_move_blocks`` flattens ``[D, quota, 25]`` to 128-word lanes).
The kernel keeps the whole block plus a 2x transit scratch in VMEM, so
there is a block size the compiler will refuse; the largest here (3.3 MB
per device) still compiled and matched on four v5e chips (2026-09-26).

Prints one JSON line per shape and a last line with all of them; exits
non-zero unless every shape ran and matched, so also without a multi-chip
TPU (tests/test_ring_exchange.py covers the interpreted ring on the CPU).
Not part of chip_smoke.py.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (label, C): the block is u32[D, C, 128]
SHAPES = [("aot_block_8x128", 8),
          ("chunked_round_quota1024_w25", 1024 * 25 // 128),
          ("chunked_round_quota8192_w25", 8192 * 25 // 128)]

_CHILD = r"""
import functools, json, sys, time
label, c = sys.argv[1], int(sys.argv[2])
import jax, numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from sparkrdma_tpu.ops.ring_exchange import make_ring_all_to_all

devs = jax.devices()
n = len(devs)
rec = {"label": label, "block": [n, c, 128], "platform": devs[0].platform,
       "device_kind": devs[0].device_kind, "devices": n}
if devs[0].platform != "tpu" or n < 2:
    rec["status"] = "error: needs a TPU host with more than one chip"
    print(json.dumps(rec)); sys.exit(1)
mesh = Mesh(np.array(devs), ("shuffle",))
sh = NamedSharding(mesh, P("shuffle"))
x = np.random.default_rng(0).integers(0, 2**32, (n, n, c, 128),
                                      dtype=np.uint32)
xd = jax.device_put(x, sh)

@jax.jit
@functools.partial(shard_map, mesh=mesh, in_specs=P("shuffle"),
                   out_specs=P("shuffle"))
def reference(v):
    return lax.all_to_all(v[0], "shuffle", split_axis=0, concat_axis=0)[None]

try:
    t0 = time.perf_counter()
    got = np.asarray(jax.block_until_ready(
        make_ring_all_to_all(mesh, "shuffle")(xd)))
    rec["first_call_s"] = round(time.perf_counter() - t0, 2)
    want = np.asarray(reference(xd))
    same = bool(np.array_equal(got, want)
                and np.array_equal(want, np.swapaxes(x, 0, 1)))
    rec["status"] = "ok" if same else "mismatch"
except Exception as e:
    rec["status"] = f"error: {type(e).__name__}: {str(e)[:600]}"
print(json.dumps(rec))
sys.exit(0 if rec["status"] == "ok" else 1)
"""


def main() -> int:
    env = dict(os.environ, PYTHONPATH=REPO)
    results = []
    for label, c in SHAPES:
        try:
            proc = subprocess.run(
                [sys.executable, "-c", _CHILD, label, str(c)],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=120)
            line = next((ln for ln in reversed(proc.stdout.splitlines())
                         if ln.startswith("{")), None)
            rec = json.loads(line) if line else {
                "label": label, "status": f"error: exit={proc.returncode}: "
                + proc.stderr[-600:]}
        except subprocess.TimeoutExpired:
            rec = {"label": label, "block": [None, c, 128],
                   "status": "timeout after 120s (hang)"}
        print(json.dumps(rec), flush=True)
        results.append(rec)
    print(json.dumps({"ring_chip_check": results}))
    return 0 if all(r["status"] == "ok" for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
