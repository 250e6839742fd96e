"""Bounded-memory sort/merge (the ExternalSorter role): vectorized merges,
spill-to-disk k-way merge, and a genuine address-space-capped run."""

import os
import subprocess
import sys

import numpy as np
import pytest

from sparkrdma_tpu.shuffle.external import ExternalMerger, merge_runs, merge_two


def test_merge_two_stable():
    rng = np.random.default_rng(0)
    ak = np.sort(rng.integers(0, 50, 200).astype(np.uint64))
    bk = np.sort(rng.integers(0, 50, 300).astype(np.uint64))
    ar = np.zeros((200, 2), np.uint8)   # tag rows by side
    br = np.ones((300, 2), np.uint8)
    keys, rows = merge_two(ak, ar, bk, br)
    assert (np.diff(keys.astype(np.int64)) >= 0).all()
    np.testing.assert_array_equal(np.sort(keys),
                                  np.sort(np.concatenate([ak, bk])))
    # stability: within one key, all a-rows precede all b-rows
    for k in np.unique(keys):
        tags = rows[keys == k, 0]
        assert (np.diff(tags.astype(np.int8)) >= 0).all()


def test_merge_runs_matches_full_sort():
    rng = np.random.default_rng(1)
    runs = []
    for _ in range(7):  # odd count exercises the bye
        rows = rng.integers(0, 2**32, size=(rng.integers(0, 500), 5),
                            dtype=np.uint32)
        rows = rows[np.argsort(rows[:, 0], kind="stable")]
        runs.append((rows[:, 0], rows))
    _, merged = merge_runs(runs)
    everything = np.concatenate([r for _, r in runs])
    want = everything[np.argsort(everything[:, 0], kind="stable")]
    np.testing.assert_array_equal(merged[:, 0], want[:, 0])


def _keys_of(rows, key_kind):
    """The callers' two key forms: column 0, or columns 0-1 packed (low
    word first) as the device plane's u64."""
    if key_kind == "u64":
        return rows[:, :2].copy().view(np.uint64).reshape(-1)
    return rows[:, 0]


def _sorted_runs(rng, lengths, key_kind, key_range):
    """Key-sorted u32[n, 5] runs; every row carries its run in column 3
    and its index within the run in column 4, so a row that moved, was
    lost or was written twice shows in the bytes."""
    runs = []
    for i, n in enumerate(lengths):
        rows = rng.integers(0, 2**32, size=(n, 5), dtype=np.uint32)
        rows[:, 0] = rng.integers(0, key_range, n)
        rows[:, 1] = rng.integers(0, min(key_range, 3), n)
        rows = rows[np.argsort(_keys_of(rows, key_kind), kind="stable")]
        rows[:, 3], rows[:, 4] = i, np.arange(n)
        runs.append((_keys_of(rows, key_kind), rows))
    return runs


# long enough that every merge crosses several blocks of the output
MERGE_LENGTHS = {
    "R1": [300],
    "R2": [900, 1300],
    "R3": [1257, 1, 2400],
    "R7": [0, 1200, 5000, 0, 3, 640, 3333],  # empty runs among the others
    "R24": [400 + 170 * (i % 5) for i in range(24)],
    "R2_one_empty": [0, 250],
}


@pytest.mark.parametrize("key_range", [50, 2**32], ids=["ties", "spread"])
@pytest.mark.parametrize("key_kind", ["u32", "u64"])
@pytest.mark.parametrize("lengths", list(MERGE_LENGTHS.values()),
                         ids=list(MERGE_LENGTHS))
def test_merge_runs_is_the_stable_sort_of_the_concatenation(
        lengths, key_kind, key_range):
    """Whole rows, byte for byte: ``merge_runs`` equals the stable argsort
    of the runs' concatenation — key-sorted, the earlier run first on
    equal keys, a run's own order kept — and leaves its inputs as they
    were."""
    rng = np.random.default_rng(len(lengths) * 1000 + key_range % 997)
    runs = _sorted_runs(rng, lengths, key_kind, key_range)
    before = [r.copy() for _, r in runs]
    keys, merged = merge_runs(runs)

    all_keys = np.concatenate([k for k, _ in runs])
    all_rows = np.concatenate([r for _, r in runs])
    order = np.argsort(all_keys, kind="stable")
    assert merged.dtype == all_rows.dtype and keys.dtype == all_keys.dtype
    assert merged.tobytes() == all_rows[order].tobytes()
    np.testing.assert_array_equal(keys, all_keys[order])
    # the tags say it without the reference: within one key the run
    # number never falls, and within one run the index only rises
    same_key = keys[1:] == keys[:-1]
    run, idx = merged[:, 3].astype(np.int64), merged[:, 4].astype(np.int64)
    assert (np.diff(run)[same_key] >= 0).all()
    assert (np.diff(idx)[same_key & (np.diff(run) == 0)] > 0).all()
    for (_, r), was in zip(runs, before):
        np.testing.assert_array_equal(r, was)
    if sum(1 for n in lengths if n) == 1:   # the one run passes through
        assert merged is next(r for _, r in runs if len(r))


def _layout_rows(layout, rng, n):
    if layout == "zero_width":      # keys-only shuffles: nothing to move
        return np.zeros((n, 0), np.uint8)
    if layout == "one_d":           # a row is one scalar
        return rng.integers(0, 2**63, n).astype(np.uint64)
    if layout == "strided":         # rows that are not contiguous in memory
        return rng.integers(0, 256, (n, 14), dtype=np.uint8)[:, ::2]
    if layout == "three_d":
        return rng.integers(0, 2**16, (n, 3, 2)).astype(np.uint16)
    raise AssertionError(layout)


@pytest.mark.parametrize("layout",
                         ["zero_width", "one_d", "strided", "three_d"])
def test_merge_runs_row_layouts(layout):
    """Rows move as opaque records whatever their shape and strides, and
    many blocks of the output are crossed (3 runs x 2,000 rows)."""
    rng = np.random.default_rng(5)
    runs = []
    for n in (2000, 1500, 2500):
        keys = np.sort(rng.integers(0, 400, n).astype(np.uint64))
        runs.append((keys, _layout_rows(layout, rng, n)))
    keys, merged = merge_runs(runs)
    all_rows = np.concatenate([r for _, r in runs])
    order = np.argsort(np.concatenate([k for k, _ in runs]), kind="stable")
    assert merged.shape == all_rows.shape and merged.dtype == all_rows.dtype
    np.testing.assert_array_equal(merged, all_rows[order])
    assert (keys[1:] >= keys[:-1]).all()


def test_external_merger_exact_and_bounded(tmp_path):
    rng = np.random.default_rng(2)
    W = 24
    budget = 1 << 20  # 1 MiB forces many spills for 8 MiB of rows
    all_keys = []
    with ExternalMerger(W, spill_dir=str(tmp_path), run_buffer_rows=1024,
                        memory_budget_bytes=budget) as m:
        for _ in range(32):
            keys = rng.integers(0, 2**63, size=8192).astype(np.uint64)
            m.add_batch(keys, rng.integers(0, 256, size=(8192, W),
                                           dtype=np.uint8))
            all_keys.append(keys)
        assert m.num_runs >= 8, "budget never triggered spilling"
        assert m.peak_buffer_bytes <= budget + 8192 * (8 + W)
        got_keys, got_payload = [], 0
        for keys, payload in m.sorted_batches():
            got_keys.append(keys)
            got_payload += len(payload)
        got = np.concatenate(got_keys)
    assert (np.diff(got.astype(np.float64)) >= 0).all()
    np.testing.assert_array_equal(np.sort(got),
                                  np.sort(np.concatenate(all_keys)))
    assert got_payload == 32 * 8192
    assert not os.listdir(tmp_path), "spill files not cleaned up"


def test_merge_runs_all_empty_preserves_shape():
    """A device whose runs are all empty must get an empty array of the
    INPUT row shape/dtype, not (0, 0) u8 — concatenation depends on it."""
    empty = np.zeros((0, 5), np.uint32)
    keys, rows = merge_runs([(empty[:, 0], empty), (empty[:, 0], empty)])
    assert rows.shape == (0, 5) and rows.dtype == np.uint32
    assert keys.dtype == np.uint32


def test_under_budget_skips_disk(tmp_path):
    """Data fitting the budget never touches disk."""
    with ExternalMerger(4, spill_dir=str(tmp_path),
                        memory_budget_bytes=1 << 20) as m:
        m.add_batch(np.array([5, 1], np.uint64), np.zeros((2, 4), np.uint8))
        m.add_batch(np.array([3], np.uint64), np.zeros((1, 4), np.uint8))
        k, _ = m.sorted_all()
        np.testing.assert_array_equal(k, [1, 3, 5])
        assert m.spilled_bytes == 0
        assert not os.listdir(tmp_path)


def test_empty_and_single_batch(tmp_path):
    with ExternalMerger(4, spill_dir=str(tmp_path)) as m:
        k, p = m.sorted_all()
        assert len(k) == 0 and p.shape == (0, 4)
    with ExternalMerger(4, spill_dir=str(tmp_path)) as m:
        m.add_batch(np.array([3, 1, 2], np.uint64),
                    np.arange(12, dtype=np.uint8).reshape(3, 4))
        k, p = m.sorted_all()
        np.testing.assert_array_equal(k, [1, 2, 3])
        np.testing.assert_array_equal(p[0], [4, 5, 6, 7])


_RLIMIT_SCRIPT = r"""
import resource, sys
import numpy as np
sys.path.insert(0, {repo!r})
from sparkrdma_tpu.shuffle.external import ExternalMerger

W = 56   # 64-byte rows
rows_total = {rows_total}
batch = 1 << 15
rng = np.random.default_rng(0)
m = ExternalMerger(W, spill_dir={spill!r}, memory_budget_bytes=4 << 20,
                   run_buffer_rows=4096)
checksum = np.uint64(0)
for start in range(0, rows_total, batch):
    keys = rng.integers(0, 2**63, size=batch).astype(np.uint64)
    checksum ^= np.bitwise_xor.reduce(keys)
    m.add_batch(keys, np.zeros((batch, W), np.uint8))

# cap the address space JUST above current usage: the ~{mb} MiB dataset can
# no longer be materialized, so only a bounded merge can finish
with open("/proc/self/status") as f:
    vm_kb = next(int(l.split()[1]) for l in f if l.startswith("VmSize"))
cap = (vm_kb << 10) + (64 << 20)
resource.setrlimit(resource.RLIMIT_AS, (cap, cap))
try:
    np.zeros(rows_total * (8 + W), np.uint8)  # the old read_sorted way
    print("CAP-NOT-EFFECTIVE")
except MemoryError:
    pass

count = 0
prev = -1
out_checksum = np.uint64(0)
for keys, payload in m.sorted_batches():
    assert int(keys[0]) >= prev
    assert (np.diff(keys.astype(np.float64)) >= 0).all()
    prev = int(keys[-1])
    count += len(keys)
    out_checksum ^= np.bitwise_xor.reduce(keys)
m.close()
assert count == rows_total, count
assert out_checksum == checksum
print("RLIMIT-MERGE-OK")
"""


def test_merge_completes_under_address_space_cap(tmp_path):
    """A reduce larger than the allowed address space completes: the spill
    merge is the only way through (materializing provably MemoryErrors)."""
    rows_total = 1 << 21  # 2M rows x 64B = 128 MiB
    script = _RLIMIT_SCRIPT.format(repo=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), rows_total=rows_total,
        spill=str(tmp_path), mb=128)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=300)
    if "CAP-NOT-EFFECTIVE" in proc.stdout:
        pytest.skip("RLIMIT_AS not enforceable on this platform")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "RLIMIT-MERGE-OK" in proc.stdout


def test_terasort_streamed_uses_merge(tmp_path):
    """The streamed TeraSort host merge is ``merge_runs`` and its
    output is unchanged (exact multiset + sorted per device)."""
    import os as _os
    _os.environ.setdefault("XLA_FLAGS",
                           "--xla_force_host_platform_device_count=8")
    import jax
    from jax.sharding import Mesh

    from sparkrdma_tpu.models.terasort import (
        TeraSortConfig, generate_rows, run_terasort_streamed)

    mesh = Mesh(np.array(jax.devices()[:8]), ("shuffle",))
    cfg = TeraSortConfig(rows_per_device=512, payload_words=4, out_factor=2)
    big = TeraSortConfig(rows_per_device=512 * 3, payload_words=4)
    rows = generate_rows(big, 8, seed=5)[: 8 * 512 * 3 - 700]  # ragged tail
    merged, rounds = run_terasort_streamed(mesh, cfg, rows)
    assert rounds == 3
    got = np.concatenate(merged)
    assert len(got) == len(rows)
    prev = -1
    for d, part in enumerate(merged):
        keys = part[:, 0].astype(np.int64)
        assert (np.diff(keys) >= 0).all(), f"device {d} unsorted"
        if len(keys):
            assert keys[0] >= prev
            prev = keys[-1]
    np.testing.assert_array_equal(
        np.sort(got[:, 0]), np.sort(rows[:, 0]))
