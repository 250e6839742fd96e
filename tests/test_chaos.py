"""Seeded chaos scenarios: end-to-end shuffle reduces under injected
faults, asserting byte-identical results via refetch/recompute.

Every scenario builds a real driver + multi-executor cluster over
loopback, scripts faults through the :mod:`sparkrdma_tpu.parallel.faults`
shim (seeded — a failing run replays exactly from the seed printed in
the assertion message), runs a reduce through the hardened path, and
checks the result against the fault-free ground truth.

Fast scenarios run in tier-1 (marked ``chaos``); the wide sweep is
``chaos + slow`` and driven by ``scripts/run_chaos.sh``, which iterates
seeds via ``CHAOS_SEED``.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.parallel import messages as M
from sparkrdma_tpu.parallel.faults import (
    BLACKHOLE,
    CORRUPT,
    CORRUPT_AT_REST,
    DELAY,
    DISCONNECT,
    EIO,
    ENOSPC,
    REFUSE_CONNECT,
    SLOW_DISK,
    TORN_WRITE,
    FaultInjector,
    StorageFaultInjector,
)
from sparkrdma_tpu.shuffle.ha import (DriverStandby, FileLeaseStore,
                                      InMemoryLeaseStore)
from sparkrdma_tpu.shuffle.manager import (PartitionerSpec, ShuffleHandle,
                                           TpuShuffleManager)
from sparkrdma_tpu.shuffle.recovery import run_map_stage, run_reduce_with_retry

pytestmark = pytest.mark.chaos

SEED = int(os.environ.get("CHAOS_SEED", "0"))
# dataplane under chaos: 1 = coalesced vectored reads (the default), 0 =
# the per-map fallback; scripts/run_chaos.sh sweeps both
COALESCE = os.environ.get("CHAOS_COALESCE", "1") not in ("0", "false")
# storage-fault sweep gate (CHAOS_DISK=0 runs the network-only matrix)
DISK = os.environ.get("CHAOS_DISK", "1") not in ("0", "false")
# metadata plane under chaos: 1 = epoch-validated location caches (the
# default), 0 = the cold pre-plane path; run_chaos.sh sweeps both —
# the failure paths differ (a warm reducer holds locations a loss just
# invalidated; a cold one re-syncs every time)
WARM = os.environ.get("CHAOS_WARM", "1") not in ("0", "false")
# adaptive reduce planning under chaos: 1 runs the whole matrix with
# adaptive_plan on (publishes carry size vectors into the driver's
# histogram, plans build on demand) so the planner's publish/plan paths
# see every injected fault; run_chaos.sh sweeps both. The mid-stage
# re-plan scenario below forces it on regardless.
SKEW = os.environ.get("CHAOS_SKEW", "0") not in ("0", "false")
# push-merge dataplane under chaos: 1 runs the whole byte-identity
# matrix with background pushes, merge targets, and merged-segment-first
# reads active (partial finalize mid-reduce included) so every injected
# fault also crosses the push/merge/serve path; run_chaos.sh sweeps
# both. Scenarios asserting exact wire counts or recompute semantics pin
# push_merge=False — the dedicated merge scenarios below own those
# assertions with deterministic coverage.
MERGE = os.environ.get("CHAOS_MERGE", "0") not in ("0", "false")
# planned push under chaos: 1 runs the whole byte-identity matrix with
# sender-driven planned pushes active in the BACKGROUND of the faulted
# reduce (adaptive_plan forced on, the driver publishes a ReducePlan
# right after the map stage, pushers race the reducer, staged ranges
# resolve first at their planned slots) so the pushed dataplane and its
# fences cross every injected fault; run_chaos.sh sweeps both. The
# dedicated kill-the-planned-reducer scenario below runs regardless.
PUSHPLAN = os.environ.get("CHAOS_PUSHPLAN", "0") not in ("0", "false")
# tenancy under chaos: 1 runs the whole matrix with every shuffle
# registered under a real tenant id (TenantMapMsg pushes, serve-path
# DRR queueing, disk-ledger charging, admission gating with a
# generous cap, and a live TTL sweeper that must expire NOTHING
# mid-test) so the tenancy plumbing sees every injected fault;
# run_chaos.sh sweeps both. The dedicated cross-tenant isolation
# scenarios below assert the blast-radius invariants regardless.
TENANT = os.environ.get("CHAOS_TENANT", "0") not in ("0", "false")
# elastic membership under chaos: 1 runs the wide byte-identity
# matrices with random join/drain CHURN in the background — a fresh
# executor joins mid-reduce (announce + membership bump + health-watch
# registration cross every injected fault) and is then gracefully
# decommissioned — so the elastic control plane sees the whole fault
# matrix; run_chaos.sh sweeps both. The dedicated scale-up/drain-down
# acceptance scenarios below run regardless.
ELASTIC = os.environ.get("CHAOS_ELASTIC", "0") not in ("0", "false")
# driver HA under chaos: 1 runs the wide byte-identity matrices with a
# lease-armed primary, a warm standby shadowing its op log, and a
# primary CRASH at a seeded random point inside the reduce window — the
# standby CAS-takes the next lease term, replays, and re-points the
# executors via TakeoverMsg, so reducer syncs ride the DriverClient
# retry envelope across a real failover under every injected fault;
# run_chaos.sh sweeps both. The dedicated SIGKILL acceptance scenario
# (separate primary process, kill -9, zero map re-executions) runs
# regardless.
DRIVER = os.environ.get("CHAOS_DRIVER", "0") not in ("0", "false")
# native client fetch engine under chaos: 1 runs the whole matrix on
# the native dataplane — the C++ block server serves and the C client
# engine (csrc/fetchclient.cpp) fetches into pool leases — so every
# injected control-plane fault, disk fault, and membership event crosses
# the native engine's fallback-to-Python envelope (conn death mid-batch,
# leases released on unwind, suspect re-resolution). Data-frame faults
# inject at the Python transport layer and so don't reach the C
# dataplane; the byte-identity assertions are the point here.
# run_chaos.sh sweeps both; requires the native .so (silently degrades
# to the Python dataplane where it isn't built).
NATIVE_FETCH = os.environ.get("CHAOS_NATIVE_FETCH",
                              "0") not in ("0", "false")
# partitioned metadata ownership under chaos: 1 runs the whole matrix
# with metadata_shards=2 + shard_ownership=True — executors publish
# map outputs DIRECTLY to per-shard write owners (fence CAS on the
# owner, batch convergence into the driver, per-shard standby streams)
# so every injected fault also crosses the sharded control-plane write
# path and its driver-direct fallback; run_chaos.sh sweeps both. The
# dedicated kill-a-shard-owner scenario below runs whenever sharding
# is on and asserts the per-shard failover costs ZERO re-executions.
SHARD = os.environ.get("CHAOS_SHARD", "0") not in ("0", "false")
# cold tier under chaos: 1 runs the whole matrix with the
# disaggregated cold tier active (push_merge forced on, finalized
# segments tiering to a blob store in the BACKGROUND of every faulted
# scenario — uploads, publishes, and tombstone reaps cross the whole
# fault matrix), plus the dedicated cold scenarios below: the
# full-fleet-loss restore under a seeded blob-fault matrix, and the
# store-outage degrade-to-hot-only acceptance. run_chaos.sh sweeps
# both. Scenarios that pin push_merge=False keep their pin (the cold
# tier rides the merge plane, so it is inert there).
COLD = os.environ.get("CHAOS_COLD", "0") not in ("0", "false")
# CHAOS_LOCKGRAPH=1: run every scenario under the lock-order shim
# (sparkrdma_tpu/analysis/lockgraph.py) so the chaos matrix doubles as
# race detection — faults drive the rare teardown/retry/suspect paths
# where lock-order inversions hide. Any cycle fails the module.
LOCKGRAPH = os.environ.get("CHAOS_LOCKGRAPH", "0") not in ("0", "false")


@pytest.fixture(scope="module", autouse=True)
def _chaos_lockgraph():
    if not LOCKGRAPH:
        yield
        return
    from engine_helpers import lockgraph_module_guard
    yield from lockgraph_module_guard()


# Faults that cut or corrupt DATA frames inject at the Python transport
# layer, which the native dataplane bypasses entirely — scenarios that
# assert those faults FIRED pin the Python dataplane (the native
# engine's own anomaly coverage lives in tests/test_native_fetch.py and
# the sanitizer harness; the byte-identity matrix still sweeps it).
PY_DATAPLANE = dict(use_cpp_runtime=False, native_fetch=False)


def _conf(**kw):
    base = dict(connect_timeout_ms=3000, max_connection_attempts=2,
                retry_backoff_base_ms=10, retry_backoff_cap_ms=80,
                fetch_retry_budget=3, use_cpp_runtime=NATIVE_FETCH,
                native_fetch=NATIVE_FETCH,
                pre_warm_connections=False,
                coalesce_reads=COALESCE,
                location_epoch_cache=WARM,
                adaptive_plan=SKEW or PUSHPLAN,
                planned_push=PUSHPLAN,
                push_merge=MERGE,
                collect_shuffle_reader_stats=True)
    if TENANT:
        # the tenancy sweep dimension: a generous admission cap (the
        # gate runs, nothing sheds) and a live TTL sweeper whose TTL no
        # scenario can reach — expiry mid-fault would be its own bug
        base.update(admission_max_inflight=16, shuffle_ttl_ms=120_000)
    if DRIVER:
        # the driver-HA sweep dimension: a lease short enough that the
        # failover lands inside the scenario, and a request deadline
        # generous enough that executor retries ride through the
        # no-primary window instead of surfacing it
        base.update(ha_standbys=1, driver_lease_ms=900,
                    request_deadline_ms=20_000)
    if SHARD:
        # the partitioned-ownership sweep dimension: two write owners,
        # a small batch so convergence happens repeatedly inside every
        # scenario's publish window
        base.update(metadata_shards=2, shard_ownership=True,
                    shard_batch_entries=4)
    base.update(kw)
    return TpuShuffleConf(**base)


def _cluster(tmp_path, n=3, **kw):
    if COLD:
        # the cold-tier sweep dimension: finalized segments tier to a
        # per-test blob store in the background of every scenario
        # (explicit pins — push_merge=False wire-count scenarios — win)
        kw.setdefault("cold_tier", True)
        kw.setdefault("cold_tier_path", str(tmp_path / "cold"))
        kw.setdefault("push_merge", True)
    conf = _conf(**kw)
    if DRIVER:
        driver = TpuShuffleManager(conf, is_driver=True,
                                   lease_store=InMemoryLeaseStore(),
                                   lease_holder="primary")
    else:
        driver = TpuShuffleManager(conf, is_driver=True)
    if TENANT:
        # every scenario's shuffles register under a real tenant id so
        # TenantMapMsg pushes, DRR serve queues, and ledger charging
        # cross every injected fault (explicit tenant= kwargs win)
        orig_register = driver.register_shuffle

        def register_with_tenant(*args, **kwargs):
            kwargs.setdefault("tenant", 1)
            return orig_register(*args, **kwargs)

        driver.register_shuffle = register_with_tenant
    execs = [TpuShuffleManager(conf, driver_addr=driver.driver_addr,
                               executor_id=str(i),
                               spill_dir=str(tmp_path / f"e{i}"))
             for i in range(n)]
    for ex in execs:
        ex.executor.wait_for_members(n)
    return driver, execs


def _map_fn(writer, map_id):
    rng = np.random.default_rng(1000 + map_id)
    keys = rng.integers(0, 5000, size=500).astype(np.uint64)
    writer.write_batch(keys)


def _reduce_fn(mgr, handle):
    reader = mgr.get_reader(handle, 0, handle.num_partitions)
    keys, _ = reader.read_all()
    return np.sort(keys)


def _expected(num_maps):
    return np.sort(np.concatenate(
        [np.random.default_rng(1000 + m).integers(0, 5000, 500)
         for m in range(num_maps)]).astype(np.uint64))


def _shutdown(driver, execs):
    for ex in execs:
        ex.stop()
    driver.stop()


class _ElasticChurn:
    """CHAOS_ELASTIC background churn: one executor JOINS mid-scenario
    (announce, membership bump, health-watch registration, placement
    recompute) and is then gracefully DECOMMISSIONED — so every fault
    in the matrix also crosses the elastic control plane. The churner
    owns no shuffle data, so the drain is coverage-trivial and the
    scenario's byte-identity assertions are untouched."""

    def __init__(self, conf, driver, tmp_path):
        self._conf = conf
        self._driver = driver
        self._dir = str(tmp_path / "churn")
        self._joiner = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="elastic-churn")
        self._thread.start()

    def _run(self):
        try:
            self._joiner = TpuShuffleManager(
                self._conf, driver_addr=self._driver.driver_addr,
                executor_id="churn", spill_dir=self._dir)
            self._joiner.join_cluster()
            slot = self._joiner.executor.exec_index(timeout=5)
            time.sleep(0.15)  # let the scenario's reduce overlap the join
            self._driver.driver.decommission_slot(slot, deadline_ms=5000)
        except Exception:  # noqa: BLE001 — churn must never fail the
            # scenario; its own assertions live in the dedicated tests
            pass

    def stop(self):
        self._thread.join(timeout=10)
        if self._joiner is not None:
            self._joiner.stop()


class _DriverFailover:
    """CHAOS_DRIVER=1 background churn: a warm standby shadows the
    primary's op log; at a seeded random point inside the reduce window
    the primary CRASHES (server down, lease renewals stop — the
    in-process stand-in for SIGKILL; the real kill -9 acceptance is the
    dedicated scenario at the bottom of this file). The standby
    CAS-takes the next lease term, replays, and re-points the executors
    via TakeoverMsg, so the scenario's byte-identity assertions hold
    unchanged: reducer syncs ride the DriverClient retry envelope
    across the outage."""

    def __init__(self, driver):
        self._driver = driver
        ep = driver.driver
        self.standby = DriverStandby(driver.conf, ep.lease_store,
                                     "chaos-standby",
                                     primary_addr=ep.address).start()
        # seeded kill point: varies across the sweep, replays exactly
        rng = np.random.default_rng(SEED + 7700)
        self._delay = 0.05 + rng.random() * 0.3
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="driver-failover-churn")
        self._thread.start()

    def _run(self):
        time.sleep(self._delay)
        try:
            self._driver.driver.stop()
        except Exception:  # noqa: BLE001 — the crash itself must never
            # fail the scenario; the assertions live in the test body
            pass

    def stop(self):
        self._thread.join(timeout=10)
        self.standby.stop()


# -- tier-1 chaos scenarios (fast, deterministic counts) -----------------


def test_chaos_corruption_healed_by_refetch(tmp_path):
    """Bit-flipped fetch payloads are caught by the CRC32 trailer and
    refetched within the budget; the reduce is byte-identical and the
    failure counters show the retries that absorbed it."""
    driver, execs = _cluster(tmp_path, push_merge=False, **PY_DATAPLANE)
    injector = FaultInjector(seed=SEED)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        injector.install_endpoint(execs[0].executor)
        injector.add(CORRUPT, msg_type=M.FetchBlocksResp, times=3)

        reader = execs[0].get_reader(handle, 0, handle.num_partitions)
        keys, _ = reader.read_all()
        np.testing.assert_array_equal(np.sort(keys), _expected(6),
                                      err_msg=f"seed={SEED}")
        assert injector.fired_count(CORRUPT) == 3, f"seed={SEED}"
        assert reader.metrics.checksum_failures >= 3, f"seed={SEED}"
        assert reader.metrics.retries >= 3, f"seed={SEED}"
        assert reader.metrics.failed_fetches == 0, f"seed={SEED}"
        snap = execs[0].reader_stats.snapshot()
        assert snap["failures"]["checksum_mismatches"] >= 3, snap
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_connect_refusal_burst(tmp_path):
    """A refusal burst at fetch time is absorbed by connect retries with
    backoff plus the fetch retry envelope — no stage retry needed.
    push_merge pinned off: merged resolution can satisfy the reduce
    without any fresh dial, so the refusal count would depend on
    finalize timing."""
    driver, execs = _cluster(tmp_path, push_merge=False)
    injector = FaultInjector(seed=SEED)
    map_runs = []
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        injector.install_endpoint(execs[0].executor)
        injector.add(REFUSE_CONNECT, times=3)

        def counting_map_fn(writer, map_id):
            map_runs.append(map_id)
            _map_fn(writer, map_id)

        got = run_reduce_with_retry(execs, handle, counting_map_fn,
                                    _reduce_fn, reducer_index=0,
                                    driver=driver)
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        assert injector.fired_count(REFUSE_CONNECT) == 3, f"seed={SEED}"
        assert map_runs == [], \
            f"seed={SEED}: transient refusals must not escalate to recompute"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_transient_disconnect_absorbed(tmp_path):
    """One mid-stream disconnect (response cut on the wire) fails the
    whole in-flight window, but the retry envelope re-dials and refetches
    — byte-identical, no recompute."""
    driver, execs = _cluster(tmp_path, read_ahead_depth=4, **PY_DATAPLANE)
    injector = FaultInjector(seed=SEED)
    map_runs = []
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        injector.install_endpoint(execs[0].executor)
        injector.add(DISCONNECT, msg_type=M.FetchBlocksResp, times=1)

        def counting_map_fn(writer, map_id):
            map_runs.append(map_id)
            _map_fn(writer, map_id)

        got = run_reduce_with_retry(execs, handle, counting_map_fn,
                                    _reduce_fn, reducer_index=0,
                                    driver=driver)
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        assert injector.fired_count(DISCONNECT) == 1, f"seed={SEED}"
        assert map_runs == [], f"seed={SEED}"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_peer_kill_mid_fetch_recompute(tmp_path):
    """A map-output owner dies while the reducer's window is in flight:
    location reads from the victim succeed, then every data response
    disconnects mid-stream and every re-dial is refused (a peer that
    died between STEP 2 and STEP 3). The failure exhausts the retry
    budget, escalates to FetchFailed, the stage retry recomputes on
    survivors — never on the dead slot — and the reduce completes
    byte-identical."""
    driver, execs = _cluster(tmp_path, read_ahead_depth=4,
                             fetch_retry_budget=1, push_merge=False,
                             **PY_DATAPLANE)
    injector = FaultInjector(seed=SEED)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        victim_slot = execs[2].executor.exec_index()
        victim_addr = (execs[2].executor.manager_id.rpc_host,
                       execs[2].executor.manager_id.rpc_port)
        injector.install_endpoint(execs[0].executor)
        injector.add(DISCONNECT, peer=victim_addr,
                     msg_type=M.FetchBlocksResp)
        # after=1: the first dial (location reads) succeeds — the peer
        # "dies" between STEP 2 and STEP 3; every re-dial then bounces
        injector.add(REFUSE_CONNECT, peer=victim_addr, after=1)

        # the REAL server dies the instant the injected disconnect fires,
        # so the recovery loop's reachability probe (which uses a raw
        # socket, not the shimmed cache) also sees a dead peer and the
        # tombstone gate opens
        done = threading.Event()

        def kill_on_disconnect():
            while (injector.fired_count(DISCONNECT) == 0
                   and not done.wait(0.005)):
                pass
            execs[2].executor.server.stop()

        killer = threading.Thread(target=kill_on_disconnect)
        killer.start()
        try:
            got = run_reduce_with_retry(execs, handle, _map_fn, _reduce_fn,
                                        reducer_index=0, driver=driver)
        finally:
            done.set()
            killer.join()
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        assert injector.fired_count(DISCONNECT) >= 1, f"seed={SEED}"
        table = execs[0].executor.get_driver_table(1, 6, timeout=5)
        for m in range(6):
            assert table.entry(m)[1] != victim_slot, f"seed={SEED}"
        # the driver handle fed the tombstone path
        from sparkrdma_tpu.parallel.endpoints import TOMBSTONE
        assert driver.driver.members()[victim_slot] == TOMBSTONE, \
            f"seed={SEED}"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_blackhole_partition_heartbeat_escalates(tmp_path):
    """A silently partitioned peer (requests vanish, nothing bounces) is
    detected by the heartbeat monitor well before the 10 s request
    deadline; the suspect verdict fails the fetch into the recompute
    loop and the reduce still completes."""
    interval_ms = 200
    driver, execs = _cluster(tmp_path, request_deadline_ms=10000,
                             heartbeat_interval_ms=interval_ms,
                             heartbeat_misses=2, fetch_retry_budget=2,
                             push_merge=False)
    injector = FaultInjector(seed=SEED)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        victim = execs[1].executor.manager_id
        injector.install_endpoint(execs[0].executor)
        # partition: everything the victim sends back is dropped
        injector.add(BLACKHOLE, peer=(victim.rpc_host, victim.rpc_port))

        t0 = time.monotonic()
        got = run_reduce_with_retry(execs, handle, _map_fn, _reduce_fn,
                                    reducer_index=0, driver=driver)
        wall = time.monotonic() - t0
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        ep = execs[0].executor
        assert ep.suspect_events >= 1, f"seed={SEED}: heartbeat never fired"
        # detection + recompute must ride the heartbeat, not the 10 s
        # request deadline (let alone a TCP-scale timeout)
        assert wall < 8.0, \
            f"seed={SEED}: {wall:.1f}s — waited out deadlines instead of " \
            f"heartbeat (2x interval = {2 * interval_ms / 1000:.1f}s)"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_vectored_corruption_refetches_only_affected_ranges(tmp_path):
    """A corrupt sub-block inside a coalesced (cross-map) vectored
    response is isolated by the per-block CRC trailer: ONLY the affected
    map's ranges refetch (not the whole vectored request), and the
    retry/trace attribution names that map."""
    if not COALESCE:
        pytest.skip("per-map dataplane sweep: vectored path disabled")
    from sparkrdma_tpu.shuffle.reader import TpuShuffleReader
    from sparkrdma_tpu.utils.trace import Tracer

    driver, execs = _cluster(tmp_path, n=2, push_merge=False)
    injector = FaultInjector(seed=SEED)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        # every map on ONE peer: the reducer coalesces all 6 maps into a
        # single vectored request (6 segments, 24 blocks)
        run_map_stage(execs, handle, _map_fn,
                      placement={m: 1 for m in range(6)})
        injector.install_endpoint(execs[0].executor)
        injector.add(CORRUPT, msg_type=M.FetchBlocksResp, times=1)

        tracer = Tracer()
        reader = TpuShuffleReader(execs[0].executor, execs[0].resolver,
                                  _conf(), handle.shuffle_id, 6, 0, 4, 0,
                                  tracer=tracer)
        keys, _ = reader.read_all()
        np.testing.assert_array_equal(np.sort(keys), _expected(6),
                                      err_msg=f"seed={SEED}")
        m = reader.metrics
        assert injector.fired_count(CORRUPT) == 1, f"seed={SEED}"
        assert m.checksum_failures >= 1, f"seed={SEED}"
        assert m.failed_fetches == 0, f"seed={SEED}"
        # exactly one vectored request covered all 6 maps...
        vec = [e for e in tracer._events if e["name"] == "fetch.vectored"]
        assert len(vec) == 1 and vec[0]["args"]["maps"] == 6, f"seed={SEED}"
        # ...and the heal refetched ONE map's ranges, not the request:
        # a single bit flip lands in one block (or its trailer word), so
        # one segment of 4 blocks goes back on the wire
        refetches = [e for e in tracer._events
                     if e["name"] == "fetch.refetch_range"]
        assert len(refetches) == 1, f"seed={SEED}: {refetches}"
        blamed = refetches[0]["args"]["map"]
        assert 0 <= blamed < 6, f"seed={SEED}"
        assert refetches[0]["args"]["blocks"] < vec[0]["args"]["blocks"], \
            f"seed={SEED}: refetch was not narrower than the request"
        # the retry instant attributes the SAME map the refetch named
        retries = [e for e in tracer._events if e["name"] == "fetch.retry"]
        assert retries and all(e["args"]["map"] == blamed
                               for e in retries), f"seed={SEED}"
        # wire accounting: 1 batched location RPC + 1 vectored read + 1
        # range refetch — nothing else
        assert m.requests_per_reduce == 3, f"seed={SEED}: {m}"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def _wait_merge_ready(driver, execs, handle):
    """Deterministic point past the asynchronous push+finalize pipeline:
    every pusher drained, every (map, partition) covered at the driver."""
    from sparkrdma_tpu.shuffle.push_merge import wait_for_coverage
    for ex in execs:
        assert ex.pusher is not None and ex.pusher.drain(15), \
            f"seed={SEED}: pusher did not drain"
    assert wait_for_coverage(driver.driver, handle.shuffle_id,
                             handle.num_maps, handle.num_partitions,
                             timeout=15), \
        f"seed={SEED}: merged coverage never completed"


def test_chaos_merge_repoint_zero_reexecutions(tmp_path):
    """The push-merge recovery acceptance: an executor owning map
    outputs dies MID-REDUCE with merge_replicas >= 1 and full replica
    coverage on survivors — the stage completes with ZERO map
    re-executions (a location-table flip to the replicas), the dead
    slot is tombstoned, and the retry serves every lost map from merged
    segments, byte-identical to the fault-free run."""
    driver, execs = _cluster(tmp_path, fetch_retry_budget=1,
                             push_merge=True, merge_replicas=2,
                             push_deadline_ms=8000, **PY_DATAPLANE)
    injector = FaultInjector(seed=SEED)
    map_runs = []
    merged_metrics = []
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        _wait_merge_ready(driver, execs, handle)
        victim_slot = execs[2].executor.exec_index()
        victim_addr = (execs[2].executor.manager_id.rpc_host,
                       execs[2].executor.manager_id.rpc_port)
        injector.install_endpoint(execs[0].executor)
        # the victim dies between the reducer's location reads and its
        # data reads (the peer_kill choreography): the first in-flight
        # response disconnects, every re-dial bounces, and the REAL
        # server dies so the tombstone probe agrees
        injector.add(DISCONNECT, peer=victim_addr,
                     msg_type=M.FetchBlocksResp)
        injector.add(REFUSE_CONNECT, peer=victim_addr, after=1)
        done = threading.Event()

        def kill_on_disconnect():
            while (injector.fired_count(DISCONNECT) == 0
                   and not done.wait(0.005)):
                pass
            execs[2].executor.server.stop()

        def counting_map_fn(writer, map_id):
            map_runs.append(map_id)
            _map_fn(writer, map_id)

        def reduce_fn(mgr, h, state={"attempt": 0}):
            # attempt 1 fetches per-map (a reducer that had not learned
            # the merged directory yet) so the kill lands mid-reduce;
            # the RETRY resolves merged-segment-first — the re-point
            state["attempt"] += 1
            if state["attempt"] == 1:
                from sparkrdma_tpu.shuffle.reader import TpuShuffleReader
                reader = TpuShuffleReader(
                    mgr.executor, mgr.resolver, _conf(push_merge=False),
                    h.shuffle_id, h.num_maps, 0, h.num_partitions, 0)
            else:
                reader = mgr.get_reader(h, 0, h.num_partitions)
            keys, _ = reader.read_all()
            merged_metrics.append(reader.metrics)
            return np.sort(keys)

        killer = threading.Thread(target=kill_on_disconnect)
        killer.start()
        try:
            got = run_reduce_with_retry(execs, handle, counting_map_fn,
                                        reduce_fn, reducer_index=0,
                                        driver=driver)
        finally:
            done.set()
            killer.join()
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        # ZERO map re-executions: recovery re-pointed every lost map to
        # a surviving merged replica instead of recomputing
        assert map_runs == [], \
            f"seed={SEED}: maps {map_runs} re-executed despite replicas"
        from sparkrdma_tpu.parallel.endpoints import TOMBSTONE
        assert driver.driver.members()[victim_slot] == TOMBSTONE, \
            f"seed={SEED}"
        # the dead slot's segments left the directory; survivors' stayed
        d = driver.driver.merged_directory(1)
        assert d is not None and all(
            e.slot != victim_slot
            for p in d.partitions() for e in d.entries(p)), f"seed={SEED}"
        # the retry actually served merged segments
        assert merged_metrics[-1].merged_reads >= 1, f"seed={SEED}"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_merge_corrupt_segment_degrades_per_map(tmp_path):
    """At-rest rot on a merged segment: the reducer-side entry CRC
    catches it and that partition DEGRADES to the per-map dataplane —
    byte-identical output, merged_fallbacks counted, no stage retry."""
    import glob

    driver, execs = _cluster(tmp_path, push_merge=True, merge_replicas=1,
                             push_deadline_ms=8000)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        _wait_merge_ready(driver, execs, handle)
        # rot the segment the reducer WILL choose for partition 0 (the
        # directory's widest-coverage entry — the fetcher's own policy),
        # on disk on its hosting executor: the serve path carries the
        # rotted bytes and the wire CRC trailer is computed over them,
        # so only the published entry CRC can tell
        d = driver.driver.merged_directory(1)
        chosen = d.entries(0)[0]
        slot_dirs = {execs[i].executor.exec_index():
                     str(tmp_path / f"e{i}") for i in range(len(execs))}
        seg = os.path.join(slot_dirs[chosen.slot], "merge", "seg_1_0.bin")
        assert glob.glob(seg), f"seed={SEED}: {seg} missing"
        with open(seg, "r+b") as f:
            f.seek(0)
            first = f.read(1)
            f.seek(0)
            f.write(bytes([first[0] ^ 0xFF]))

        reader = execs[0].get_reader(handle, 0, handle.num_partitions)
        keys, _ = reader.read_all()
        np.testing.assert_array_equal(np.sort(keys), _expected(6),
                                      err_msg=f"seed={SEED}")
        m = reader.metrics
        assert m.merged_fallbacks >= 1, f"seed={SEED}: {m}"
        assert m.checksum_failures >= 1, f"seed={SEED}: {m}"
        assert m.failed_fetches == 0, f"seed={SEED}: {m}"
        assert m.merged_reads >= 1, \
            f"seed={SEED}: clean partitions should still serve merged"
    finally:
        _shutdown(driver, execs)


def test_chaos_pushplan_reducer_kill_mid_push(tmp_path):
    """The planned reducer for partition 0 dies MID-PUSH — after
    accepting its first pushed range, while the senders' replay is
    still streaming toward it. Staged inputs die with it; the reduce on
    a survivor serves its OWN staged partitions pushed-first,
    pull-fills every hole, recovery recomputes the dead slot's maps,
    and the output is an EXACT multiset of the fault-free ground truth
    — zero duplicate rows, zero lost rows."""
    driver, execs = _cluster(tmp_path, adaptive_plan=True,
                             planned_push=True, push_merge=False,
                             coalesce_target_bytes=2048,
                             fetch_retry_budget=1)
    holder = {"victim_slot": None}
    killed = threading.Event()

    def arm(ep, orig):
        def handler(conn, msg):
            orig(conn, msg)
            if (holder["victim_slot"] is not None
                    and ep.exec_index() == holder["victim_slot"]
                    and not killed.is_set()):
                killed.set()
                # stop from a fresh thread: the handler runs on a serve
                # worker the stop would otherwise wait on
                threading.Thread(target=ep.server.stop,
                                 daemon=True).start()
        return handler

    for ex in execs:
        ep = ex.executor
        ep._on_push_planned = arm(ep, ep._on_push_planned)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=8,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        plan = driver.driver.build_reduce_plan(1)
        assert plan is not None, f"seed={SEED}"
        holder["victim_slot"] = plan.placement_of(0)
        assert killed.wait(10), \
            f"seed={SEED}: no push ever reached the planned reducer"
        victim_idx = next(
            i for i, ex in enumerate(execs)
            if ex.executor.exec_index() == holder["victim_slot"])
        reducer_idx = next(i for i in range(len(execs))
                           if i != victim_idx)
        got = run_reduce_with_retry(execs, handle, _map_fn, _reduce_fn,
                                    reducer_index=reducer_idx,
                                    max_stage_retries=3, driver=driver)
        # zero duplicate rows, zero lost rows: exact multiset equality
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        from sparkrdma_tpu.parallel.endpoints import TOMBSTONE
        assert driver.driver.members()[holder["victim_slot"]] \
            == TOMBSTONE, f"seed={SEED}"
        # the senders saw the death, not an error: failed planned pushes
        # are shed (the ranges stay pull-fetched), never worker-fatal
        snaps = [ex.executor.pushed_store.snapshot()
                 for i, ex in enumerate(execs) if i != victim_idx]
        assert all(s is not None for s in snaps), f"seed={SEED}"
    finally:
        _shutdown(driver, execs)


# -- cross-tenant isolation (the CHAOS_TENANT satellite) -----------------


def _map_fn_t2(writer, map_id):
    rng = np.random.default_rng(3000 + map_id)
    writer.write_batch(rng.integers(0, 5000, size=500).astype(np.uint64))


def _expected_t2(num_maps):
    return np.sort(np.concatenate(
        [np.random.default_rng(3000 + m).integers(0, 5000, 500)
         for m in range(num_maps)]).astype(np.uint64))


def test_chaos_tenant_executor_loss_isolated(tmp_path):
    """An executor loss inside tenant 1's shuffle must not perturb
    tenant 2: tenant 1 heals by recompute-on-survivors (its maps
    re-execute), tenant 2's shuffle — whose outputs never touched the
    dead slot — reads byte-identical with ZERO re-executions, zero
    failed fetches, and its location epoch UNBUMPED (the tombstone
    invalidates only shuffles naming the dead slot)."""
    driver, execs = _cluster(tmp_path, read_ahead_depth=4,
                             fetch_retry_budget=1, push_merge=False,
                             **PY_DATAPLANE)
    injector = FaultInjector(seed=SEED)
    t1_reruns = []
    try:
        h1 = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                     partitioner=PartitionerSpec("modulo"),
                                     tenant=1)
        run_map_stage(execs, h1, _map_fn)  # tenant 1 spans every slot
        # tenant 2's maps live ONLY on the survivors (execs 0 and 1)
        h2 = driver.register_shuffle(2, num_maps=4, num_partitions=4,
                                     partitioner=PartitionerSpec("modulo"),
                                     tenant=2)
        for m in range(4):
            w = execs[m % 2].get_writer(h2, m)
            _map_fn_t2(w, m)
            w.close()
        epoch2_before = driver.driver.epoch_of(2)

        victim_addr = (execs[2].executor.manager_id.rpc_host,
                       execs[2].executor.manager_id.rpc_port)
        victim_slot = execs[2].executor.exec_index()
        injector.install_endpoint(execs[0].executor)
        injector.add(DISCONNECT, peer=victim_addr,
                     msg_type=M.FetchBlocksResp)
        injector.add(REFUSE_CONNECT, peer=victim_addr, after=1)
        done = threading.Event()

        def kill_on_disconnect():
            while (injector.fired_count(DISCONNECT) == 0
                   and not done.wait(0.005)):
                pass
            execs[2].executor.server.stop()

        def counting_map_fn(writer, map_id):
            t1_reruns.append(map_id)
            _map_fn(writer, map_id)

        killer = threading.Thread(target=kill_on_disconnect)
        killer.start()
        try:
            got1 = run_reduce_with_retry(execs, h1, counting_map_fn,
                                         _reduce_fn, reducer_index=0,
                                         driver=driver)
        finally:
            done.set()
            killer.join()
        np.testing.assert_array_equal(got1, _expected(6),
                                      err_msg=f"seed={SEED}")
        assert t1_reruns, f"seed={SEED}: the fault never landed"
        from sparkrdma_tpu.parallel.endpoints import TOMBSTONE
        assert driver.driver.members()[victim_slot] == TOMBSTONE, \
            f"seed={SEED}"

        # tenant 2: byte-identical, no retries, no re-executions (its
        # read succeeding outside any retry loop IS the proof), and the
        # tombstone did not bump its epoch — its warm caches survive
        reader2 = execs[0].get_reader(h2, 0, 4)
        keys2, _ = reader2.read_all()
        np.testing.assert_array_equal(np.sort(keys2), _expected_t2(4),
                                      err_msg=f"seed={SEED}")
        m2 = reader2.metrics
        assert m2.failed_fetches == 0, f"seed={SEED}: {m2}"
        assert m2.retries == 0, f"seed={SEED}: {m2}"
        assert driver.driver.epoch_of(2) == epoch2_before, \
            f"seed={SEED}: tenant 2's epoch bumped by tenant 1's loss"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def test_chaos_tenant_corrupt_segment_isolated(tmp_path):
    """At-rest rot on tenant 1's merged segment: tenant 1's read
    degrades that partition per-map (byte-identical, fallback counted);
    tenant 2's shuffle on the same cluster still serves MERGED with
    zero fallbacks, zero checksum failures, zero re-executions — the
    corruption's blast radius is one tenant's one partition."""
    import glob

    driver, execs = _cluster(tmp_path, push_merge=True, merge_replicas=1,
                             push_deadline_ms=8000)
    try:
        h1 = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                     partitioner=PartitionerSpec("modulo"),
                                     tenant=1)
        run_map_stage(execs, h1, _map_fn)
        _wait_merge_ready(driver, execs, h1)
        h2 = driver.register_shuffle(2, num_maps=6, num_partitions=4,
                                     partitioner=PartitionerSpec("modulo"),
                                     tenant=2)
        run_map_stage(execs, h2, _map_fn_t2)
        _wait_merge_ready(driver, execs, h2)

        # rot the segment tenant 1's reducer WILL choose for partition 0
        d = driver.driver.merged_directory(1)
        chosen = d.entries(0)[0]
        slot_dirs = {execs[i].executor.exec_index():
                     str(tmp_path / f"e{i}") for i in range(len(execs))}
        seg = os.path.join(slot_dirs[chosen.slot], "merge", "seg_1_0.bin")
        assert glob.glob(seg), f"seed={SEED}: {seg} missing"
        with open(seg, "r+b") as f:
            first = f.read(1)
            f.seek(0)
            f.write(bytes([first[0] ^ 0xFF]))

        reader1 = execs[0].get_reader(h1, 0, 4)
        keys1, _ = reader1.read_all()
        np.testing.assert_array_equal(np.sort(keys1), _expected(6),
                                      err_msg=f"seed={SEED}")
        m1 = reader1.metrics
        assert m1.merged_fallbacks >= 1, f"seed={SEED}: {m1}"

        # tenant 2 is untouched: all-merged serving, clean counters
        reader2 = execs[0].get_reader(h2, 0, 4)
        keys2, _ = reader2.read_all()
        np.testing.assert_array_equal(np.sort(keys2), _expected_t2(6),
                                      err_msg=f"seed={SEED}")
        m2 = reader2.metrics
        assert m2.merged_reads >= 1, f"seed={SEED}: {m2}"
        assert m2.merged_fallbacks == 0, f"seed={SEED}: {m2}"
        assert m2.checksum_failures == 0, f"seed={SEED}: {m2}"
        assert m2.failed_fetches == 0, f"seed={SEED}: {m2}"
        assert driver.driver.epoch_of(2) == 1, \
            f"seed={SEED}: tenant 2 re-executed under tenant 1's rot"
    finally:
        _shutdown(driver, execs)


def test_chaos_stale_cache_never_serves_dead_peer(tmp_path):
    """Executor loss mid-iteration: the reducer's warm location cache
    points at the dead peer. The fetch fails, recovery tombstones +
    recomputes, the loss BUMPS the epoch, and the re-synced snapshot
    never names the tombstoned slot — byte-identical output, no stale
    location served after invalidation."""
    if not WARM:
        pytest.skip("cold sweep: no cache to go stale")
    driver, execs = _cluster(tmp_path, fetch_retry_budget=1,
                             push_merge=False, **PY_DATAPLANE)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn)
        # superstep 1 (cold): warms the reducer's location cache
        got1 = _reduce_fn(execs[0], handle)
        np.testing.assert_array_equal(got1, _expected(6),
                                      err_msg=f"seed={SEED}")
        plane = execs[0].executor.location_plane
        assert plane.snapshot()["tables"] >= 1, f"seed={SEED}"
        assert driver.driver.epoch_of(1) == 1, f"seed={SEED}"
        # the victim dies between supersteps; the warm cache still names
        # its slot
        victim_slot = execs[2].executor.exec_index()
        execs[2].executor.server.stop()
        # superstep 2: the stale cache leads to a failed fetch — NEVER a
        # wrong result — and recovery repairs + invalidates
        got2 = run_reduce_with_retry(execs, handle, _map_fn, _reduce_fn,
                                     reducer_index=0, driver=driver)
        np.testing.assert_array_equal(got2, _expected(6),
                                      err_msg=f"seed={SEED}")
        # the loss bumped the epoch (pushed invalidation)
        assert driver.driver.epoch_of(1) > 1, f"seed={SEED}"
        # the re-synced view never names the tombstoned slot
        table = execs[0].executor.get_driver_table(1, 6, timeout=5)
        for m in range(6):
            assert table.entry(m)[1] != victim_slot, f"seed={SEED}"
        # superstep 3 over the repaired state: clean, still identical
        got3 = _reduce_fn(execs[0], handle)
        np.testing.assert_array_equal(got3, _expected(6),
                                      err_msg=f"seed={SEED}")
    finally:
        _shutdown(driver, execs)


def test_chaos_corrupt_reexecution_bumps_epoch_mid_iteration(tmp_path):
    """Corrupt-output healing mid-iteration: at-rest rot caught at serve
    time re-executes exactly the rotten map; the repair publish BUMPS
    the epoch so every reducer's warm cache refreshes — the next
    superstep reads the healed output under the new epoch,
    byte-identical."""
    if not WARM:
        pytest.skip("cold sweep: no cache to invalidate")
    driver, execs = _cluster(tmp_path, at_rest_checksum=True,
                             push_merge=False)
    injector = StorageFaultInjector(seed=SEED)
    injector.install()
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        # one committed output rots right after its commit attested it
        injector.add(CORRUPT_AT_REST, op="commit", times=1)
        run_map_stage(execs, handle, _map_fn)
        assert injector.fired_count(CORRUPT_AT_REST) == 1, f"seed={SEED}"
        # superstep 1 trips the serve-time check -> corrupt_output
        # verdict -> re-execution of exactly that map -> repair publish
        got = run_reduce_with_retry(execs, handle, _map_fn, _reduce_fn,
                                    reducer_index=0, driver=driver)
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        assert driver.driver.epoch_of(1) > 1, \
            f"seed={SEED}: corrupt re-execution did not bump the epoch"
        # superstep 2: warm under the NEW epoch, clean and identical
        got2 = _reduce_fn(execs[0], handle)
        np.testing.assert_array_equal(got2, _expected(6),
                                      err_msg=f"seed={SEED}")
        r = execs[0].get_reader(handle, 0, handle.num_partitions)
        keys, _ = r.read_all()
        assert r.metrics.failed_fetches == 0, f"seed={SEED}"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


def _skew_map_fn(writer, map_id):
    rng = np.random.default_rng(4000 + map_id)
    keys = np.where(rng.random(1500) < 0.7, 3,
                    rng.integers(0, 8, 1500)).astype(np.uint64)
    writer.write_batch(keys)


def _skew_expected(num_maps):
    parts = []
    for m in range(num_maps):
        rng = np.random.default_rng(4000 + m)
        parts.append(np.where(rng.random(1500) < 0.7, 3,
                              rng.integers(0, 8, 1500)).astype(np.uint64))
    return np.sort(np.concatenate(parts))


def test_chaos_replan_mid_stage_after_executor_loss(tmp_path):
    """The adaptive planner's mid-stage re-plan: a skewed shuffle plans
    into coalesced + split tasks placed across executors; one executor
    dies AFTER the first task completes. The lost maps recompute on
    survivors, the driver re-plans under a bumped plan epoch — completed
    tasks keep their ranges and results, only orphaned tasks re-assign —
    and the stage finishes with ZERO duplicate and ZERO lost rows
    (exact multiset equality against the fault-free ground truth)."""
    from sparkrdma_tpu.shuffle.recovery import run_planned_reduce

    driver, execs = _cluster(tmp_path, adaptive_plan=True,
                             push_merge=False,
                             coalesce_target_bytes=2048,
                             split_threshold_bytes=4096)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=8,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _skew_map_fn)
        # publishes are one-sided (no ack): plan only once the driver's
        # size histogram holds every map, or the plan is built from a
        # partial (or empty) histogram
        deadline = time.monotonic() + 5.0
        while (driver.driver.size_histogram(1).maps_recorded < 6
               and time.monotonic() < deadline):
            time.sleep(0.005)
        plan = driver.plan_reduce(handle)
        assert plan is not None and len(plan.tasks) >= 3, f"seed={SEED}"
        assert plan.counts()["split_partitions"] >= 1, f"seed={SEED}"

        victim_slot = execs[2].executor.exec_index()
        state = {"killed": False}

        def kill_after_first(task, slot):
            if not state["killed"]:
                state["killed"] = True
                execs[2].executor.server.stop()

        res = run_planned_reduce(execs, handle, _skew_map_fn, driver,
                                 on_task_done=kill_after_first)
        # zero lost, zero duplicate rows: exact multiset equality
        np.testing.assert_array_equal(np.sort(res.keys),
                                      _skew_expected(6),
                                      err_msg=f"seed={SEED}")
        assert state["killed"], f"seed={SEED}"
        # the loss forced at least one re-plan under a bumped epoch...
        assert res.plan.plan_epoch > plan.plan_epoch, f"seed={SEED}"
        assert driver.driver.plan_replans >= 1, f"seed={SEED}"
        # ...that kept every task's exact ranges (only placement moved)
        by_id = {t.task_id: t for t in res.plan.tasks}
        for t in plan.tasks:
            n = by_id[t.task_id]
            assert (n.start_partition, n.end_partition, n.map_start,
                    n.map_end) == (t.start_partition, t.end_partition,
                                   t.map_start, t.map_end), f"seed={SEED}"
        # completed ranges were never re-executed
        assert res.tasks_rerun == 0, f"seed={SEED}"
        # the repaired table no longer names the dead slot
        table = execs[0].executor.get_driver_table(1, 6, timeout=5)
        for m in range(6):
            assert table.entry(m)[1] != victim_slot, f"seed={SEED}"
    finally:
        _shutdown(driver, execs)


def test_chaos_device_plane_loss_degrades_to_host(tmp_path, monkeypatch):
    """Device-dataplane loss scenario: the cost model picks the fused
    ICI plane for an on-mesh stage, an executor dies MID-STAGE (its
    committed outputs vanish while staging is in flight), and the stage
    degrades onto the host dataplane — recovery recomputes the lost
    maps on survivors, the retry serves the stage through the fetcher,
    and the output is byte-identical to a fault-free run."""
    import jax
    from jax.sharding import Mesh

    from engine_helpers import make_cluster, u32_payload
    from sparkrdma_tpu.engine import DAGEngine, MapStage, ResultStage
    from sparkrdma_tpu.shuffle import fetcher as fetcher_mod
    from sparkrdma_tpu.shuffle import mesh_service
    from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency

    mesh = Mesh(np.array(jax.devices()[:8]), ("shuffle",))
    P, maps, rows, key_space = 4, 6, 400, 3000

    def map_fn(ctx, writer, task_id):
        rng = np.random.default_rng(5000 + SEED * 100 + task_id)
        keys = rng.integers(0, key_space, rows).astype(np.uint64)
        writer.write((keys, u32_payload(
            rng.integers(0, 1000, rows).astype(np.uint32))))

    holder = {"engine": None, "degraded": {}}

    def reduce_fn(ctx, task_id):
        keys, payload = ctx.read(0)._r.read_all()
        # observe the degrade while the stage is alive (teardown pops
        # the memo when run() returns)
        holder["degraded"].update(holder["engine"]._mesh_degraded)
        rowsb = np.concatenate(
            [keys.view(np.uint8).reshape(len(keys), 8),
             np.ascontiguousarray(payload)], axis=1)
        return rowsb[np.lexsort(rowsb.T[::-1])].tobytes()

    fetchers = {"n": 0}
    orig_init = fetcher_mod.ShuffleFetcher.__init__

    def spy(self, *a, **kw):
        fetchers["n"] += 1
        return orig_init(self, *a, **kw)

    monkeypatch.setattr(fetcher_mod.ShuffleFetcher, "__init__", spy)

    def run(label, chaos):
        driver, execs = make_cluster(tmp_path / label)
        try:
            # sequential tasks: the injection relies on the FIRST read
            # triggering the one mesh staging pass
            engine = holder["engine"] = DAGEngine(driver, execs,
                                                  mesh=mesh,
                                                  max_parallel_tasks=1)
            holder["degraded"] = {}
            state = {"fired": False}
            if chaos:
                # the INDEXED iterator is the one staging hook every
                # mesh reduce driver (one-shot, fused, hierarchical)
                # flows through — injecting here covers them all
                orig_iter = mesh_service._iter_committed_batches_indexed

                def chaos_iter(managers, handle, delivered=None):
                    for batch in orig_iter(managers, handle, delivered):
                        yield batch
                        if not state["fired"]:
                            # mid-staging: the victim dies and its
                            # committed outputs die with it
                            state["fired"] = True
                            victim = execs[1].native
                            mid = victim.executor.manager_id
                            victim.executor.stop()
                            driver.native.driver.remove_member(mid)
                            victim.resolver.remove_shuffle(
                                handle.shuffle_id)

                monkeypatch.setattr(
                    mesh_service, "_iter_committed_batches_indexed",
                    chaos_iter)
            stage = MapStage(maps, ShuffleDependency(
                P, PartitionerSpec("modulo"), row_payload_bytes=4),
                map_fn)
            out = engine.run(ResultStage(P, reduce_fn, parents=[stage]))
            if chaos:
                monkeypatch.setattr(
                    mesh_service, "_iter_committed_batches_indexed",
                    orig_iter)
            return out, engine, state
        finally:
            for ex in execs:
                ex.stop()
            driver.stop()

    clean_out, clean_engine, _ = run("clean", chaos=False)
    assert not holder["degraded"], f"seed={SEED}"
    before_fetchers = fetchers["n"]

    chaos_out, chaos_engine, state = run("kill", chaos=True)
    assert state["fired"], f"seed={SEED}: injection never ran"
    # the device plane was selected (staging ran), then the stage
    # degraded onto the host dataplane...
    assert list(holder["degraded"].values()) == \
        ["mid-stage executor loss"], f"seed={SEED}"
    assert not chaos_engine._mesh_degraded, \
        f"seed={SEED}: teardown leaked the degrade memo"
    assert fetchers["n"] > before_fetchers, \
        f"seed={SEED}: degrade never reached the host dataplane"
    # ...byte-identically
    assert chaos_out == clean_out, f"seed={SEED}"


# -- the wide sweep (chaos + slow; scripts/run_chaos.sh) -----------------


def _scenario_faults(name, injector, victim_addr):
    if name == "corrupt_1pct":
        injector.add(CORRUPT, msg_type=M.FetchBlocksResp, prob=0.01)
    elif name == "refuse_burst":
        injector.add(REFUSE_CONNECT, times=3)
        injector.add(REFUSE_CONNECT, after=10, times=2)
    elif name == "delay_storm":
        injector.add(DELAY, msg_type=M.FetchBlocksResp, delay_s=0.05,
                     prob=0.2)
    elif name == "flaky_victim":
        injector.add(DISCONNECT, peer=victim_addr,
                     msg_type=M.FetchBlocksResp, times=2)
        injector.add(DELAY, peer=victim_addr, msg_type=M.FetchOutputResp,
                     delay_s=0.03, prob=0.5)
    elif name == "mixed":
        injector.add(CORRUPT, msg_type=M.FetchBlocksResp, prob=0.02)
        injector.add(DELAY, msg_type=M.FetchBlocksResp, delay_s=0.02,
                     prob=0.1)
        injector.add(REFUSE_CONNECT, times=2)
    else:  # pragma: no cover - scenario list and matrix stay in sync
        raise AssertionError(name)


def _map_fn_big(writer, map_id):
    rng = np.random.default_rng(1000 + map_id)
    keys = rng.integers(0, 50_000, size=3000).astype(np.uint64)
    writer.write_batch(keys)


def _expected_big(num_maps):
    return np.sort(np.concatenate(
        [np.random.default_rng(1000 + m).integers(0, 50_000, 3000)
         for m in range(num_maps)]).astype(np.uint64))


@pytest.mark.slow
@pytest.mark.parametrize("scenario", ["corrupt_1pct", "refuse_burst",
                                      "delay_storm", "flaky_victim",
                                      "mixed"])
def test_chaos_matrix(tmp_path, scenario):
    """The sweep: ~a hundred small grouped fetches (tiny read block size,
    3000 rows per map) under probabilistic faults drawn from the seeded
    injector RNG. Replay a failure with
    ``CHAOS_SEED=<seed> pytest tests/test_chaos.py -m chaos``
    (the seed is in the assertion message)."""
    driver, execs = _cluster(tmp_path, shuffle_read_block_size=1024,
                             read_ahead_depth=4)
    injector = FaultInjector(seed=SEED)
    churn = None
    failover = None
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=8,
                                         partitioner=PartitionerSpec("modulo"))
        run_map_stage(execs, handle, _map_fn_big)
        if PUSHPLAN:
            # background planned pushes: the plan publishes now, so the
            # pushers race the faulted reduce below and staged ranges
            # resolve first at their planned slots
            assert driver.driver.build_reduce_plan(1) is not None, \
                f"seed={SEED}: PUSHPLAN sweep built no plan"
        victim_addr = (execs[2].executor.manager_id.rpc_host,
                       execs[2].executor.manager_id.rpc_port)
        injector.install_endpoint(execs[0].executor)
        _scenario_faults(scenario, injector, victim_addr)
        if ELASTIC:
            churn = _ElasticChurn(driver.conf, driver, tmp_path)
        if DRIVER:
            failover = _DriverFailover(driver)

        got = run_reduce_with_retry(execs, handle, _map_fn_big, _reduce_fn,
                                    reducer_index=0, max_stage_retries=3,
                                    driver=driver)
        np.testing.assert_array_equal(
            got, _expected_big(6),
            err_msg=f"scenario={scenario} seed={SEED}")
    finally:
        injector.uninstall()
        if churn is not None:
            churn.stop()
        if failover is not None:
            failover.stop()
        _shutdown(driver, execs)


# -- the storage-fault matrix (CHAOS_DISK sweep) --------------------------
#
# Every injected ENOSPC/EIO/torn-write/slow-disk/corrupt-at-rest scenario
# must end with byte-identical job output — via spill retry, fallback
# dir, or map re-execution — or a clean, fully-reaped task failure:
# never a hang, never a served torn/corrupt block.


def _disk_faults(name, injector):
    deterministic = True
    if name == "enospc_spill":
        # two failures, absorbed by retries (budget 2 = 3 attempts)
        injector.add(ENOSPC, op="spill_write", times=2)
    elif name == "eio_spill":
        injector.add(EIO, op="spill_write", prob=0.2)
        deterministic = False
    elif name == "torn_spill":
        injector.add(TORN_WRITE, op="spill_write", torn_bytes=32, times=2)
    elif name == "slow_disk":
        injector.add(SLOW_DISK, delay_s=0.01, prob=0.3)
        deterministic = False
    elif name == "corrupt_at_rest":
        injector.add(CORRUPT_AT_REST, op="commit", times=1)
    elif name == "mixed_disk":
        injector.add(ENOSPC, op="spill_write", times=1)
        injector.add(SLOW_DISK, op="spill_write", delay_s=0.005, prob=0.2)
        injector.add(CORRUPT_AT_REST, op="commit", times=1)
    else:  # pragma: no cover - scenario list and matrix stay in sync
        raise AssertionError(name)
    return deterministic


@pytest.mark.skipif(not DISK, reason="CHAOS_DISK=0: network-only sweep")
@pytest.mark.parametrize("scenario", ["enospc_spill", "eio_spill",
                                      "torn_spill", "slow_disk",
                                      "corrupt_at_rest", "mixed_disk"])
def test_chaos_disk_matrix(tmp_path, scenario):
    """Seeded storage faults under a real multi-executor job: small spill
    threshold (every map spills), a fallback spill dir, at-rest
    checksums on. Replay a failure with
    ``CHAOS_SEED=<seed> CHAOS_COALESCE=<0|1> pytest tests/test_chaos.py
    -m chaos -k disk``."""
    driver, execs = _cluster(
        tmp_path, spill_threshold_bytes="1k",
        spill_dirs=str(tmp_path / "fallback"),
        spill_retry_budget=2, at_rest_checksum=True)
    injector = StorageFaultInjector(seed=SEED)
    injector.install()
    churn = None
    failover = None
    try:
        deterministic = _disk_faults(scenario, injector)
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        # the map stage runs UNDER the faults: spill retries, fallback
        # dirs, and WriteFailedError re-placement all exercise here
        run_map_stage(execs, handle, _map_fn)
        if PUSHPLAN:
            # background planned pushes under storage faults: staging
            # spills cross the same injected EIO/ENOSPC/slow-disk shims
            assert driver.driver.build_reduce_plan(1) is not None, \
                f"seed={SEED}: PUSHPLAN sweep built no plan"
        if ELASTIC:
            churn = _ElasticChurn(driver.conf, driver, tmp_path)
        if DRIVER:
            failover = _DriverFailover(driver)
        got = run_reduce_with_retry(execs, handle, _map_fn, _reduce_fn,
                                    reducer_index=0, max_stage_retries=3,
                                    driver=driver)
        np.testing.assert_array_equal(
            got, _expected(6),
            err_msg=f"scenario={scenario} seed={SEED}")
        if deterministic:
            assert injector.fired_count() > 0, \
                f"scenario={scenario} seed={SEED}: no fault fired"
        # no attempt artifacts may outlive the job in ANY spill dir
        # (fallback dirs are namespaced per executor — walk recursively)
        leftovers = [str(p) for p in tmp_path.rglob("*.tmp")]
        assert leftovers == [], \
            f"scenario={scenario} seed={SEED}: leaked {leftovers}"
    finally:
        injector.uninstall()
        if churn is not None:
            churn.stop()
        if failover is not None:
            failover.stop()
        _shutdown(driver, execs)


@pytest.mark.skipif(not DISK, reason="CHAOS_DISK=0: network-only sweep")
def test_chaos_disk_total_failure_is_clean(tmp_path):
    """When every spill dir fails persistently, the job FAILS CLEANLY:
    WriteFailedError after re-placement on every live executor, no hang,
    and not one ``.tmp`` left anywhere. push_merge pinned off on
    purpose: its overflow rung would RESCUE the attempt by parking the
    spill on a peer (that behavior has its own test,
    test_push_merge.py::test_overflow_spill_survives_total_enospc) —
    this scenario exists to prove the failure is clean when nothing
    can rescue."""
    from sparkrdma_tpu.shuffle.writer import WriteFailedError

    driver, execs = _cluster(tmp_path, spill_threshold_bytes="1k",
                             spill_retry_budget=1, push_merge=False)
    injector = StorageFaultInjector(seed=SEED)
    injector.install()
    try:
        injector.add(EIO, op="spill_write")  # every attempt, every dir
        handle = driver.register_shuffle(1, num_maps=2, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        with pytest.raises(WriteFailedError):
            run_map_stage(execs, handle, _map_fn)
        leftovers = [str(p) for p in tmp_path.rglob("*.tmp")]
        assert leftovers == [], f"seed={SEED}: leaked {leftovers}"
    finally:
        injector.uninstall()
        _shutdown(driver, execs)


# -- elastic membership: the ROADMAP item 2 acceptance scenarios ----------
#
# A job starts on 4 executors, SCALES TO 8 mid-job (the planner places
# new maps on the joiners), DRAINS BACK TO 4 mid-reduce-stage, and the
# final output is byte-identical to the static-membership run with ZERO
# map re-executions on the planned drains (recovery.repoint-style
# accounting: the drained maps serve from merged replicas). A drainee
# dying mid-drain falls back to ordinary tombstone recovery and still
# completes byte-identically.


def _elastic_map_fn(counter):
    def map_fn(writer, map_id):
        counter[map_id] = counter.get(map_id, 0) + 1
        rng = np.random.default_rng(6000 + map_id)
        writer.write_batch(rng.integers(0, 9000, 300).astype(np.uint64))
    return map_fn


def _elastic_expected(num_maps):
    return np.sort(np.concatenate(
        [np.random.default_rng(6000 + m).integers(0, 9000, 300)
         for m in range(num_maps)]).astype(np.uint64))


def test_chaos_elastic_scale_up_drain_down_byte_identical(tmp_path):
    """4 -> 8 -> 4 with zero re-executions on the planned drains."""
    conf = _conf(push_merge=True, merge_replicas=2,
                 drain_deadline_ms=15000)
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [TpuShuffleManager(conf, driver_addr=driver.driver_addr,
                               executor_id=str(i),
                               spill_dir=str(tmp_path / f"e{i}"))
             for i in range(4)]
    for ex in execs:
        ex.executor.wait_for_members(4)
    joiners = []
    try:
        num_maps, num_parts = 8, 6
        handle = driver.register_shuffle(
            1, num_maps=num_maps, num_partitions=num_parts,
            partitioner=PartitionerSpec("modulo"))
        counter = {}
        map_fn = _elastic_map_fn(counter)

        # SCALE UP: 4 joiners announce mid-job; the map stage then
        # places work across all 8 (joiners included)
        for j in range(4):
            joiner = TpuShuffleManager(
                conf, driver_addr=driver.driver_addr,
                executor_id=f"j{j}", spill_dir=str(tmp_path / f"j{j}"))
            joiner.join_cluster()
            joiners.append(joiner)
        all_execs = execs + joiners
        for ex in all_execs:
            ex.executor.wait_for_members(8)
        assert len(driver.driver.membership.live_slots()) == 8
        ran = run_map_stage(all_execs, handle, map_fn)
        joiner_slots = sorted(
            j.executor.exec_index(timeout=2) for j in joiners)
        placed_on_joiners = [m for m, i in ran.items() if i >= 4]
        assert placed_on_joiners, "planner never placed on the joiners"
        for ex in all_execs:
            assert ex.pusher.drain(timeout=15)

        # mid-reduce-stage: read HALF the partitions on the full fleet
        first = _reduce_keys(all_execs[0], handle, 0, num_parts // 2)

        # DRAIN DOWN: gracefully decommission all 4 joiners — planned
        # retires, ZERO re-executions (the repoint accounting)
        for slot in sorted(joiner_slots, reverse=True):
            res = driver.driver.decommission_slot(slot)
            assert res["status"] == "drained", \
                f"seed={SEED} drain of slot {slot}: {res}"
        assert driver.driver.drains_completed == 4
        assert driver.driver.drain_fallbacks == 0
        for j in joiners:
            j.stop()
        joiners_alive = []

        # finish the stage on the shrunk fleet; retry envelope covers
        # any straggler still holding pre-drain cached locations
        def rest_fn(mgr, h):
            return _reduce_keys(mgr, h, num_parts // 2, num_parts)

        rest = run_reduce_with_retry(execs, handle, map_fn, rest_fn,
                                     reducer_index=0,
                                     max_stage_retries=3, driver=driver)
        got = np.sort(np.concatenate([first, rest]))
        np.testing.assert_array_equal(
            got, _elastic_expected(num_maps),
            err_msg=f"seed={SEED}: elastic run diverged from the "
                    "static-membership ground truth")
        assert sum(counter.values()) == num_maps, \
            (f"seed={SEED}: planned drains re-executed maps: {counter} "
             f"(joiner-placed: {placed_on_joiners})")
        joiners = joiners_alive
    finally:
        for j in joiners:
            j.stop()
        _shutdown(driver, execs)


def _reduce_keys(mgr, handle, start, end):
    keys, _ = mgr.get_reader(handle, start, end).read_all()
    return keys


def test_chaos_elastic_drainee_death_mid_drain_falls_back(tmp_path):
    """The drainee dies MID-drain (after DrainReq lands, before its
    replication pass answers): the decommission falls back to ordinary
    tombstone recovery, the reduce re-executes the lost maps, and the
    output stays byte-identical."""
    conf = _conf(push_merge=False)
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [TpuShuffleManager(conf, driver_addr=driver.driver_addr,
                               executor_id=str(i),
                               spill_dir=str(tmp_path / f"e{i}"))
             for i in range(3)]
    for ex in execs:
        ex.executor.wait_for_members(3)
    try:
        num_maps = 6
        handle = driver.register_shuffle(
            1, num_maps=num_maps, num_partitions=4,
            partitioner=PartitionerSpec("modulo"))
        counter = {}
        map_fn = _elastic_map_fn(counter)
        ran = run_map_stage(execs, handle, map_fn)
        victim = execs[2]
        victim_slot = victim.executor.exec_index(timeout=2)
        owned = [m for m, i in ran.items() if i == 2]
        assert owned

        # die mid-drain: the DrainReq handler kills the executor's
        # servers instead of replicating, so no DrainResp ever arrives
        orig = victim.executor._drain_replicate

        def die_mid_drain(deadline):
            victim.executor.stop()
            if victim.block_server is not None:
                victim.block_server.stop()
            raise RuntimeError("drainee died mid-drain")

        victim.executor._drain_replicate = die_mid_drain
        res = driver.driver.decommission_slot(victim_slot,
                                              deadline_ms=3000)
        assert res["status"] == "fallback", f"seed={SEED}: {res}"
        assert driver.driver.drain_fallbacks == 1
        from sparkrdma_tpu.parallel.membership import SLOT_DEAD
        assert driver.driver.membership.state_of(victim_slot) == SLOT_DEAD

        got = run_reduce_with_retry(execs[:2], handle, map_fn, _reduce_fn,
                                    reducer_index=0, max_stage_retries=3,
                                    driver=driver)
        np.testing.assert_array_equal(
            got, _elastic_expected(num_maps),
            err_msg=f"seed={SEED}: fallback run diverged")
        # tombstone recovery re-executed exactly the drainee's maps
        assert sum(counter.values()) == num_maps + len(owned), \
            f"seed={SEED}: {counter}"
    finally:
        _shutdown(driver, execs)


# -- driver HA: the kill -9 acceptance scenario ---------------------------
#
# The primary driver runs in its OWN PROCESS holding a file-backed lease
# and gets SIGKILLed at a seeded random point after the map outputs have
# replicated to a warm in-test standby. The standby must CAS-take the
# next lease term within the lease TTL, replay its shadowed op log, and
# re-point the executors — and the job must complete byte-identically
# with ZERO map re-executions: the map outputs live on the executors,
# so losing the driver may cost a wait, never a recompute.

_PRIMARY_CHILD = r"""
import json, os, sys, time
from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.parallel.endpoints import DriverEndpoint
from sparkrdma_tpu.shuffle.ha import FileLeaseStore

conf = TpuShuffleConf(**json.loads(sys.argv[1]))
ep = DriverEndpoint(conf, host="127.0.0.1",
                    lease_store=FileLeaseStore(sys.argv[2]),
                    lease_holder="primary")
ep.register_shuffle(7, num_maps=4, num_partitions=4)
with open(sys.argv[3] + ".tmp", "w") as f:
    json.dump({"host": ep.server.host, "port": ep.server.port,
               "pid": os.getpid()}, f)
os.replace(sys.argv[3] + ".tmp", sys.argv[3])
while True:  # hold the lease until SIGKILL
    time.sleep(0.5)
"""


def test_chaos_driver_sigkill_failover_zero_reexecutions(tmp_path):
    conf_kw = dict(connect_timeout_ms=2000, max_connection_attempts=1,
                   retry_backoff_base_ms=20, retry_backoff_cap_ms=150,
                   pre_warm_connections=False, use_cpp_runtime=False,
                   ha_standbys=1, driver_lease_ms=800,
                   request_deadline_ms=20_000)
    conf = TpuShuffleConf(**conf_kw)
    lease_path = str(tmp_path / "lease.json")
    addr_path = str(tmp_path / "driver_addr.json")
    child_src = tmp_path / "primary_child.py"
    child_src.write_text(_PRIMARY_CHILD)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, str(child_src), json.dumps(conf_kw), lease_path,
         addr_path], env=env, cwd=repo_root)
    standby = None
    execs = []
    try:
        deadline = time.monotonic() + 60
        while not os.path.exists(addr_path):
            assert proc.poll() is None, \
                f"seed={SEED}: primary child died at startup"
            assert time.monotonic() < deadline, \
                f"seed={SEED}: primary child never published its address"
            time.sleep(0.05)
        with open(addr_path) as f:
            info = json.load(f)
        addr = (info["host"], info["port"])

        standby = DriverStandby(conf, FileLeaseStore(lease_path),
                                "standby-1", primary_addr=addr).start()
        execs = [TpuShuffleManager(conf, driver_addr=addr,
                                   executor_id=str(i),
                                   spill_dir=str(tmp_path / f"e{i}"))
                 for i in range(2)]
        for ex in execs:
            ex.executor.wait_for_members(2)

        handle = ShuffleHandle(7, 4, 4, 0, PartitionerSpec("modulo"))
        map_runs = []
        runs_lock = threading.Lock()

        def map_fn(writer, map_id):
            with runs_lock:
                map_runs.append(map_id)
            rng = np.random.default_rng(1000 + map_id)
            writer.write_batch(
                rng.integers(0, 5000, size=500).astype(np.uint64))

        run_map_stage(execs, handle, map_fn)
        # all four publishes are on the primary; wait until the standby's
        # shadowed op log has gone QUIET having heard them — nothing
        # mutates driver state after the map stage, so a stable ingest
        # seq means the async replication stream has fully drained and a
        # kill at any later instant loses no op
        table, _ = execs[0].executor.get_driver_table_v(
            7, expect_published=4, timeout=10)
        assert table.num_published == 4, f"seed={SEED}"
        stable_since, last_seen = time.monotonic(), standby._last
        while time.monotonic() - stable_since < 0.5:
            assert time.monotonic() < deadline, \
                f"seed={SEED}: standby never caught up"
            time.sleep(0.05)
            if standby._last != last_seen:
                stable_since, last_seen = time.monotonic(), standby._last
        assert last_seen[1] > 0, f"seed={SEED}: standby heard no ops"

        # reducers launch, then the primary dies at a seeded random
        # point inside the reduce window: reducers that already synced
        # never notice; the rest ride the DriverClient retry envelope
        # into the promoted standby
        results = {}

        def reduce_one(i):
            reader = execs[i].get_reader(handle, 0, 4)
            keys, _ = reader.read_all()
            results[i] = np.sort(keys)

        threads = [threading.Thread(target=reduce_one, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        time.sleep(np.random.default_rng(SEED + 990).random() * 0.2)
        os.kill(proc.pid, signal.SIGKILL)
        t_kill = time.monotonic()
        proc.wait(timeout=10)

        # takeover within the lease TTL (the remaining TTL at kill time
        # is at most one driver_lease_ms; the watcher polls at TTL/4,
        # promotion itself is bounded by replay) + scheduling grace
        while standby.endpoint is None:
            assert time.monotonic() - t_kill < \
                conf.driver_lease_ms / 1000 + 1.0, \
                f"seed={SEED}: standby never took the lease"
            time.sleep(0.02)
        new_primary = standby.endpoint
        assert new_primary.incarnation >= 1, f"seed={SEED}"

        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), \
            f"seed={SEED}: a reducer hung across the failover"
        expected = _expected(4)
        for i in range(2):
            np.testing.assert_array_equal(
                results[i], expected,
                err_msg=f"seed={SEED}: reducer {i} diverged after kill -9")
        # ZERO re-executions: losing the driver costs a wait, never a
        # recompute — every map ran exactly once
        assert sorted(map_runs) == [0, 1, 2, 3], \
            f"seed={SEED}: map re-executions after failover: {map_runs}"
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)
        for ex in execs:
            ex.stop()
        if standby is not None:
            standby.stop()


# -- partitioned metadata ownership: the shard-owner kill acceptance ------
#
# A shard OWNER is metadata-only infrastructure: killing it mid-stage
# must cost a per-shard handoff (standby log replay + republish
# backstop), never a map re-execution. The victim here owns shard 0's
# fence CAS but holds ZERO map outputs (placement pins the data on the
# other executors), so any re-execution in this scenario would be the
# control plane LOSING a publish — exactly the bug class the handoff
# protocol exists to rule out.


def test_chaos_shard_owner_kill_mid_publish_zero_reexecutions(tmp_path):
    """Kill the owner of shard 0 while the map stage's publishes are
    streaming at it (a seeded point after its first applied write). The
    stragglers bounce to the driver-direct path, the driver hands the
    shard to a successor, and the reduce completes byte-identical with
    ZERO map re-executions — the driver table never lost a publish."""
    driver, execs = _cluster(tmp_path, n=4, metadata_shards=2,
                             shard_ownership=True,
                             shard_batch_entries=64,  # unconverged tail
                             push_merge=False)
    map_runs = []
    killer = None
    done = threading.Event()
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        smap = None
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and smap is None:
            smv = execs[0].executor.location_plane.shard_map_v(1)
            smap = smv[0] if smv is not None else None
            time.sleep(0.02)
        assert smap is not None, f"seed={SEED}: no shard map pushed"
        victim_slot = smap.shard_slots[0]
        victim_idx = next(i for i, ex in enumerate(execs)
                          if ex.executor.exec_index() == victim_slot)
        survivors = [i for i in range(len(execs)) if i != victim_idx]

        def kill_on_first_applied():
            victim_ep = execs[victim_idx].executor
            while (victim_ep.shard_owner.applied == 0
                   and not done.wait(0.002)):
                pass
            if done.is_set():
                return
            victim_ep.stop()  # abrupt: applied writes left unconverged
            driver.driver.remove_member(victim_ep.manager_id)

        killer = threading.Thread(target=kill_on_first_applied)
        killer.start()
        # the victim hosts METADATA only: every map output lives on the
        # survivors, so the owner kill can never justify a recompute
        run_map_stage(execs, handle, _map_fn,
                      placement={m: survivors[m % len(survivors)]
                                 for m in range(6)})
        killer.join(timeout=10)
        assert not killer.is_alive(), f"seed={SEED}: killer hung"
        assert execs[victim_idx].executor.shard_owner.applied > 0, \
            f"seed={SEED}: the victim never owned a publish"
        deadline = time.monotonic() + 8
        while (time.monotonic() < deadline
               and driver.driver.shard_handoffs == 0):
            time.sleep(0.05)
        assert driver.driver.shard_handoffs >= 1, f"seed={SEED}"

        def counting_map_fn(writer, map_id):
            map_runs.append(map_id)
            _map_fn(writer, map_id)

        live = [execs[i] for i in survivors]
        got = run_reduce_with_retry(live, handle, counting_map_fn,
                                    _reduce_fn, reducer_index=0,
                                    max_stage_retries=3, driver=driver)
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        assert map_runs == [], \
            (f"seed={SEED}: shard-owner death re-executed maps "
             f"{map_runs} — a publish was lost in the handoff")
        smv2 = execs[survivors[0]].executor.location_plane.shard_map_v(1)
        assert smv2 is not None and victim_slot not in smv2[0].shard_slots, \
            f"seed={SEED}: the dead owner still holds a shard"
    finally:
        done.set()
        if killer is not None:
            killer.join(timeout=10)
        _shutdown(driver, execs)


# -- the cold tier: full-fleet loss under the blob-fault matrix -----------
#
# The disaggregated tier's acceptance scenario class (CHAOS_COLD=1): the
# ENTIRE fleet dies after map finalize + tier upload, and a fresh fleet
# must reduce byte-identically from the blob store — under a SEEDED
# matrix of blob faults on both the upload path (outages, torn uploads,
# at-rest rot — segments degrade to hot-only or publish rotten blobs
# the restore CRC must catch) and the restore path (outages, slow
# store). Whatever the faults ate, the answer is byte-identical: cold
# restore where coverage survived, re-execution where it didn't.


@pytest.mark.skipif(not COLD, reason="CHAOS_COLD=0: cold tier inert")
def test_chaos_cold_full_fleet_loss_under_blob_faults(tmp_path):
    from sparkrdma_tpu.parallel.faults import (BLOB_CORRUPT, BLOB_SLOW,
                                               BLOB_UNAVAILABLE,
                                               TORN_UPLOAD,
                                               BlobFaultInjector)
    from sparkrdma_tpu.shuffle.cold_tier import wait_for_tiered_coverage
    from sparkrdma_tpu.shuffle.push_merge import wait_for_coverage

    driver, execs = _cluster(tmp_path, n=3, **PY_DATAPLANE)
    inj = BlobFaultInjector(seed=SEED)
    inj.install()
    fresh = []
    counter = {}

    def map_fn(writer, map_id):
        counter[map_id] = counter.get(map_id, 0) + 1
        _map_fn(writer, map_id)

    try:
        # upload-side faults: some puts fail outright, some land short
        # (must never become visible), some commit then rot at rest
        inj.add(BLOB_UNAVAILABLE, op="put", prob=0.15)
        inj.add(TORN_UPLOAD, op="put", prob=0.1, torn_bytes=32)
        inj.add(BLOB_CORRUPT, op="put", prob=0.15, flip_bits=3)

        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec(
                                             "modulo"))
        run_map_stage(execs, handle, map_fn)
        for ex in execs:
            assert ex.pusher.drain(15), f"seed={SEED}"
        assert wait_for_coverage(driver.driver, 1, 6, 4, timeout=15), \
            f"seed={SEED}"
        for ex in execs:
            if ex.executor.tiering is not None:
                assert ex.executor.tiering.drain(20), f"seed={SEED}"
        # coverage is best-effort under upload faults — whatever tiered,
        # tiered; the job must not care either way
        wait_for_tiered_coverage(driver.driver, 1, 6, 4, timeout=2)

        # the spot-market event: the ENTIRE fleet is gone
        mids = [ex.executor.manager_id for ex in execs]
        for ex in execs:
            ex.stop()
        for mid in mids:
            driver.driver.remove_member(mid)

        # restore-side faults: a blinking, slow store
        inj.add(BLOB_UNAVAILABLE, op="get", prob=0.15)
        inj.add(BLOB_SLOW, op="get", prob=0.3, delay_s=0.01)

        conf = _conf(cold_tier=True,
                     cold_tier_path=str(tmp_path / "cold"),
                     push_merge=True, **PY_DATAPLANE)
        fresh = [TpuShuffleManager(conf, driver_addr=driver.driver_addr,
                                   executor_id=f"f{i}",
                                   spill_dir=str(tmp_path / f"f{i}"))
                 for i in range(3)]
        from sparkrdma_tpu.parallel.endpoints import TOMBSTONE
        for ex in fresh:
            ex.executor.wait_for_members(6)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                members = ex.executor.members()
                if all(members[s] == TOMBSTONE for s in range(3)):
                    break
                time.sleep(0.02)

        got = run_reduce_with_retry(fresh, handle, map_fn, _reduce_fn,
                                    reducer_index=0, max_stage_retries=8,
                                    driver=driver)
        np.testing.assert_array_equal(
            got, _expected(6),
            err_msg=f"seed={SEED}: cold restore diverged under blob "
                    f"faults (fired: {dict(inj.fired)})")
        # every map ran at least once (the original stage) and only
        # AS re-executions where the fault matrix destroyed coverage
        assert all(n >= 1 for n in counter.values()), \
            f"seed={SEED}: {counter}"
    finally:
        inj.uninstall()
        _shutdown(driver, fresh if fresh else execs)


@pytest.mark.skipif(not COLD, reason="CHAOS_COLD=0: cold tier inert")
def test_chaos_cold_store_outage_degrades_to_hot_only(tmp_path):
    """The blob store is DOWN for the entire job: every upload fails
    its whole retry budget, nothing tiers, and the job must not notice
    — tiering never fails a job (the graceful-degradation half of the
    acceptance)."""
    from sparkrdma_tpu.parallel.faults import (BLOB_UNAVAILABLE,
                                               BlobFaultInjector)

    driver, execs = _cluster(tmp_path, n=3, **PY_DATAPLANE)
    inj = BlobFaultInjector(seed=SEED)
    inj.install()
    try:
        inj.add(BLOB_UNAVAILABLE)  # every op, every time: store DOWN
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec(
                                             "modulo"))
        run_map_stage(execs, handle, _map_fn)
        for ex in execs:
            assert ex.pusher.drain(15), f"seed={SEED}"
        from sparkrdma_tpu.shuffle.push_merge import wait_for_coverage
        assert wait_for_coverage(driver.driver, 1, 6, 4, timeout=15), \
            f"seed={SEED}"
        for ex in execs:
            if ex.executor.tiering is not None:
                assert ex.executor.tiering.drain(20), f"seed={SEED}"
        got = _reduce_fn(execs[0], handle)
        np.testing.assert_array_equal(got, _expected(6),
                                      err_msg=f"seed={SEED}")
        snaps = [ex.executor.tiering.snapshot() for ex in execs
                 if ex.executor.tiering is not None]
        assert snaps, f"seed={SEED}: no tiering service installed"
        assert all(s["uploads_done"] == 0 for s in snaps), \
            f"seed={SEED}: {snaps}"
        assert sum(s["uploads_failed"] for s in snaps) > 0, \
            f"seed={SEED}: {snaps}"
        directory = driver.driver.tiered_directory(1)
        assert directory is None or len(directory) == 0, f"seed={SEED}"
    finally:
        inj.uninstall()
        _shutdown(driver, execs)
