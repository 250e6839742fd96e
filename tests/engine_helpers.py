"""Shared helpers for the DAG-engine test suites (test_engine.py,
test_engine_mesh.py): row codecs, deterministic tables, and the 3-executor
in-process compat cluster."""

import numpy as np

from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.shuffle.spark_compat import SparkCompatShuffleManager

CONF_KW = dict(connect_timeout_ms=1000, max_connection_attempts=2)
CONF = TpuShuffleConf(**CONF_KW)


def u32_payload(values) -> np.ndarray:
    return np.ascontiguousarray(values, dtype="<u4").view(np.uint8).reshape(-1, 4)


def payload_u32(payload: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(payload).view("<u4").ravel()


def make_table(seed: int, rows: int, key_space: int):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, key_space, size=rows).astype(np.uint64)
    vals = rng.integers(0, 1000, size=rows).astype(np.uint32)
    return keys, vals


def make_cluster(tmp_path, n: int = 3, **conf_kw):
    """(driver, executors) with membership settled; caller stops them.
    ``conf_kw``: config keys laid over ``CONF``'s."""
    conf = TpuShuffleConf(**CONF_KW, **conf_kw) if conf_kw else CONF
    driver = SparkCompatShuffleManager(conf, isDriver=True)
    execs = [SparkCompatShuffleManager(
        conf, driverAddr=driver.driverAddr, executorId=str(i),
        spill_dir=str(tmp_path / f"e{i}")) for i in range(n)]
    for ex in execs:
        ex.native.executor.wait_for_members(n)
    return driver, execs


def lockgraph_module_guard():
    """Shared body of the CHAOS_LOCKGRAPH module fixtures
    (tests/test_chaos.py, tests/test_membership.py): install the
    lock-order shim, snapshot pre-existing cycles (a session-wide
    ANALYSIS_LOCKGRAPH shim shares the graph — blame only cycles that
    appear DURING the module), and on teardown fail on any new cycle.
    Generator: fixtures drive it with ``yield from``."""
    from sparkrdma_tpu.analysis import lockgraph

    owned = lockgraph.current() is None
    graph = lockgraph.install()
    pre = {tuple(c) for c in graph.cycles()}
    yield
    if owned:
        lockgraph.uninstall()
    new = [c for c in graph.cycles() if tuple(c) not in pre]
    assert not new, graph.format_cycles()
