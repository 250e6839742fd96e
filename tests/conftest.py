"""Test harness: run everything on an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; XLA's host platform can be
split into N virtual devices, which exercises the same SPMD partitioner and
collective lowering paths the TPU backend uses. This stands in for the
multi-node cluster runs the reference was only ever validated on
(reference: no src/test at all — see SURVEY.md §4).

The platform is pinned through jax's config as well as whatever
``JAX_PLATFORMS`` the caller exported, so a bare ``pytest tests/`` on a
host with a chip never takes it — backends initialize lazily, so this
takes effect as long as it runs before any ``jax.devices()`` call.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: longer than the tier-1 wall-clock budget on a CPU host; "
        "excluded by the default `-m 'not slow'` run, exercised "
        "explicitly and on hardware rounds")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection scenario (parallel/faults.py); "
        "fast ones run in tier-1, the wide sweep is chaos+slow and "
        "driven by scripts/run_chaos.sh across CHAOS_SEED values")
    # ANALYSIS_LOCKGRAPH=1: run the whole session under the lock-order
    # shim (sparkrdma_tpu/analysis/lockgraph.py). Every lock the package
    # creates during the run is tracked; a lock-order cycle fails the
    # session at exit (scripts/run_analysis.sh --lockgraph drives this).
    global _lockgraph
    if os.environ.get("ANALYSIS_LOCKGRAPH", "0") not in ("0", "false", ""):
        from sparkrdma_tpu.analysis import lockgraph

        _lockgraph = lockgraph.install()


_lockgraph = None


def pytest_sessionfinish(session, exitstatus):
    if _lockgraph is None:
        return
    from sparkrdma_tpu.analysis import lockgraph

    lockgraph.uninstall()
    cycles = _lockgraph.cycles()
    if cycles:
        import sys

        print("\n" + _lockgraph.format_cycles(), file=sys.stderr)
        session.exitstatus = 3
    else:
        print(f"\nlockgraph: acyclic "
              f"({len(_lockgraph.edges())} distinct orderings)")
