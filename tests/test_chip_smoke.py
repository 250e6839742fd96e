"""The chip entry points refuse to pass without the chip, the compile
cache goes where it is told, and the smoke's own job builders are right
at toy size — checked here so chip time is not spent finding out."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env):
    full = dict(os.environ, PYTHONPATH=REPO, **env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_chip_entry_points_fail_without_a_tpu(script):
    """An inherited JAX_PLATFORMS=cpu must fail the run, not pass it on
    the CPU: non-zero exit, one line saying why, no summary."""
    proc = _run([os.path.join(REPO, script)], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr.strip().splitlines()[-1]


def test_verdict_line_has_the_contract_keys_and_no_others():
    """The driver's check reads the last line of standard output and
    refuses any key beyond these; the jobs' facts go on the line before."""
    import chip_smoke

    devs = jax.devices()
    for failures in ([], ["job_a: x"]):
        line = json.loads(json.dumps(chip_smoke.verdict(failures, devs)))
        assert line == {"ok": not failures,
                        "device": {"platform": devs[0].platform,
                                   "kind": devs[0].device_kind,
                                   "count": len(devs)}}
        assert isinstance(line["device"]["kind"], str)
        assert type(line["device"]["count"]) is int


def test_compile_cache_default_ignores_the_working_directory(tmp_path):
    code = ("from sparkrdma_tpu.utils.compile_cache import "
            "enable_compile_cache as e; import jax; "
            "print(e()); print(jax.config.jax_compilation_cache_dir)")
    seen = set()
    for name in ("a", "b"):
        cwd = tmp_path / name
        cwd.mkdir()
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, text=True,
            env=dict(env, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
            capture_output=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        returned, configured = proc.stdout.split()
        assert returned == configured
        seen.add(returned)
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, jax has already read it; the
    helper sets no directory in code."""
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.append(key))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    assert enable_compile_cache() == "/placed/outside"
    assert "jax_compilation_cache_dir" not in updates


def test_smoke_jobs_at_toy_size_on_the_cpu_mesh():
    """Job A against numpy_terasort, and Job B's device- and host-plane
    runs byte-identical to each other and to the numpy sort, on the
    8-device CPU mesh (gather transport, a one-shot plan at this size)."""
    import chip_smoke

    mesh = Mesh(np.array(jax.devices()[:8]), (chip_smoke.AXIS,))
    log = chip_smoke.CompileLog()
    rec, failures = chip_smoke.run_job_a(mesh, 1 << 20, 3, log)
    assert failures == [] and rec["verified"], (failures, rec)
    assert rec["collective_exchanges"] >= 1  # read from DATA_PLANE

    rec, failures = chip_smoke.run_job_b(mesh, 2 << 20, 3, log)
    assert failures == [], failures
    assert rec["verified"] and rec["identical_to_host_plane"]
    assert rec["plan"]["plane"] == "device"
    assert rec["tcp_fetchers_built"] == 0 and rec["degrade_instants"] == 0
    assert rec["collective_exchanges"] >= 1
    assert rec["host_plane"]["tcp_fetchers_built"] > 0
    json.dumps(rec)  # the record must be printable as the summary


def test_smoke_pagerank_job_at_toy_size_on_the_cpu_mesh():
    """Job C: one PageRank job at the cell's degree against the float64
    reference, on four CPU devices; its facts are a record for the
    report line, and ``verdict`` has no key for them."""
    import chip_smoke

    mesh = Mesh(np.array(jax.devices()[:4]), (chip_smoke.AXIS,))
    rec, failures = chip_smoke.run_job_c(mesh, 1 << 14, 2**31 + 3,
                                         chip_smoke.CompileLog())
    assert failures == [] and rec["verified"], (failures, rec)
    assert rec["edges"] == 4 << 14 and rec["iterations"] == 3
    assert rec["vertices"] == 4 * ((1 << 14) * 468_750 // 16_777_216)
    assert rec["contributions_received"] == [4 << 14] * 3
    assert 0 < rec["recv_fill"] <= 1 and rec["ranks_on_device"]
    assert rec["against_reference"]["bound_share"] < 1
    json.dumps(rec)
    assert set(chip_smoke.verdict([], jax.devices()[:1])) == {"ok", "device"}
