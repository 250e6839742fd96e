"""Run one cell of ``BENCHMARK.json`` once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process per run: resolve the cell's files, set the system up from the
seed, warm one unit (set-up), then run units back to back in a closed
loop for ``--seconds`` — a shuffle's caller waits for it — and print the
result as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``. With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profile of the window's
first units and from the program's spans.

``correct`` is decided outside the window: the last unit's whole output
against the plain reference, the configuration's guarantees on every
unit, and zero compiles inside the window. Without a TPU (or with fewer
chips than the cell asks for) nothing is printed and the exit code is
non-zero. ``--rehearsal`` runs the same logic at the traffic file's toy
size on 4 virtual CPU devices, for the sandbox: its metrics are named
``rehearsal.<name>``, so no CPU number stands under a device metric's name.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()   # before the heavy imports: set-up

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import drivers, manifest, readers, xplane  # noqa: E402

SCRATCH = os.path.join(ROOT, ".bench_scratch")
DEFAULT_CACHE_DIR = os.path.join(ROOT, ".jax_cache")
REHEARSAL_DEVICES = 4
MAX_RAISED_UNITS = 3   # a unit that raises thrice has poisoned the window


class CompileLog:
    """Backend compiles since process start, from jax's own monitoring
    events (a persistent-cache hit still counts: something was built
    for a shape the warm-up did not cover)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1


def _cpu_seconds() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def _start_jax(rehearsal: bool):
    if rehearsal:
        flag = "--xla_force_host_platform_device_count"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                f"{os.environ.get('XLA_FLAGS', '')} "
                f"{flag}={REHEARSAL_DEVICES}").strip()
    import jax

    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
        return jax
    # the directory is part of the cache's key: the operator's, or a fixed
    # one inside this checkout. Every program is cached, however short its
    # compile, so that only a checkout's first run compiles.
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def run_window(jax, workload, seconds: float, traced_units: int,
               profile_dir):
    """Units back to back until ``seconds`` have passed; with a
    ``profile_dir`` the first ``traced_units`` run under the profiler.
    Returns ``(units, cpu_s)``; a unit that raised is ``None``."""
    units: list = []
    raised = 0
    profiling = profile_dir is not None
    if profiling:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # it would slow the host's work
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    cpu0 = _cpu_seconds()
    t_start = time.perf_counter()
    try:
        while True:
            with jax.profiler.TraceAnnotation(xplane.UNIT_SPAN):
                try:
                    units.append(workload.run_unit())
                except Exception:  # the boundary: count it, carry on
                    traceback.print_exc()
                    units.append(None)
                    raised += 1
            cpu1 = _cpu_seconds()
            if profiling and len(units) >= traced_units:
                jax.profiler.stop_trace()
                profiling = False
            if raised >= MAX_RAISED_UNITS:
                break
            if not profiling and time.perf_counter() - t_start >= seconds:
                break
    finally:
        if profiling:
            jax.profiler.stop_trace()
    return units, cpu1 - cpu0


def end_to_end(done: list, unit_bytes: int, chips: int, cpu_s: float,
               setup_s: float) -> dict:
    """Every end-to-end quantity the harness takes itself, by name."""
    makespan = statistics.median(u["end"] - u["start"] for u in done)
    gb = unit_bytes * len(done) / 1e9
    return {
        "step_makespan_s": makespan,
        "job_makespan_s": makespan,
        # all completed units over all the time they took, gaps included
        "shuffle_gbps_per_chip":
            gb / (done[-1]["end"] - done[0]["start"]) / chips,
        "host_cpu_s_per_gb": cpu_s / gb,
        "setup_s": setup_s,
    }


def judge(workload, units: list, compiles: int):
    """Outside the window: the guarantees of every unit, then the last
    unit's whole output against the plain reference. Returns ``(done,
    failed, notes)``; the run is correct when nothing failed and there
    is nothing to note."""
    done = [u for u in units if u is not None]
    failed = len(units) - len(done)
    notes: list = []
    for i, unit in enumerate(done):
        broken = workload.unit_problems(unit)
        if broken:
            failed += 1
            notes.append(f"unit {i}: {broken}")
    if compiles:
        notes.append(f"{compiles} compiles inside the window")
    if units[-1] is not None:
        notes += workload.verify_last()
    return done, failed, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy size on 4 virtual CPU devices; every metric "
                         "is named rehearsal.<name>")
    args = ap.parse_args(argv)
    me = "benchmark/run.py"

    cell = manifest.load_cell(manifest.load_manifest(), args.workload)
    driver = drivers.load(cell.config["kind"])
    jax = _start_jax(args.rehearsal)
    devs = jax.devices()
    if not args.rehearsal and devs[0].platform != "tpu":
        print(f"{me}: no TPU: jax's default backend is "
              f"{devs[0].platform!r}; nothing ran", file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"{me}: cell {cell.name} asks for {cell.chips} chips, "
              f"jax has {len(devs)}; nothing ran", file=sys.stderr)
        return 1
    devs = devs[:cell.chips]

    compile_log = CompileLog()
    compile_log.install()
    sizes = dict(cell.traffic)
    if args.rehearsal:
        sizes.update(sizes.get("rehearsal", {}))
    os.makedirs(SCRATCH, exist_ok=True)
    profile_dir = None
    if args.trace:
        profile_dir = os.path.join(SCRATCH, f"profile_{cell.name}")
        shutil.rmtree(profile_dir, ignore_errors=True)

    workload = driver.Workload(cell.config, sizes, devs, args.seed, SCRATCH)
    try:
        for _ in range(sizes.get("warm_units", 1)):
            broken = workload.unit_problems(workload.run_unit())
            if broken:
                raise RuntimeError(f"the warm unit broke a guarantee: "
                                   f"{broken}")
        compiles_before = compile_log.compiles
        setup_s = time.perf_counter() - _PROCESS_START
        units, cpu_s = run_window(jax, workload, args.seconds,
                                  sizes.get("traced_units", 3), profile_dir)
        done, failed, notes = judge(
            workload, units, compile_log.compiles - compiles_before)
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devs]
    finally:
        workload.close()
    if not done:
        print(f"{me}: no unit completed", file=sys.stderr)
        return 1

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(peak) if all(peak) else None}
    line = {"correct": not notes and not failed, "attempted": len(units),
            "failed": failed}
    if args.trace:
        reduced = xplane.reduce_trace(
            xplane.load_xplane(xplane.find_xplane(profile_dir)),
            [d.id for d in devs])
        if not args.rehearsal and (reduced is None
                                   or not reduced["busy_s"] > 0):
            print(f"{me}: the trace shows no operation on the device",
                  file=sys.stderr)
            return 1
        reading = readers.Reading(done, reduced, workload.info,
                                  devs[0].device_kind)
        values = {m["name"]: readers.read_metric(spec, reading)
                  for m, spec in cell.per_layer}
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            line["breakdown"] = xplane.breakdown(reduced)
    else:
        e2e = end_to_end(done, workload.unit_bytes, cell.chips, cpu_s,
                         setup_s)
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in cell.end_to_end}
    prefix = "rehearsal." if args.rehearsal else ""
    line["metrics"] = {prefix + k: v for k, v in values.items()
                       if v is not None}
    line["device"] = device
    spans = sorted(u["end"] - u["start"] for u in done)
    print(f"{me}: {cell.name} seed {args.seed}: {len(done)} units of "
          f"{spans[0]:.4f} / {statistics.median(spans):.4f} / "
          f"{spans[-1]:.4f} s (min / median / max)", file=sys.stderr)
    for note in notes:
        print(f"{me}: {note}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
