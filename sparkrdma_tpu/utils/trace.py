"""Chrome-trace-format span tracer.

The reference's "tracing" is hand-rolled wall-clock logging
(RdmaNode.java:309-310 connection timing; RdmaShuffleManager.scala:353-354,
397-398 table read/write latencies; per-fetch histograms). This upgrades
that to structured spans any engineer can open in ``chrome://tracing`` /
Perfetto: writer spill, commit, publish, location reads, grouped fetches,
staging, exchange rounds — each a timed event with thread identity.

Enabled by the ``trace_file`` config key; zero overhead when off (the
module-level NULL tracer's span() is a no-op context manager).

One call site, two clocks: a live tracer's ``span`` also enters a
``jax.profiler.TraceAnnotation`` of the same name, so whenever a profiler
session is running (``device_profile``, or anyone's ``jax.profiler``
trace) the program's spans lie in the profile beside the device's ops.
With no session an annotation is one flag read. Every ``Tracer`` of a
process counts from one origin, so the dumps of a driver's and its
executors' tracers overlay in Perfetto.

A span also says what its thread did: the thread's user and kernel CPU
seconds, page faults and context switches inside the block, and the whole
process's CPU seconds meanwhile (``ACCOUNTING_ARGS``, in the event's
``args``); wall less CPU is time the thread was off the CPU.
``complete_span``, ``instant`` and ``counter`` events carry none.
"""

from __future__ import annotations

import json
import os
import resource
import threading
import time
from contextlib import contextmanager
from typing import List

# the one origin of every Tracer's clock in this process
_ORIGIN = time.perf_counter()

# what a live tracer's span adds to its event's args, from the thread's
# rusage at both ends of the block: reserved, a caller passes none of them
ACCOUNTING_ARGS = ("cpu_user_s", "cpu_sys_s", "minflt", "majflt", "nvcsw",
                   "nivcsw", "proc_cpu_s")
# Linux's; where it is missing the spans carry no accounting
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``. Imported here, so only a
    live tracer's first span pays for importing jax and the no-op tracer
    never does."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class Tracer:
    MAX_EVENTS = 1_000_000  # ~300 MB of JSON; beyond this, count drops

    def __init__(self, process_name: str = "sparkrdma_tpu"):
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self.process_name = process_name
        self.enabled = True
        self.dropped = 0

    def _now_us(self) -> float:
        return (time.perf_counter() - _ORIGIN) * 1e6

    @contextmanager
    def span(self, name: str, category: str = "shuffle", **args):
        """Time the block as one chrome event and, under a running
        profiler session, as a ``TraceAnnotation`` of the same name.
        Yields the event's ``args`` dict: a block that learns a size only
        at its end (the rows a ``next()`` returned) fills it in there.
        At the block's end the args gain ``ACCOUNTING_ARGS``: what the
        thread (``RUSAGE_THREAD``) and, in ``proc_cpu_s``, the process
        spent between the block's two ends."""
        if not self.enabled:
            yield args
            return
        if not args.keys().isdisjoint(ACCOUNTING_ARGS):
            raise ValueError(
                f"span {name!r}: {sorted(set(args) & set(ACCOUNTING_ARGS))} "
                "are the tracer's own accounting args")
        if _RUSAGE_THREAD is not None:
            ru0 = resource.getrusage(_RUSAGE_THREAD)
            proc0 = time.process_time()
        start = self._now_us()
        try:
            with _annotation(name):
                yield args
        finally:
            end = self._now_us()
            if _RUSAGE_THREAD is not None:
                ru1 = resource.getrusage(_RUSAGE_THREAD)
                args.update(
                    cpu_user_s=ru1.ru_utime - ru0.ru_utime,
                    cpu_sys_s=ru1.ru_stime - ru0.ru_stime,
                    minflt=ru1.ru_minflt - ru0.ru_minflt,
                    majflt=ru1.ru_majflt - ru0.ru_majflt,
                    nvcsw=ru1.ru_nvcsw - ru0.ru_nvcsw,
                    nivcsw=ru1.ru_nivcsw - ru0.ru_nivcsw,
                    proc_cpu_s=time.process_time() - proc0)
            with self._lock:
                if len(self._events) >= self.MAX_EVENTS:
                    self.dropped += 1
                else:
                    self._events.append({
                        "name": name, "cat": category, "ph": "X",
                        "ts": start, "dur": end - start,
                        "pid": os.getpid(), "tid": threading.get_ident(),
                        "args": args,
                    })

    def now_us(self) -> float:
        """Current trace-clock timestamp, for ``complete_span``: async
        callers stamp boundaries as they happen (issue, wire landing,
        completion) and emit the spans afterwards — a context manager
        can't bracket work whose two ends live on different threads."""
        return self._now_us()

    def complete_span(self, name: str, category: str, start_us: float,
                      end_us: float, **args) -> None:
        """Record a span with explicit trace-clock endpoints (from
        ``now_us``). Used by the pipelined fetcher to emit separate
        issue→wire→complete phases of one asynchronous fetch. Tracer-only:
        an annotation cannot be entered after the fact, so these spans
        are in the chrome dump and in no profile."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) >= self.MAX_EVENTS:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "cat": category, "ph": "X",
                "ts": start_us, "dur": max(0.0, end_us - start_us),
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": args,
            })

    def counter(self, name: str, value: float,
                category: str = "fault") -> None:
        """Chrome "C"-phase counter sample: running totals (retries,
        suspicions) render as a stepped series that lines up against the
        fetch spans, so "retry burst at t=..." is visible next to the
        fetches it delayed."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) >= self.MAX_EVENTS:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "cat": category, "ph": "C",
                "ts": self._now_us(), "pid": os.getpid(),
                "args": {"value": value},
            })

    def instant(self, name: str, category: str = "shuffle", **args) -> None:
        if not self.enabled:
            return
        with self._lock:
            if len(self._events) >= self.MAX_EVENTS:
                self.dropped += 1
                return
            self._events.append({
                "name": name, "cat": category, "ph": "i", "s": "t",
                "ts": self._now_us(), "pid": os.getpid(),
                "tid": threading.get_ident(), "args": args,
            })

    def dump(self, path: str) -> int:
        """Write chrome trace JSON; returns event count."""
        with self._lock:
            events = list(self._events)
        meta = [{"name": "process_name", "ph": "M", "pid": os.getpid(),
                 "args": {"name": self.process_name,
                          "dropped_events": self.dropped}}]
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events,
                       "displayTimeUnit": "ms"}, f)
        return len(events)


@contextmanager
def device_profile(log_dir: str):
    """The operator's one call for "a device profile with the program's
    spans in it": an XLA profile (``.xplane.pb`` under ``log_dir``, for
    TensorBoard / Perfetto) of the block, holding the device's ops and,
    on the host plane, every span a live ``Tracer`` records meanwhile.
    The Python tracer is off: it would slow the host work being
    measured. A profiler that cannot start or stop raises: a run that
    asked for a profile must not pass for one that has it.
    """
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=options):
        yield


class _NullTracer(Tracer):
    def __init__(self):
        super().__init__()
        self.enabled = False


NULL = _NullTracer()


def get(conf=None) -> Tracer:
    """A live tracer when conf.trace_file is set, else the no-op tracer."""
    if conf is not None and getattr(conf, "trace_file", ""):
        return Tracer()
    return NULL
